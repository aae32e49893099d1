"""`ray_tpu.train.bringup.session` begins (`init_session` on every worker) -> rank 0 entered
`train_fn` (its `ray_tpu.train.worker.first_report` begins): the mesh build included."""

from benchmark.harness import bringup

META = {
    "name": "entry.session_start_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.session_start_s if b else None

"""The placement group ready and the gang's actors up: `ray_tpu.train.bringup.placement` +
`ray_tpu.train.bringup.spawn` (asked for -> every worker's `metadata()` back), the driver's spans."""

from benchmark.harness import bringup

META = {
    "name": "entry.spawn_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.spawn_s if b else None

"""Inside `_start_jax`: `ray_tpu.train.worker.distributed_init` (`jax.distributed.initialize`
blocks until the whole gang joined), the slowest rank. Nothing where one worker is the gang."""

from benchmark.harness import bringup

META = {
    "name": "entry.gang_join_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.gang_join_s if b else None

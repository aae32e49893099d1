"""All of `_backend.on_start` (`ray_tpu.train.bringup.backend`): the chip grant's round and
`_start_jax` on every worker (import of jax, gang join, first device touch)."""

from benchmark.harness import bringup

META = {
    "name": "entry.backend_start_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.backend_start_s if b else None

"""Inside `_start_jax`: `ray_tpu.train.worker.import_jax` + `ray_tpu.train.worker.device_touch`
(the first `jax.local_devices()`: libtpu opens the chip), the slowest rank."""

from benchmark.harness import bringup

META = {
    "name": "entry.device_touch_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.device_touch_s if b else None

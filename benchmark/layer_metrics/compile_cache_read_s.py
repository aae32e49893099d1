"""Seconds rank 0 spent reading executables from the persistent compile cache over the `fit()`
(`/jax/compilation_cache/cache_retrieval_time_sec`; 0 on a cold run)."""

from benchmark.harness import bringup

META = {
    "name": "compile.cache_read_s",
    "unit": "s",
    "better": "lower",
    "source": "program_counter",
    "layer": "compile",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.compile.get("cache_read_s") if b else None

"""Median of the loop's span around `shard_batch` / `host_local_to_global`."""

META = {
    "name": "host.h2d_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "program_span",
    "layer": "host phases",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["span_ms_per_step"].get("h2d")

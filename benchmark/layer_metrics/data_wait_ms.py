"""Median of the loop's span around `next(batches)`: what a step waits for
`ray_tpu.data` -> `get_dataset_shard` -> `iter_batches`."""

META = {
    "name": "data.wait_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "program_span",
    "layer": "data",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["span_ms_per_step"].get("data_wait")

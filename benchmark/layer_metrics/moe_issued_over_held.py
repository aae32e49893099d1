"""Rows of products the grouped-matmul kernels issue for the held pairs of the reference check's
routing (`ops/grouped_matmul.py issued_rows` at each kernel's block size, three products forward
and for both gradients, counted in the worker by the model file's `check`) over nine times the
held pairs: 1 is no row multiplied that the routing did not ask for; `router width / held` (8)
would be every pair multiplied, those of experts held elsewhere too."""

META = {
    "name": "moe.issued_over_held",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("routing", {}).get("issued_over_held")

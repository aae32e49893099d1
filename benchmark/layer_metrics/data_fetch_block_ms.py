"""One block pull as the program times it: `ray_tpu.data.next_bundle` (the
coordinator's round trip) plus `ray_tpu.data.fetch_block` (the object
plane's), median over the pulls inside the traced steps. A reading: one pull
in 8 steps at 64-row blocks and 8-row batches; nothing when none fell inside."""

from statistics import median

from benchmark.harness import program_trace

META = {
    "name": "data.fetch_block_ms",
    "unit": "ms/block",
    "better": "lower",
    "source": "program_span",
    "layer": "data",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    if program is None:
        return None
    pulls, asked = [], None
    for name, _, dur, _ in program.spans:  # by start: a pull is one of each, in this order
        if name == "ray_tpu.data.next_bundle":
            asked = dur
        elif name == "ray_tpu.data.fetch_block" and asked is not None:
            pulls.append((asked + dur) / 1e6)
            asked = None
    return median(pulls) if pulls else None

"""One block pull as the program times it: `ray_tpu.data.next_bundle` (the
coordinator's round trip) plus `ray_tpu.data.fetch_block` (the object
plane's), median over the pulls inside the traced steps; nothing when none
fell inside. So the entry lists a cell only where the mix's geometry puts a
pull into every window of 8 traced steps (`lfm2-24b-a2b-ep8-l5.fed4k`: every
second step). Where a block lasts eight steps it does not: a packed block
holds a row or several more than `block_rows` (the document that crosses its
end), every so often a pull comes a step late, and a window between two
holds none: 63 of 5,475 windows of `gpt2-medium.fed` over twelve seeds,
36 of 10,224 of `olmoe-1b-7b-l1.fed4k`, none of LFM2's 2,160 (PR 50;
`tests/benchmark/test_benchmark_traffic.py` walks the generator's own blocks
and holds the list to it)."""

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

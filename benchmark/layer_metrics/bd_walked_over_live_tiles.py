"""Tile pairs of the doubled row's score matrix that a program of the flash kernels walked in the traced steps, over the tile
pairs that hold a kept score. Walked: the `tiles_<walked>of<all>` component of each traced flash kernel's `op_name`, which
`ops/flash_attention.py` writes from the schedule it hands the kernel (the grid's second axis). Live: counted from the mask's
table by the model file (`live_tile_pairs`, in the check's summary by the number of tile pairs of the square, so each kernel is
held against the count at its own tiles). The largest over the kernels: 1.0 is no empty pair walked; the causal walk of the
same row would read 272 / 160 = 1.7, every pair 3.2. Nothing where the run has no trace, no flash kernel under such a scope,
or the check reports no mask."""

import re

from benchmark.harness import program_trace, xplane

META = {
    "name": "bd.walked_over_live_tiles",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "block diffusion",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    live = run["summary"]["check"].get("block_diffusion", {}).get("live_tiles_of_all")
    program = program_trace.of(run)
    if not live or program is None or not program.trace.devices:
        return None
    ratios = []
    for name in {op[0] for op in program.trace.devices[0]["ops"] if op[2] == xplane.MOSAIC_TARGET}:
        path = program.scopes.get(name, "").split("/")
        walk = [re.fullmatch(r"tiles_(\d+)of(\d+)", part) for part in path]
        if any(part.startswith(program_trace.FLASH_PREFIX) for part in path) and any(walk):
            walked, of_all = next(m for m in walk if m).groups()
            if of_all in live:
                ratios.append(int(walked) / live[of_all])
    return max(ratios) if ratios else None

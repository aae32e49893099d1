"""Query heads a program of the pair-streamed forward flash kernel takes (`ops/flash_attention.py _fwd_pairs_plan`): the
`group_<n>` component that `_fwd_pairs` writes into each traced `flash_fwd` call's `op_name`, the least over the calls. Which
plan a group of 7 query heads on one key/value head got: 7 is the whole group a program (k, v and the mask made once for it),
14 or 28 two or four groups with their key/value heads, 1 a head a program (12 % slower where it was measured: PERF.md
section 6, PR 44). Nothing where no flash kernel of the traced program carries such a component."""

import re

from benchmark.harness import program_trace, xplane

META = {
    "name": "attn.heads_a_program",
    "unit": "count",
    "better": "higher",
    "source": "program_counter",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return None
    heads = []
    for op in program.trace.devices[0]["ops"]:
        parts = program.scopes.get(op[0], "").split("/") if op[2] == xplane.MOSAIC_TARGET else ()
        if program_trace.FLASH_FWD in parts:
            heads += [int(m.group(1)) for m in (re.fullmatch(r"group_(\d+)", part) for part in parts) if m]
    return min(heads) if heads else None

"""Blocks of 128 keys that the flash kernels' schedules walk under the window over the blocks the band needs: the
`keys_<scored>of<walked>` component of each traced window call's `op_name`, which `ops/flash_attention.py` writes from the
schedule it hands the kernel: walked is every key block of every tile pair of the walk, scored those inside the pairs' live
spans, which is what the band needs at that grain. Walked over scored, the largest over the kernels: 1.0 is a walk whose tiles
hold the band and nothing else; 720 / 600 = 1.2 at a window of 2,048 under 512 x 1,024 tiles. What a tile that follows the
window would bring down (ROADMAP B6). Nothing where no flash kernel runs under a window kind's scope."""

import re

from benchmark.layer_metrics import kernels_flash_window_ms

META = {
    "name": "swa.walked_over_live_blocks",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "window attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    ratios = []
    for path in kernels_flash_window_ms.flash_calls(run).values():
        counts = [re.fullmatch(r"keys_(\d+)of(\d+)", part) for part in path.split("/")]
        for scored, walked in (m.groups() for m in counts if m):
            if int(scored):
                ratios.append(int(walked) / int(scored))
    return max(ratios) if ratios else None

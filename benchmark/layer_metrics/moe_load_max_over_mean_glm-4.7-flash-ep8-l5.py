"""`moe.load_max_over_mean` in `glm-4.7-flash-ep8-l5.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import moe_load_max_over_mean as listed

META = {**listed.META, "name": "moe.load_max_over_mean.glm-4.7-flash-ep8-l5"}
read = listed.read

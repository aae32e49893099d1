"""`moe.load_max_over_mean` in `keye-vl-2.0-30b-a3b-ep8.fed16k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import moe_load_max_over_mean as listed

META = {**listed.META, "name": "moe.load_max_over_mean.keye-vl-2.0-30b-a3b-ep8"}
read = listed.read

"""`moe.load_max_over_mean` in `sdar-30b-a3b-chat-ep8.fed8k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import moe_load_max_over_mean as listed

META = {**listed.META, "name": "moe.load_max_over_mean.sdar-30b-a3b-chat-ep8"}
read = listed.read

"""`moe.issued_over_held` in `glm-4.7-flash-ep8-l5.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import moe_issued_over_held as listed

META = {**listed.META, "name": "moe.issued_over_held.glm-4.7-flash-ep8-l5"}
read = listed.read

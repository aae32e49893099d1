"""`host.stall_pct` in `olmoe-1b-7b-l1.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import host_stall_pct as listed

META = {**listed.META, "name": "host.stall_pct.olmoe-1b-7b-l1"}
read = listed.read

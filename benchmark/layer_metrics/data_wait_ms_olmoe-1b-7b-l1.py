"""`data.wait_ms` in `olmoe-1b-7b-l1.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import data_wait_ms as listed

META = {**listed.META, "name": "data.wait_ms.olmoe-1b-7b-l1"}
read = listed.read

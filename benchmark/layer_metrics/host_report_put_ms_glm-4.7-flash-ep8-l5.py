"""`host.report_put_ms` in `glm-4.7-flash-ep8-l5.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import host_report_put_ms as listed

META = {**listed.META, "name": "host.report_put_ms.glm-4.7-flash-ep8-l5"}
read = listed.read

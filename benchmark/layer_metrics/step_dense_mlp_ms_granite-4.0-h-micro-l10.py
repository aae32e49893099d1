"""`step.dense_mlp_ms` in `granite-4.0-h-micro-l10.fed4k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own, until a
`benchmark` PR puts the cell on that entry's list and deletes this file."""

from benchmark.layer_metrics import step_dense_mlp_ms as listed

META = {**listed.META, "name": "step.dense_mlp_ms.granite-4.0-h-micro-l10"}
read = listed.read

"""`moe.dispatch_ms` in `sdar-30b-a3b-chat-ep8.fed8k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import moe_dispatch_ms as listed

META = {**listed.META, "name": "moe.dispatch_ms.sdar-30b-a3b-chat-ep8"}
read = listed.read

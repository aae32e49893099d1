"""`kernels.gmm_roofline` in `sdar-30b-a3b-chat-ep8.fed8k`: that entry lists its cells and a later cell cannot
append itself, so the cell brings the same reading under a name of its own."""

from benchmark.layer_metrics import kernels_gmm_roofline as listed

META = {**listed.META, "name": "kernels.gmm_roofline.sdar-30b-a3b-chat-ep8"}
read = listed.read

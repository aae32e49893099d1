"""The least time the chip could take for one step's stream mixing: the bytes any implementation must move (the model
file's `mhc_mix_bytes_per_step`, from shapes: each sublayer's streams read once and written once a pass beside u and y,
forward and backward, the streams in bf16; no recomputation) over peak HBM bandwidth (its one product has 24 columns:
bandwidth is its bound), over `mhc.mix_ms`, the time under the scope `mhc`: the same work whether XLA's fusions or a
kernel run it. Nothing where the model file counts no such bytes or the program has no such scope."""

import importlib

from benchmark.layer_metrics import mhc_mix_ms

META = {
    "name": "mhc.mix_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "residual streams",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    took = mhc_mix_ms.under(run, "mhc")
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    count = getattr(model, "mhc_mix_bytes_per_step", None)
    if count is None:
        return None
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floor_s = count(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s * 1e3 / took

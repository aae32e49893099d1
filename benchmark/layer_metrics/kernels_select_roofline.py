"""The least time the chip could take for one step's `select` kernel, the larger of FLOPs / peak and bytes / peak HBM
bandwidth (the model file's `select_flops_per_step` and `select_bytes_per_step`: what the mathematics asks for), over
`kernels.select_ms`."""

from benchmark.layer_metrics import kernels_select_ms

META = {
    "name": "kernels.select_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    took = kernels_select_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floors = (model.select_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
              model.select_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(floors) * 1e3 / took

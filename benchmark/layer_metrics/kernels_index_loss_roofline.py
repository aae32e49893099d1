"""The least time the chip could take for one step's `index_loss` kernel, the larger of FLOPs / peak and bytes / peak HBM
bandwidth (the model file's `index_loss_flops_per_step` and `index_loss_bytes_per_step`: what the mathematics asks for), over
`kernels.index_loss_ms`."""

from benchmark.layer_metrics import kernels_index_loss_ms

META = {
    "name": "kernels.index_loss_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    took = kernels_index_loss_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floors = (model.index_loss_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
              model.index_loss_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(floors) * 1e3 / took

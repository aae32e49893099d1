"""The least time the chip could take for one step's state-space duality scans, the larger of FLOPs / peak and bytes /
peak HBM bandwidth (the model file's `ssd_flops_per_step` and `ssd_bytes_per_step`: what the chunked form's mathematics
asks for at the chunk the configuration declares, C B^T once a group; a product made a head is the kernel's choice and
not counted), over `kernels.ssd_ms` (the scan's time, read here as that reader reads it)."""

from benchmark.layer_metrics import kernels_ssd_ms

META = {
    "name": "kernels.ssd_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    took = kernels_ssd_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floors = (model.ssd_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
              model.ssd_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(floors) * 1e3 / took

"""The least time the chip could take for one step's attention under the window, the larger of FLOPs / peak and bytes / peak
HBM bandwidth (the model file's `flash_window_flops_per_step` and `flash_window_bytes_per_step`: the band's kept scores,
counted from the mask and not from the tiles, so what a walk scores of a crossed tile beyond the band is no work), over
`kernels.flash_window_ms`, those calls' kernels' time. Nothing where the model file counts no window."""

from benchmark.layer_metrics import kernels_flash_window_ms

META = {
    "name": "kernels.flash_window_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    took = kernels_flash_window_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    if not hasattr(model, "flash_window_flops_per_step"):
        return None
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floors = (model.flash_window_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
              model.flash_window_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(floors) * 1e3 / took

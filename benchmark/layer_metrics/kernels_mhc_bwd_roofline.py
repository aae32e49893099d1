"""The least time the chip could take for one step's `mhc_post_bwd` and `mhc_pre_bwd` kernels: the bytes the two must move
(the model file's `mhc_bwd_bytes_per_step`, from shapes: every operand read once and every result written once, the
streams in bf16; their products have 20 and 24 columns, so bandwidth is their bound) over peak HBM bandwidth, over
`kernels.mhc_bwd_ms` (the two kernels' time, read here as that reader reads it)."""

from benchmark.layer_metrics import kernels_mhc_bwd_ms

META = {
    "name": "kernels.mhc_bwd_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    took = kernels_mhc_bwd_ms.read(run)
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floor_s = model.mhc_bwd_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s * 1e3 / took

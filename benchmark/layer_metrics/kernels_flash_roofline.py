"""The least time the chip could take for one step's attention, the larger of
FLOPs / peak and bytes / peak HBM bandwidth (both from shapes, by the model
file; causal: half the square), over the time the flash kernels took: the
`took` of `kernels.flash_ms` (`program_trace.flash_ms`: the Mosaic calls named
`flash_*`, and no Mosaic kernel of another name). `bound(run)` says which of
the two binds."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.flash_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    took = program_trace.flash_ms_of(run)
    if not took or run["peaks"] is None:
        return None
    return 100.0 * max(_floors(run)) * 1e3 / took


def _floors(run):
    import importlib

    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    return (model.flash_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
            model.flash_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])


def bound(run) -> str:
    compute, memory = _floors(run)
    return "compute" if compute >= memory else "memory"

"""The least time the chip could take for one step's `gdn_fwd` and `gdn_bwd` kernels, the larger of FLOPs / peak and
bytes / peak HBM bandwidth (the model file's `gdn_flops_per_step` and `gdn_bytes_per_step`: what the chunked form's
mathematics asks for at the chunk the kernels declare, the doubling behind `T` and the kept states not counted), over
`kernels.gdn_ms` (the two kernels' time, read here as that reader reads it)."""

from benchmark.harness import program_trace

META = {
    "name": "kernels.gdn_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "kernels",
    "moves": "tokens_per_s_per_chip"
}
KERNELS = ("gdn_fwd", "gdn_bwd")  # `kernels.gdn_ms`'s


def read(run):
    import importlib

    program = program_trace.of(run)
    took = [program.kernel(name) for name in KERNELS] if program else []
    if not took or None in took or run["peaks"] is None:
        return None
    took = sum(took)
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floors = (model.gdn_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
              model.gdn_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])
    return 100.0 * max(floors) * 1e3 / took

"""The least time the chip could take for one step's `conv_mix`: the bytes it must move (the
model file's `conv_mix_bytes_per_step`, from shapes: forward and backward, in bf16; no
recomputation, which the compiled step does not run) over peak HBM bandwidth (it multiplies no
matrix, so bandwidth is its only bound), over
`conv.mix_ms`, the time under the scope. Nothing where the model file counts no such bytes or
the program has no such scope."""

import importlib

from benchmark.harness import scope_trace

META = {
    "name": "conv.mix_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "short convolution",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    took = scope_trace.scope_ms(run, ("conv_mix",))
    if not took or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    count = getattr(model, "conv_mix_bytes_per_step", None)
    if count is None:
        return None
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    floor_s = count(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"]
    return 100.0 * floor_s * 1e3 / took

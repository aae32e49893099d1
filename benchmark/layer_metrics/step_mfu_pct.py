"""Model FLOP/s utilisation over the traced steps: the FLOPs forward and
backward require per token (the model file's arithmetic: matmul parameters,
full-square attention, no recomputation) x tokens per second of the traced
window / (chips x peak)."""

META = {
    "name": "step.mfu_pct",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "step",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    import importlib

    trace = run["device_trace"]
    if not trace or not trace.window_s or run["peaks"] is None:
        return None
    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    tokens_per_s = trace.host_steps * batch["global_rows"] * batch["seq"] / trace.window_s
    flops = model.train_flops_per_token(run["config"], batch["seq"]) * tokens_per_s
    return 100.0 * flops / (run["summary"]["device"]["count"] * run["peaks"]["bf16_flops_per_s"])

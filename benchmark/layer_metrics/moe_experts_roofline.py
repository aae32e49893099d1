"""The least time the chip could take for one step's expert matmuls, the larger
of FLOPs / peak and bytes / peak HBM bandwidth (both from shapes, by the model
file: the routed `tokens x k` pairs alone, no padding of a group to a tile, no
recomputation), over `moe.experts_ms`, the time under the scope `experts`. By
scope, so it reads whatever computes the experts."""

from benchmark.harness import scope_trace

META = {
    "name": "moe.experts_roofline",
    "unit": "%",
    "better": "higher",
    "source": "device_trace",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    took = scope_trace.scope_ms(run, ("experts",))
    if not took or run["peaks"] is None:
        return None
    return 100.0 * max(_floors(run)) * 1e3 / took


def _floors(run):
    import importlib

    model = importlib.import_module("benchmark.models." + run["config"]["model"])
    batch = run["config"]["batch"]
    rows = batch["global_rows"] // run["summary"]["device"]["count"]
    return (model.moe_expert_flops_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["bf16_flops_per_s"],
            model.moe_expert_bytes_per_step(run["config"], rows, batch["seq"]) / run["peaks"]["hbm_bytes_per_s"])


def bound(run) -> str:
    compute, memory = _floors(run)
    return "compute" if compute >= memory else "memory"

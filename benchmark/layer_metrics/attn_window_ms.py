"""Device time per step of the attention of the window layers of `models/trinity.py`: the operations whose `op_name` has
the stack's scope `attention` and a window kind's own (`window`, `dense_window`) as components, the two flash kernels under
the window's mask and whatever XLA puts round them, forward and backward together (under "save_attn" the call is not made
again). The busy union inside each traced step, the median over the steps: `scope_trace.scope_ms`'s arithmetic with a pick
of two scopes at once (`kind_ms`, which `attn.full_ms` reads too). Nothing where the program has no such scopes."""

from statistics import median

from benchmark.harness import program_trace, xplane

META = {
    "name": "attn.window_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "window attention",
    "moves": "tokens_per_s_per_chip"
}
WINDOW_KINDS, FULL_KINDS = ("window", "dense_window"), ("full", "dense_full")


def under(path, kinds, within):
    """Whether an `op_name` lies under `within` and under one of the `kinds`."""
    parts = path.split("/")
    return within in parts and any(kind in parts for kind in kinds)


def kind_ms(run, kinds, within="attention"):
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return None
    trace, dev = program.trace, program.trace.devices[0]
    mine = [(op[4], op[4] + op[5]) for op in trace._leaf_ops(dev) if under(program.scopes.get(op[0], ""), kinds, within)]
    runs = trace.step_runs(dev)
    if not mine or not runs:
        return None
    return median(xplane.measure(xplane.union(xplane.clip(mine, start, start + dur))) for _, _, start, dur in runs) / 1e6


def read(run):
    return kind_ms(run, WINDOW_KINDS)

"""Device time of each traced step, by scope: what varies from step to step of one run.

    python3 tools/steps_by_scope.py benchmark/out/<cell>.<seed> router dispatch,combine experts select index_loss

after a `--trace 1` run of that cell and seed, in the same chip call (the machine is thrown away). Prints the device
milliseconds of each of the traced steps, then for each argument (scopes joined by a comma are one group) the busy
union of the operations under it inside each step, then the flash kernels (and the gated delta rule's, `gdn_fwd` and `gdn_bwd`, where the program has them: they lie under
the scope `gdn`, so name that scope to keep them out of the last line) and everything under none of them.
`tools/scope_table.py` gives the medians by instruction; this gives the steps side by side: PR 42 found with it that a
rate spreading by 0.67 % between seeds was the expert layer alone (18-44 ms a step by the routing's draw) while every
other part of the step was constant to 0.01 ms (PERF.md section 6)."""
import json
import sys

sys.path.insert(0, ".")
from benchmark.harness import program_trace, xplane  # noqa: E402

stem, groups = sys.argv[1], [tuple(arg.split(",")) for arg in sys.argv[2:]]
trace = xplane.Trace(json.load(open(stem + ".trace.json")))
program = program_trace.of({"summary": {"trace_table": stem + ".trace.json"}, "device_trace": trace})
dev = trace.devices[0]
runs = trace.step_runs(dev)
parts = lambda op: program.scopes.get(op[0], "").split("/")  # noqa: E731
flash = lambda op: op[2] == xplane.MOSAIC_TARGET and any(p.startswith("flash_") for p in parts(op))  # noqa: E731


def per_step(pick):
    ops = [(op[4], op[4] + op[5]) for op in trace._leaf_ops(dev) if pick(op)]
    return [round(xplane.measure(xplane.union(xplane.clip(ops, s, s + d))) / 1e6, 2) for _, _, s, d in runs]


print("step device ms", [round(d / 1e6, 2) for _, _, _, d in runs])
for group in groups:
    print(group, per_step(lambda op, group=group: set(group) & set(parts(op))))
print("flash kernels", per_step(flash))
gdn = lambda op: op[2] == xplane.MOSAIC_TARGET and any(p in ("gdn_fwd", "gdn_bwd") for p in parts(op))  # noqa: E731
if any(gdn(op) for op in trace._leaf_ops(dev)):  # the gated delta rule's two kernels, where the program has them
    print("gdn kernels", per_step(gdn))
named = {scope for group in groups for scope in group}
print("everything else", per_step(lambda op: not named & set(parts(op)) and not flash(op)))

"""Device time per step under the scope `mhc` of `ops/hyper_connections.py` and `models/xing4.py`: the residual path of
several streams, everything between a sublayer's input streams and its output streams that is not the sublayer
(the maps' norm and product, the Sinkhorn rounds, both mixes, the streams' first copy and last sum), forward,
recomputation and backward together, every sublayer of the step. `under(run, *components)`: the busy union of the
operations whose `op_name` holds every one of `components` (`scope_trace.scope_ms` takes any of its scopes: `maps` alone
could be another program's name); a scope opened outside `blocks` stands in its `op_name` as `jvp(mhc)` or
`transpose(jvp(mhc))` and counts as `mhc`. Nothing where the program has no such scope."""

import re
from statistics import median

from benchmark.harness import program_trace, xplane

META = {
    "name": "mhc.mix_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "residual streams",
    "moves": "tokens_per_s_per_chip"
}


def _components(op_name):
    return {re.sub(r"^(?:\w+\()+|\)+$", "", part) for part in op_name.split("/")}


def under(run, *components):
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return None
    trace, dev = program.trace, program.trace.devices[0]
    mine = [(op[4], op[4] + op[5]) for op in trace._leaf_ops(dev)
            if set(components) <= _components(program.scopes.get(op[0], ""))]
    runs = trace.step_runs(dev)
    if not mine or not runs:
        return None
    return median(xplane.measure(xplane.union(xplane.clip(mine, start, start + dur)))
                  for _, _, start, dur in runs) / 1e6


def read(run):
    return under(run, "mhc")

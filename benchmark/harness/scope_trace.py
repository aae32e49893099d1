"""Device time per step of what the program put under a `jax.named_scope`:
what the readers of one scope's metric (`moe.router_ms`, ...) share.

The busy union, inside each run of the step's program on the first device, of
the operations whose `op_name` has one of the scopes as a component (forward,
recomputation and backward alike: `jit(step_fn)/transpose(jvp(blocks))/while/
body/closed_call/moe/experts/...` is under `experts`), median of the traced
steps: `program_trace.phase_ms`'s arithmetic with another pick."""

from __future__ import annotations

from statistics import median
from typing import Any, Dict, Iterable, Optional

from benchmark.harness import program_trace, xplane


def scope_ms(run: Dict[str, Any], scopes: Iterable[str]) -> Optional[float]:
    """Milliseconds per step under any of `scopes`, or nothing where the run
    was not traced or its program carries no such scope."""
    program = program_trace.of(run)
    if program is None or not program.trace.devices:
        return None
    wanted = set(scopes)
    trace, dev = program.trace, program.trace.devices[0]
    mine = [(op[4], op[4] + op[5]) for op in trace._leaf_ops(dev)
            if wanted.intersection(program.scopes.get(op[0], "").split("/"))]
    runs = trace.step_runs(dev)
    if not mine or not runs:
        return None
    return median(xplane.measure(xplane.union(xplane.clip(mine, start, start + dur)))
                  for _, _, start, dur in runs) / 1e6

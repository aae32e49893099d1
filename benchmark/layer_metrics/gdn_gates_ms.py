"""Device time per step under the scope `gdn_gates` of `models/olmo_hybrid.py` (`beta` and the log decay `g` with
their two projections of 30 columns; until PR 54 the L2 norms of q and k too, which `gdn.conv_ms` holds since),
forward, recomputation and backward together: `scope_trace.scope_ms`."""

from benchmark.harness import scope_trace

META = {
    "name": "gdn.gates_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "linear attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ('gdn_gates',))

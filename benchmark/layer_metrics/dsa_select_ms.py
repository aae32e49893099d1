"""Device time per step under the scope `select` (`ops/lightning_indexer.py select`: the indexer's scores a tile of queries at a time, the exact top-k threshold, the packed selection and its row statistics), forward, recomputation and backward
together, every layer of the step: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "dsa.select_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("select",))

"""Device time per step under the scope `indexer` (the indexer's three projections, its LayerNorm and rotation in `qkv_part` (`models/keye_vl2.py`), and off the TPU the XLA form's scores), forward, recomputation and backward
together, every layer of the step: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "dsa.indexer_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("indexer",))

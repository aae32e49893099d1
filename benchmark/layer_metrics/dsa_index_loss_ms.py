"""Device time per step under the scope `index_loss` (`ops/lightning_indexer.py index_loss`: the head-mean probabilities, the scores again, the KL and the gradient to the indexer's inputs), forward, recomputation and backward
together, every layer of the step: `scope_trace.scope_ms`. Nothing where the program has no such scope."""

from benchmark.harness import scope_trace

META = {
    "name": "dsa.index_loss_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "sparse attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return scope_trace.scope_ms(run, ("index_loss",))

"""The least `collectives.exposed_ms` over the gang's ranks, each rank's read from its own raw trace on
its own clock (`step_account.ranks`): the exposed time that no rank's lateness explains. Rank 0's
`collectives.exposed_ms` less this is what rank 0 waits for the others. Alone, rank 0's own reading.
Nothing without a trace of a TPU."""

from benchmark.harness import step_account

META = {
    "name": "collectives.exposed_min_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "device_trace",
    "layer": "collectives",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    ranks = step_account.ranks(run)
    exposed = [r["collectives.exposed_ms"] for r in ranks or () if r["collectives.exposed_ms"] is not None]
    return min(exposed) if exposed else None

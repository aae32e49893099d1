"""The largest expert's load over the mean load, the worst layer's, in the
routing the reference check saw (the `routing_stats` of the cell's model, by
its model file's `check`, on the first rows of the run's first batch): 1 is an even spread, `num_experts` is
everything on one expert."""

META = {
    "name": "moe.load_max_over_mean",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("routing", {}).get("load_max_over_mean")

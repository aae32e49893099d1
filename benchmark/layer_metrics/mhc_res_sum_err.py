"""The largest distance from 1 of a row or column sum of any sublayer's H_res in the checked step (the program's
`routing_stats` and the reference's, by the model file's `check`, on the first row of the run's first batch): what the
Sinkhorn rounds left of a doubly stochastic matrix. 0 is one; a round left out reads a tenth and more."""

META = {
    "name": "mhc.res_sum_err",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "residual streams",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("res_sum_err")

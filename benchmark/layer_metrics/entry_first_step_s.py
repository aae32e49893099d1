"""`fit()` called in the parent -> the first train step done in rank 0 (same
machine, wall clock), less the benchmark's own reference check and its look
into the compiled step: spawn, chip grant, gang join, state init, compiles."""

META = {
    "name": "entry.first_step_s",
    "unit": "s",
    "better": "lower",
    "source": "host_clock",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    s = run["summary"]
    own = s["setup_spans_s"].get("reference_check", 0.0) + s["setup_spans_s"].get("inspect_step", 0.0)
    return s["first_step_wall"] - run["parent"]["t_fit_wall"] - own

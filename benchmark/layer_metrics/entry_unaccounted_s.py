"""`fit()` -> loop entered (the benchmark's `t_loop_wall` - `t_fit_wall`) less the union of the
placement, spawn and backend spans and session -> `train_fn` entered: what the timeline does not see."""

from benchmark.harness import bringup

META = {
    "name": "entry.unaccounted_s",
    "unit": "s",
    "better": "lower",
    "source": "program_span",
    "layer": "entry, chip ownership, gang join",
    "moves": "setup_s"
}


def read(run):
    b = bringup.of(run)
    return b.unaccounted_s if b else None

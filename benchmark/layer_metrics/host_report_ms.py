"""Median of the loop's span around `session.report`: the queue hand-off in
step with the driver's round."""

META = {
    "name": "host.report_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "program_span",
    "layer": "host phases",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["span_ms_per_step"].get("report")

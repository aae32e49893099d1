"""The bounded `queue.put` inside `session.report` (`ray_tpu.train.report.put`):
the part of `host.report_ms` that is the driver's round holding the worker
back, median over the traced steps."""

from statistics import median

from benchmark.harness import program_trace

META = {
    "name": "host.report_put_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "program_span",
    "layer": "host phases",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    program = program_trace.of(run)
    puts = program.span_ms("ray_tpu.train.report.put") if program else []
    return median(puts) if puts else None

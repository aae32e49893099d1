"""Seconds inside the backend compiler over set-up in rank 0, retrieval from
the persistent cache included (`jax.monitoring`
`/jax/core/compile/backend_compile_duration`)."""

META = {
    "name": "compile.backend_s",
    "unit": "s",
    "better": "lower",
    "source": "program_counter",
    "layer": "compile",
    "moves": "setup_s"
}


def read(run):
    return run["summary"]["compiles_setup"]["seconds"]

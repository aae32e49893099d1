"""User + system CPU of the parent and every descendant (head, workers, data
tasks) over the window, from `/proc/<pid>/stat`, per completed step."""

META = {
    "name": "host.cpu_ms",
    "unit": "ms/step",
    "better": "lower",
    "source": "host_clock",
    "layer": "host phases",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    cpu, steps = run["parent"].get("cpu_s_in_window"), run["summary"]["completed"]
    return cpu * 1e3 / steps if cpu is not None and steps else None

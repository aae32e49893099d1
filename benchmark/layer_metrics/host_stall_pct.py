"""The share of the window's time that `tokens_per_s_per_chip` leaves out:
time between completions beyond the median step at the same position in its
group, over the steps clear of the traced ones. Stalls of the host that come
now and then (a neighbour on its cores, a periodic tick of the control plane)
are here and nowhere else. Not read where the traced steps leave under three
readings a position (the four-chip cell: 40 steps, and the profiler's stop
takes 10 s there)."""

META = {
    "name": "host.stall_pct",
    "unit": "%",
    "better": "lower",
    "source": "host_clock",
    "layer": "host phases",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    share = run["summary"].get("stall_share")
    return None if share is None else 100.0 * share

"""1 - busy union / traced window, averaged over the chips that traced."""

META = {
    "name": "device.idle_pct",
    "unit": "%",
    "better": "lower",
    "source": "device_trace",
    "layer": "device",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    pairs = [p for p in run["summary"]["traced_busy_window_s"] if p[1] > 0]
    if not pairs:
        return None
    return 100.0 * (1.0 - sum(b for b, _ in pairs) / sum(w for _, w in pairs))

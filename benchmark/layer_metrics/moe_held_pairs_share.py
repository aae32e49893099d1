"""Of the (token, expert) pairs the routers made in the reference check's rows, the share whose
expert this chip holds (`routing_stats` of `models/lfm2.py`: `held_pairs` over `held_pairs +
elsewhere_pairs`, all expert layers together). An even router gives held / routed-over experts
(0.125 for 8 of 64); more is more work here than the deployment's other chips have."""

META = {
    "name": "moe.held_pairs_share",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("routing", {}).get("held_pairs_share")

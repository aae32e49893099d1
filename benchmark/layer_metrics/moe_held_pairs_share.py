"""Of the (token, expert) pairs the routers made in the reference check's rows, the share whose
expert this chip holds (the `routing_stats` of the cell's model: `held_pairs` over `held_pairs +
elsewhere_pairs`, all expert layers together). An even router gives held / routed-over experts
(0.125 for 8 of 64); more is more work here than the deployment's other chips have. A cell whose
routers are even by construction (SDAR's, tiled for the eight chips: 0.125 in every run) is not on
its list: a reading that cannot move is no entry."""

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

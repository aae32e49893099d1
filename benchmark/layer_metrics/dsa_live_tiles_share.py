"""Of the 512 x 512 pairs of tiles on or under the diagonal, the share in which some query selected some key, all layers
together, on the row the reference check saw (`lightning_indexer.selection_counts`): what a kernel that skips a tile
pair with nothing kept could not skip. Near 1 at a token-level choice of 2,048 in 16,384."""

META = {
    "name": "dsa.live_tiles_share",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "sparse attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("selection", {}).get("live_tiles_share")

"""The share of the causal (query, key) pairs that the indexers' selections keep, all layers together, on the row the
reference check saw (`selection_stats` of `models/keye_vl2.py`): top-2,048 of a row of 16,384 keeps 0.2344 by arithmetic,
more only through ties. What a walk that visited the selected pairs alone would have left of the causal walk."""

META = {
    "name": "dsa.selected_share",
    "unit": "ratio",
    "better": "lower",
    "source": "program_counter",
    "layer": "sparse attention",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    return run["summary"]["check"].get("selection", {}).get("selected_share")

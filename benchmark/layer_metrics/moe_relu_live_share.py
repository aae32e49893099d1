"""Of the hidden units of the held experts (`moe_ffn_hidden_size` a (token, expert) pair held here), the share whose gate
`W_gate,e m` is above zero, so that ReGLU's `relu` leaves them non-zero: counted by the cell's model over the reference
check's rows (`routing_stats`' `relu_live_share`, a layer at a time; the check holds each layer's count to the reference's),
the mean over the layers. What a grouped product that skips a dead unit's row of W_down, and its column of W_up, could save:
at 50 % half of two of the three products. Nothing where the model's check counts none (an activation with no dead units)."""

META = {
    "name": "moe.relu_live_share",
    "unit": "%",
    "better": "lower",
    "source": "program_counter",
    "layer": "expert layer",
    "moves": "tokens_per_s_per_chip"
}


def read(run):
    share = run["summary"].get("check", {}).get("routing", {}).get("relu_live_share")
    return None if share is None else 100.0 * share

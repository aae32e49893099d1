"""The layer the 30B-A3B family's models share (`keye_vl2.py`, `sdar.py`; `trinity.py` takes its shapes and its
projections), in
the parts a model builds its own block from: grouped-query attention's
projections with an RMSNorm over each head's own dimensions on q and on k and a
rotation by tables the model supplies, the output projection, and a
token-choice mixture of SwiGLU experts of which this chip holds some
(`moe.moe_mlp(held_from=)`); the leaves' shapes with their initialisation and
logical axes, and the parameter tree made from them.

A configuration needs `d_model, n_head, n_kv_head, head_dim, d_expert,
n_experts, held, first_expert_held, experts_per_token, norm_topk_prob,
n_layer, vocab_size, norm_eps, dtype, param_dtype`.
"""

from __future__ import annotations

import math
from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from ray_tpu.models.llama import apply_rope, rms_norm
from ray_tpu.models.moe import moe_mlp, routing_report
from ray_tpu.models.stack import draw, per_leaf


# --------------------------------------------------------------------------- sizes
def layer_shapes(config) -> Dict[str, Any]:
    """{name: (shape, init: a normal's std, or "ones" / "zeros", logical axes)} of one layer."""
    d, nh, nkv, hd, f = config.d_model, config.n_head, config.n_kv_head, config.head_dim, config.d_expert
    std, out_std = 0.02, 0.02 / math.sqrt(2 * config.n_layer)
    return {
        "attn_norm": ((d,), "ones", (None,)), "mlp_norm": ((d,), "ones", (None,)),
        "wq": ((d, nh, hd), std, ("embed", "heads", None)),
        "wk": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
        "wv": ((d, nkv, hd), std, ("embed", "kv_heads", None)),
        "q_norm": ((hd,), "ones", (None,)), "k_norm": ((hd,), "ones", (None,)),
        "wo": ((nh, hd, d), out_std, ("heads", None, "embed")),
        "moe": {
            "router_w": ((d, config.n_experts), std, ("embed", None)),
            "w_gate": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_up": ((config.held, d, f), std, ("expert", "embed", "mlp")),
            "w_down": ((config.held, f, d), out_std, ("expert", "mlp", "embed")),
        },
    }


def matmul_params(config) -> int:
    """One layer's parameters that every position meets as an operand of a product: W_q, W_k, W_v, W_o, the router."""
    d, hd = config.d_model, config.head_dim
    return 2 * d * config.n_head * hd + 2 * d * config.n_kv_head * hd + d * config.n_experts


def layer_params(config) -> int:
    """One layer's parameters here: the experts held, not all the router names."""
    d = config.d_model
    return matmul_params(config) + 3 * config.held * d * config.d_expert + 2 * d + 2 * config.head_dim


# --------------------------------------------------------------------------- init
def tree(config, shapes: Dict[str, Any], leaf: Callable):
    """A tree like the parameters': `leaf(name, shape, init, axes)` for every leaf, a layer's
    (`shapes`) stacked over the layers (`stack.per_leaf`).

    The embedding's rows are N(0, 1), `torch.nn.Embedding`'s own: at 0.02 a
    token's row (norm 0.9) is outweighed after one layer by the running mean of
    the values, which a group of 8 query heads on one key/value head adds up
    coherently (2.2) and which is the same vector for every query; the routers
    of the later layers then see one input, a row's 16,384 tokens all choose
    the same 8 experts, and a layer's held load is 0, 1, 2 or 3 times the
    even share by the draw (PERF.md section 6, PR 42)."""
    d = config.d_model
    return {
        "embed": leaf("embed", (config.vocab_size, d), 1.0, ("vocab", "embed")),
        "blocks": per_leaf(shapes, leaf, (config.n_layer,)),
        "final_norm": leaf("final_norm", (d,), "ones", (None,)),
        "lm_head": leaf("lm_head", (config.vocab_size, d), 0.02, ("vocab", "embed")),
    }


def init_params(config, key, shapes: Dict[str, Any]) -> Dict[str, Any]:
    """One counter over the leaves a normal draws, in the tree's order: `key` folded with it."""
    counter = iter(range(1 << 30))
    return tree(config, shapes, lambda name, shape, init, axes: draw(
        None if isinstance(init, str) else jax.random.fold_in(key, next(counter)), shape, init, config.param_dtype))


def param_logical_axes(config, shapes: Dict[str, Any]) -> Dict[str, Any]:
    return tree(config, shapes, lambda name, shape, init, axes: axes)


# --------------------------------------------------------------------------- forward
def by_batch(table):
    """A per-position stream (S, B, pairs), as `stack.apply_stack` takes them, against (B, heads, S, pairs)."""
    return table.transpose(1, 0, 2)[:, None]


def qkv_heads(h, layer, cos, sin, config):
    """q (B, heads, S, hd), k and v (B, kv heads, S, hd) of the normed input h
    (B, S, D): the three projections, the norm over each head's own dimensions
    on q and on k where the layer has its scales (`q_norm`, `k_norm`:
    `smallthinker.py`'s has none), the rotation by `cos`, `sin` (B | 1, 1, S,
    hd / 2), or none where they are None (a layer without positions:
    `trinity.py`'s full kind)."""
    cdt, eps = config.dtype, config.norm_eps
    q = jnp.einsum("bsd,dnh->bnsh", h, layer["wq"].astype(cdt))
    k = jnp.einsum("bsd,dnh->bnsh", h, layer["wk"].astype(cdt))
    v = jnp.einsum("bsd,dnh->bnsh", h, layer["wv"].astype(cdt))
    rotated = (lambda x: x) if cos is None else (lambda x: apply_rope(x, cos, sin))
    normed = lambda x, name: rms_norm(x, layer[name], eps).astype(cdt) if name in layer else x  # noqa: E731
    return rotated(normed(q, "q_norm")), rotated(normed(k, "k_norm")), v


def out_and_experts(x, o, layer, config):
    """`stack.block`'s second half: x + o W_o, then the held experts' part of the
    expert layer on its norm. -> (x, what `moe_mlp` reports). The scope names are
    read from the compiled program's `op_name`s (PERF.md, "names")."""
    cdt = config.dtype
    with jax.named_scope("attn_out"):
        x = x + jnp.einsum("bnsh,nhd->bsd", o.astype(cdt), layer["wo"].astype(cdt))
    with jax.named_scope("moe"):
        h = rms_norm(x, layer["mlp_norm"], config.norm_eps).astype(cdt)
        moe = layer["moe"]
        h, aux = moe_mlp(
            h, moe["router_w"], moe["w_gate"], moe["w_up"], moe["w_down"],
            k=config.experts_per_token, norm_topk_prob=config.norm_topk_prob,
            held_from=config.first_expert_held)
        return x + h, aux


def routing_stats(aux: Dict[str, Any], pairs: int) -> Dict[str, Any]:
    """What the routers did, per layer (leading axis), from `moe_mlp`'s reports
    of the layers and the (token, expert) pairs a layer routes:
    `moe.routing_report`, the load-balancing term beside."""
    return {**routing_report(aux, pairs), "load_balance": aux["load_balance"]}

"""Manifold-constrained hyper-connections (DeepSeek-AI, "mHC", arXiv:2512.24880,
over Hyper-Connections, arXiv:2409.19606): a residual path of n streams round
a sublayer F, mixed per token by three maps that the token's own streams make.

    X (n, d) the token's streams;  r = (mean(vec(X)^2) + norm_eps)^-1/2
    m = r * (vec(X) Phi),  Phi (n d, n^2 + 2 n)
    H_pre  = sigmoid(a_pre m[:n] + b_pre)                      (n,)
    H_post = 2 sigmoid(a_post m[n:2n] + b_post)                (n,)
    A = clip(a_res mat(m[2n:]) + B_res, lo, hi);  M = exp(A)   (n, n)
    `rounds` times: rows of M over (their sum + eps), columns of M over (their sum + eps);  H_res = M
    u = sum_i H_pre[i] X[i];  y = F(N(u));  X'[i] = H_post[i] y + sum_j H_res[i, j] X[j]

Here the streams are x (B, n, S, d), a stream a (S, d) plane, and a map holds a
token on the minor axis: H_pre, H_post (n, B, S), H_res (n, n, B, S). Every sum
over streams is adds of slices of a leading axis, so the 2 x `rounds`
normalisations are element-wise work on sixteen (B, S) planes that XLA may fuse
whole, and no (S, n, n) array with n on the lanes is ever made. float32 inside
whatever x's dtype; the gradient is autodiff's through every round.

Plain `jax.numpy`: the passes over the streams (`maps`' product, `pre_mix`,
`post_res_mix`) are bound by HBM and XLA fuses each into a pass or two. The
scopes `mhc/maps`, `mhc/sinkhorn`, `mhc/pre`, `mhc/post` are what a trace
splits the layer by (PERF.md, "names").
"""

from __future__ import annotations

import functools
import operator
from typing import Tuple

import jax
import jax.numpy as jnp


def n_maps(n: int) -> int:
    """The columns of Phi, and the entries of the biases: H_pre's n, H_post's n, H_res's n x n."""
    return n * n + 2 * n


def _sum(planes):
    return functools.reduce(operator.add, planes)


def sinkhorn(logits, rounds: int, eps: float, clamp: Tuple[float, float]):
    """H_res (n, n, ...) from A (n, n, ...): exp of the clamped entries, then `rounds` times the rows over their sum
    (axis 1 runs along a row) and the columns over theirs, each sum with `eps` added."""
    with jax.named_scope("sinkhorn"):
        m = jnp.exp(jnp.clip(logits, *clamp))
        n = m.shape[0]
        for _ in range(rounds):
            m = m / (_sum([m[:, j] for j in range(n)]) + eps)[:, None]
            m = m / (_sum([m[i] for i in range(n)]) + eps)[None]
        return m


def maps(x, phi, alpha, bias, *, norm_eps: float, rounds: int, eps: float, clamp: Tuple[float, float]):
    """(H_pre (n, B, S), H_post (n, B, S), H_res (n, n, B, S)), float32, of the streams x (B, n, S, d).
    phi: (n^2 + 2 n, n, d), Phi's columns as rows (the streams' norm has no scale of its own: it is folded into
    Phi); alpha: (3,) the scales a_pre, a_post, a_res; bias: (n^2 + 2 n,) b_pre, b_post, then B_res row by row."""
    n = x.shape[1]
    with jax.named_scope("mhc"):
        with jax.named_scope("maps"):
            xf = x.astype(jnp.float32)
            r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 3)) + norm_eps)  # (B, S)
            # f32 x f32 at full precision: 24 columns of a product whose rounding would move every map.
            m = r * jnp.einsum("bnsd,cnd->cbs", xf, phi.astype(jnp.float32), precision=jax.lax.Precision.HIGHEST)
            alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)[:, None, None]
            h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
            logits = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, *m.shape[1:])
        h_res = sinkhorn(logits, rounds, eps, clamp)
    return h_pre, h_post, h_res


def pre_mix(x, h_pre):
    """u (B, S, d) f32 = sum_i H_pre[i] X[i]: what the sublayer's norm reads."""
    with jax.named_scope("mhc"), jax.named_scope("pre"):
        return _sum([h_pre[i][..., None] * x[:, i].astype(jnp.float32) for i in range(x.shape[1])])


def post_res_mix(x, y, h_post, h_res):
    """X' (B, n, S, d) in x's dtype: X'[i] = H_post[i] y + sum_j H_res[i, j] X[j], y (B, S, d) the sublayer's output."""
    n = x.shape[1]
    with jax.named_scope("mhc"), jax.named_scope("post"):
        xs = [x[:, j].astype(jnp.float32) for j in range(n)]
        yf = y.astype(jnp.float32)
        out = [h_post[i][..., None] * yf + _sum([h_res[i, j][..., None] * xs[j] for j in range(n)]) for i in range(n)]
        return jnp.stack(out, axis=1).astype(x.dtype)

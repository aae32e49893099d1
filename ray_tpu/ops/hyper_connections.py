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
whatever x's dtype.

Forward every pass is plain `jax.numpy` on every platform: each is bound by HBM
and XLA fuses it into a pass or two at the bytes (PERF.md section 6, PR 66).
The maps' small planes (the sigmoids, all `rounds` normalisations) are jax's to
differentiate. The three passes over the streams are not (PR 67): what autodiff
makes of `post_res_mix` is n + n^2 multiply-reduces over pairs of (S, d) planes
that XLA gathers into 7.5 fusions a sublayer, each reading its planes again
(18.4 ms a step of the Xing4.0 cell for 5.0 of bytes), and of the product a
float32 cotangent of the streams' size at six MXU passes. Each pass has a
backward rule written by hand (`jax.custom_vjp`) that keeps the arrays it was
handed and nothing else (x, y, the maps, Phi, and r and m, a (B, S) and a
(n^2 + 2 n, B, S) plane):

    `post_res_mix`  dX[j] = sum_i H_res[i, j] dX'[i];  dy = sum_i H_post[i] dX'[i]
                    dH_post[i] = sum_d dX'[i] y;  dH_res[i, j] = sum_d dX'[i] X[j]
    `pre_mix`       dX[i] = H_pre[i] du;  dH_pre[i] = sum_d du X[i]
    the product     dp = r dm;  dX[i] = (dp Phi^T)[i] - (sum_c dm_c m_c) r^2 / (n d) X[i];  dPhi = X^T dp

On a TPU the first and the last are a Mosaic kernel each, one pass over the
streams: `mhc_post_bwd` loads a tile of tokens of dX', X and y once, writes dX
and dy and sums the n + n^2 products over d in float32; `mhc_pre_bwd` loads X
once, makes dp Phi^T (24 rows, on the MXU, the six bf16 products of two float32
operands as one product of 144 rows) and dPhi (X bf16: three products as one of
72 rows) and writes dX. A kernel takes a token on sublanes, so the rule hands it
a token's scalars as a (B, S, columns) operand made from the planes (0.4 MB a
sublayer); the public layouts stay. `pre_mix`'s rule is `jax.numpy` on every
platform: one reduction that reads X and du once, and H_pre[i] du left for XLA
to form inside the sum of the streams' cotangents. Elsewhere, and where the
shapes do not fit (`fits`) or the streams are laid out over several devices,
all three rules are `jax.numpy` written as one pass. Chosen by the platform the
call is compiled for and by the shapes: no argument of a model, environment
variable or configuration key.

Where x arrives as bf16 the product is three bf16 products, x (Phi_hi + Phi_mid
+ Phi_lo), issued as one of 3 x 24 columns: a bf16 x has no middle or low part,
so these are every non-zero term of `Precision.HIGHEST`'s six. A float32 x
takes `HIGHEST`.

The scopes `mhc/maps`, `mhc/sinkhorn`, `mhc/pre`, `mhc/post` are what a trace
splits the layer by (PERF.md, "names"); the kernels stand under `mhc/post` and
`mhc/pre`.
"""

from __future__ import annotations

import functools
import math
import operator
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.chunked_scan import select_backend

F32, BF16 = jnp.float32, jnp.bfloat16
HIGHEST = jax.lax.Precision.HIGHEST
LANES = 128
PACK = 16  # the sublanes of a bf16 tile
# Tokens a program of either kernel holds, with all n streams and all of d (a stream's row of 3,584 bf16 is 7 KB in
# one piece), and the tokens a step of `mhc_post_bwd`'s walk sums at a time (`tools/mhc_bench.py --set`, PR 67).
TOKEN_TILE, ROWS = 128, 16
CHUNK = 512  # lanes a step of `mhc_pre_bwd`'s walk: the columns of one product
VMEM_ROOM = 64 << 20  # of the v5e's 128 MiB: a program's blocks, two buffers each


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


# --------------------------------------------------------------------------- which form
def token_tile(seq: int) -> int:
    """The tokens a program holds: of a row no whole number of `TOKEN_TILE`, the most that divide both."""
    return math.gcd(seq, TOKEN_TILE)


def _block_bytes(n: int, seq: int, d: int, itemsize: int) -> int:
    """What the larger kernel's blocks hold of VMEM (`mhc_post_bwd`: dX', X, dX, y, dy), two buffers each."""
    return 2 * (3 * n + 2) * token_tile(seq) * d * itemsize


def fits(shape, itemsize: int) -> bool:
    """Whether the kernels take streams of `shape` (B, n, S, d): whole lane rows of d, a tile of whole lane rows of
    tokens (`mhc_pre_bwd` takes dp with a token on the lanes too) or the whole row of whole bf16 tiles, a token's
    n + n^2 scalars inside one lane row, the blocks inside `VMEM_ROOM`."""
    _, n, seq, d = shape
    tile = token_tile(seq)
    return (d % LANES == 0 and tile % PACK == 0 and (tile % LANES == 0 or tile == seq) and n + n * n <= LANES
            and _block_bytes(n, seq, d, itemsize) <= VMEM_ROOM)


def _kernels(name: str, x, backend: Optional[str], mesh) -> bool:
    """Whether a rule's backward pass is its kernel: what the call asks for, else the kernel where the call is
    compiled for one TPU (the mesh's platform where there is one) and `fits`. The kernels are one device's
    programs with no shard_map round them: streams laid out over a mesh take the `jax.numpy` rules."""
    fit = fits(x.shape, x.dtype.itemsize)
    if backend is None:
        one = mesh is None or mesh.size == 1
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None) if fit and one else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"{name}: backend {backend!r} is neither 'pallas' nor 'xla'")
    if backend == "pallas" and not fit:
        raise ValueError(f"{name}: the kernels take no streams of {x.shape}")
    return backend == "pallas"


# --------------------------------------------------------------------------- the product
def _bf16_parts(a):
    """(hi, mid, lo) bf16 with hi + mid + lo = a to float32's last bit: `ops/chunked_scan.py _bf16_parts` for XLA's
    side of a call, by `reduce_precision` and not a cast there and back, which a compiler that allows excess
    precision may drop (inside a Mosaic kernel nothing does)."""
    parts, rest = [], a.astype(F32)
    for _ in range(3):
        part = jax.lax.reduce_precision(rest, exponent_bits=8, mantissa_bits=7)
        parts.append(part.astype(BF16))
        rest = rest - part
    return parts


def _thirds(a, axis: int = 0):
    """The sum of the three equal parts of `axis`, the smallest first."""
    hi, mid, lo = jnp.split(a, 3, axis=axis)
    return lo + mid + hi


def product(x, phi):
    """p (columns, B, S) f32 = vec(X) Phi at full precision, x (B, n, S, d), phi (columns, n, d) f32."""
    if x.dtype == BF16:  # x has no middle or low part: three of `HIGHEST`'s six products, issued as one
        return _thirds(jnp.einsum("bnsd,cnd->cbs", x, jnp.concatenate(_bf16_parts(phi)), preferred_element_type=F32))
    return jnp.einsum("bnsd,cnd->cbs", x.astype(F32), phi.astype(F32), precision=HIGHEST)


def _tokens_first(planes):
    """(B, S, columns) of (columns, B, S): a token's scalars side by side, as a kernel's tile of tokens takes them."""
    return planes.transpose(1, 2, 0)


def _pre_bwd_kernel(x_ref, cols_ref, rows_ref, coef_ref, phis_ref, dx_ref, dphi_ref, *, chunk):
    """x, dx: (1, n, tile, d); cols (1, tile, 6 C) bf16, dp's parts against `phis` (6 C, n d), Phi's; rows (1, 3 C |
    C, tile), dp's parts (x bf16) or dp (x f32) with a token on the lanes; coef (1, tile, 1); dphi (1, 3 C | C, n d),
    one block a batch row that every tile of its tokens adds to."""
    n, _, d = x_ref.shape[1:]

    @pl.when(pl.program_id(1) == 0)
    def _():
        dphi_ref[...] = jnp.zeros_like(dphi_ref)

    cols, rows, coef = cols_ref[0], rows_ref[0], coef_ref[0]
    exact = HIGHEST if x_ref.dtype == F32 else None  # bf16 against bf16 is exact in one pass
    steps = d // chunk  # of a stream; Phi's rows hold the streams' lanes one after the other

    def walk(k, carry):
        at = pl.ds(pl.multiple_of(k % steps * chunk, chunk), chunk)
        wide = pl.ds(pl.multiple_of(k * chunk, chunk), chunk)
        x = x_ref[0, k // steps, :, at]
        from_maps = jnp.dot(cols, phis_ref[:, wide], preferred_element_type=F32)
        dx_ref[0, k // steps, :, at] = (from_maps + coef * x.astype(F32)).astype(dx_ref.dtype)
        dphi_ref[0, :, wide] += jnp.dot(rows, x, preferred_element_type=F32, precision=exact)
        return carry

    jax.lax.fori_loop(0, n * steps, walk, 0)


# A function of its own in the step's program, as `ops/short_conv.py`'s: under `jax.jit` the call with its kernel is
# traced once a process and lowered once a program, not once a sublayer and a trace of the train step.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _pre_bwd(x, phi, dp, coef, interpret=False):
    """(dX (B, n, S, d) in x's type, dPhi (columns, n, d) f32) of the product and the norm, by `mhc_pre_bwd`:
    dp (columns, B, S) = r dm, coef (B, S) the norm's term on X."""
    batch, n, seq, d = x.shape
    columns, tile = phi.shape[0], token_tile(seq)
    chunk = math.gcd(d, CHUNK)
    dp = _tokens_first(dp)
    hi, mid, lo = _bf16_parts(dp)
    p_hi, p_mid, p_lo = _bf16_parts(phi.reshape(columns, n * d))
    # Every product of two parts that float32 can tell from nothing, each pair side by side along the contraction.
    cols = jnp.concatenate([hi, hi, mid, hi, lo, mid], axis=2)
    phis = jnp.concatenate([p_hi, p_mid, p_hi, p_lo, p_hi, p_mid], axis=0)
    rows = jnp.concatenate([hi, mid, lo], axis=2) if x.dtype == BF16 else dp
    rows = rows.transpose(0, 2, 1)  # a token on the lanes: the left operand of a product over tokens
    streams = pl.BlockSpec((1, n, tile, d), lambda b, t: (b, 0, t, 0))
    whole = lambda a: pl.BlockSpec(a.shape, lambda b, t: (0,) * a.ndim)  # noqa: E731
    blocks = 2 * (2 * n * tile * d * x.dtype.itemsize + phis.size * 2 + rows.shape[1] * n * d * 4)
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=blocks + (24 << 20))  # and a step's arrays
    with jax.named_scope("mhc"), jax.named_scope("pre"), jax.named_scope(f"tile_{tile}"):
        dx, dphi = pl.pallas_call(
            functools.partial(_pre_bwd_kernel, chunk=chunk),
            grid=(batch, seq // tile),
            in_specs=[streams, pl.BlockSpec((1, tile, cols.shape[2]), lambda b, t: (b, t, 0)),
                      pl.BlockSpec((1, rows.shape[1], tile), lambda b, t: (b, 0, t)),
                      pl.BlockSpec((1, tile, 1), lambda b, t: (b, t, 0)), whole(phis)],
            out_specs=[streams, pl.BlockSpec((1, rows.shape[1], n * d), lambda b, t: (b, 0, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype),
                       jax.ShapeDtypeStruct((batch, rows.shape[1], n * d), F32)],
            interpret=interpret, name="mhc_pre_bwd", compiler_params=params,
            cost_estimate=pl.CostEstimate(flops=2 * x.size * (cols.shape[2] + rows.shape[1]), transcendentals=0,
                                          bytes_accessed=2 * x.size * x.dtype.itemsize),
        )(x, cols, rows, coef[..., None], phis)
    dphi = dphi.sum(axis=0)
    return dx, (_thirds(dphi) if x.dtype == BF16 else dphi).reshape(phi.shape)


def _xla_pre_bwd(x, phi, dp, coef):
    """`_pre_bwd` as `jax.numpy` on whole arrays."""
    xf = x.astype(F32)
    dx = jnp.einsum("cbs,cnd->bnsd", dp, phi.astype(F32), precision=HIGHEST) + coef[:, None, :, None] * xf
    return dx.astype(x.dtype), jnp.einsum("cbs,bnsd->cnd", dp, xf, precision=HIGHEST)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _columns(x, phi, norm_eps, kernel, interpret):
    """m (columns, B, S) f32 = r (vec(X) Phi), r the streams' norm."""
    return _columns_fwd(x, phi, norm_eps, kernel, interpret)[0]


def _columns_fwd(x, phi, norm_eps, kernel, interpret):
    xf = x.astype(F32)
    r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 3)) + norm_eps)  # (B, S)
    m = r * product(x, phi)
    return m, (x, phi, r, m)


def _columns_bwd(norm_eps, kernel, interpret, res, dm):
    x, phi, r, m = res
    # r = (sum X^2 / (n d) + eps)^-1/2 and m = r p: dr = sum_c dm_c p_c, dr/dX = - r^3 X / (n d).
    coef = -(dm * m).sum(axis=0) * r * r / (x.shape[1] * x.shape[3])
    if kernel:
        dx, dphi = _pre_bwd(x, phi, r * dm, coef, interpret=interpret)
    else:
        with jax.named_scope("mhc"), jax.named_scope("pre"):
            dx, dphi = _xla_pre_bwd(x, phi, r * dm, coef)
    return dx, dphi.astype(phi.dtype)


_columns.defvjp(_columns_fwd, _columns_bwd)


def maps(x, phi, alpha, bias, *, norm_eps: float, rounds: int, eps: float, clamp: Tuple[float, float], mesh=None,
         backend: Optional[str] = None, interpret: bool = False):
    """(H_pre (n, B, S), H_post (n, B, S), H_res (n, n, B, S)), float32, of the streams x (B, n, S, d).
    phi: (n^2 + 2 n, n, d), Phi's columns as rows (the streams' norm has no scale of its own: it is folded into
    Phi); alpha: (3,) the scales a_pre, a_post, a_res; bias: (n^2 + 2 n,) b_pre, b_post, then B_res row by row.
    mesh, backend, interpret: as `post_res_mix`'s, for the gradient of the product and the norm."""
    n = x.shape[1]
    with jax.named_scope("mhc"):
        with jax.named_scope("maps"):
            # 24 columns of a product whose rounding would move every map: at full precision.
            m = _columns(x, phi, float(norm_eps), _kernels("maps", x, backend, mesh), interpret)
            alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)[:, None, None]
            h_pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
            h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
            logits = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, *m.shape[1:])
        h_res = sinkhorn(logits, rounds, eps, clamp)
    return h_pre, h_post, h_res


# --------------------------------------------------------------------------- u
def _xla_pre_mix(x, h_pre):
    return _sum([h_pre[i][..., None] * x[:, i].astype(jnp.float32) for i in range(x.shape[1])])


_pre_mix = jax.custom_vjp(_xla_pre_mix)


def _pre_mix_fwd(x, h_pre):
    return _xla_pre_mix(x, h_pre), (x, h_pre)


def _pre_mix_bwd(res, du):
    """One reduction over d that reads X and du once; H_pre[i] du is an element-wise product that XLA forms where
    the streams' cotangents are added up, and that no pass writes alone."""
    x, h_pre = res
    with jax.named_scope("mhc"), jax.named_scope("pre"):
        dh_pre = jnp.sum(x.astype(F32) * du[:, None], axis=3).transpose(1, 0, 2)
        dx = (h_pre.transpose(1, 0, 2)[..., None] * du[:, None]).astype(x.dtype)
    return dx, dh_pre


_pre_mix.defvjp(_pre_mix_fwd, _pre_mix_bwd)


def pre_mix(x, h_pre):
    """u (B, S, d) f32 = sum_i H_pre[i] X[i]: what the sublayer's norm reads."""
    with jax.named_scope("mhc"), jax.named_scope("pre"):
        return _pre_mix(x, h_pre)


# --------------------------------------------------------------------------- X'
def _post_bwd_kernel(g_ref, x_ref, y_ref, h_ref, dx_ref, dy_ref, dh_ref, *, rows):
    """g, x, dx: (1, n, tile, d); y, dy: (1, tile, d); h (1, tile, n + n n), a token's H_post then H_res row by row;
    dh (1, tile, LANES), their gradients on the first lanes. A step holds `rows` tokens and walks d a lane row at a
    time: every sum over d is `rows` x LANES partial sums until the row's end."""
    n, tile, d = x_ref.shape[1:]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)

    def walk_tokens(step, carry):
        at = pl.ds(pl.multiple_of(step * rows, rows), rows)
        h = h_ref[0, at, :]
        wide = [jnp.broadcast_to(h[:, c:c + 1], (rows, LANES)) for c in range(n + n * n)]
        h_post, h_res = wide[:n], [wide[n + i * n:n + (i + 1) * n] for i in range(n)]

        def walk_lanes(c, sums):
            cols = pl.ds(pl.multiple_of(c * LANES, LANES), LANES)
            g = [g_ref[0, i, at, cols].astype(F32) for i in range(n)]
            xs = [x_ref[0, j, at, cols].astype(F32) for j in range(n)]
            y = y_ref[0, at, cols].astype(F32)
            for j in range(n):
                dx_ref[0, j, at, cols] = _sum([h_res[i][j] * g[i] for i in range(n)]).astype(dx_ref.dtype)
            dy_ref[0, at, cols] = _sum([h_post[i] * g[i] for i in range(n)]).astype(dy_ref.dtype)
            products = [g[i] * y for i in range(n)] + [g[i] * xs[j] for i in range(n) for j in range(n)]
            return tuple(s + p for s, p in zip(sums, products))

        zeros = jnp.zeros((rows, LANES), F32)
        sums = jax.lax.fori_loop(0, d // LANES, walk_lanes, (zeros,) * (n + n * n))
        out = zeros
        for c, s in enumerate(sums):
            out = jnp.where(lane == c, jnp.sum(s, axis=1, keepdims=True), out)
        dh_ref[0, at, :] = out
        return carry

    jax.lax.fori_loop(0, tile // rows, walk_tokens, 0)


@functools.partial(jax.jit, static_argnames=("interpret",))
def _post_bwd(g, x, y, h_post, h_res, interpret=False):
    """(dX, dy, dH_post, dH_res) of `post_res_mix` by `mhc_post_bwd`, g the cotangent of X'."""
    batch, n, seq, d = x.shape
    tile = token_tile(seq)
    h = _tokens_first(jnp.concatenate([h_post, h_res.reshape(n * n, batch, seq)]))
    streams = pl.BlockSpec((1, n, tile, d), lambda b, t: (b, 0, t, 0))
    plane = pl.BlockSpec((1, tile, d), lambda b, t: (b, t, 0))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_block_bytes(n, seq, d, x.dtype.itemsize) + (16 << 20))  # and a step's arrays
    with jax.named_scope("mhc"), jax.named_scope("post"), jax.named_scope(f"tile_{tile}"):
        dx, dy, dh = pl.pallas_call(
            functools.partial(_post_bwd_kernel, rows=math.gcd(tile, ROWS)),
            grid=(batch, seq // tile),
            in_specs=[streams, streams, plane, pl.BlockSpec((1, tile, n + n * n), lambda b, t: (b, t, 0))],
            out_specs=[streams, plane, pl.BlockSpec((1, tile, LANES), lambda b, t: (b, t, 0))],
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct(y.shape, y.dtype),
                       jax.ShapeDtypeStruct((batch, seq, LANES), F32)],
            interpret=interpret, name="mhc_post_bwd", compiler_params=params,
            cost_estimate=pl.CostEstimate(flops=(4 * n + 4) * x.size, transcendentals=0,
                                          bytes_accessed=(3 * x.size + 2 * y.size) * x.dtype.itemsize),
        )(g, x, y, h)
    dh = dh[..., :n + n * n].transpose(2, 0, 1)
    return dx, dy, dh[:n], dh[n:].reshape(h_res.shape)


def _xla_post_bwd(g, x, y, h_post, h_res):
    """`_post_bwd` as `jax.numpy` on whole arrays."""
    gf, xf, yf = g.astype(F32), x.astype(F32), y.astype(F32)
    dx = jnp.einsum("ijbs,bisd->bjsd", h_res, gf, precision=HIGHEST)
    dy = jnp.einsum("ibs,bisd->bsd", h_post, gf, precision=HIGHEST)
    dh_post = jnp.einsum("bisd,bsd->ibs", gf, yf, precision=HIGHEST)
    dh_res = jnp.einsum("bisd,bjsd->ijbs", gf, xf, precision=HIGHEST)
    return dx.astype(x.dtype), dy.astype(y.dtype), dh_post, dh_res


def _xla_post_res_mix(x, y, h_post, h_res):
    n = x.shape[1]
    xs = [x[:, j].astype(jnp.float32) for j in range(n)]
    yf = y.astype(jnp.float32)
    out = [h_post[i][..., None] * yf + _sum([h_res[i, j][..., None] * xs[j] for j in range(n)]) for i in range(n)]
    return jnp.stack(out, axis=1).astype(x.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def _post_res_mix(x, y, h_post, h_res, kernel, interpret):
    return _xla_post_res_mix(x, y, h_post, h_res)


def _post_res_mix_fwd(x, y, h_post, h_res, kernel, interpret):
    return _xla_post_res_mix(x, y, h_post, h_res), (x, y, h_post, h_res)


def _post_res_mix_bwd(kernel, interpret, res, g):
    if kernel:
        return _post_bwd(g, *res, interpret=interpret)
    with jax.named_scope("mhc"), jax.named_scope("post"):
        return _xla_post_bwd(g, *res)


_post_res_mix.defvjp(_post_res_mix_fwd, _post_res_mix_bwd)


def post_res_mix(x, y, h_post, h_res, *, mesh=None, backend: Optional[str] = None, interpret: bool = False):
    """X' (B, n, S, d) in x's dtype: X'[i] = H_post[i] y + sum_j H_res[i, j] X[j], y (B, S, d) the sublayer's output.

    backend: "pallas" (the gradient by the kernel) | "xla" | None (`select_backend` for the platform the computation
      is compiled for, the mesh's where there is one; "xla" where the shapes do not fit the kernel or the mesh holds
      more than one device).
    mesh: the jax.sharding.Mesh the surrounding jit shards over."""
    with jax.named_scope("mhc"), jax.named_scope("post"):
        return _post_res_mix(x, y, h_post, h_res, _kernels("post_res_mix", x, backend, mesh), interpret)

"""The short convolution of a linear-attention layer's q, k or v, from the
projection's output to the scan's operand:

    pre_t = sum_j taps[j] z_{t - (n - 1) + j}      causal, depthwise, n taps, zeros before the row's first position
    y     = silu(pre)                               cut into heads, heads first: (B, S, H d) -> (B, H, S, d)
    out   = y * rsqrt(sum over a head's channels of y^2 + 1e-6) * scale       (`normalize`; else out = y * scale)

z (B, S, C) arrives in the type the projection left it in (bf16 in a bf16
model) and `out` leaves in the same type; everything between them is float32
and nothing between them is rounded.

Forward it is a chain of XLA operations on every platform (`_xla_short_conv`):
XLA fuses the four shifted products, SiLU and the cast into one pass at HBM's
rate (47 + 47 MB of v in 122 us on a v5e), and a Mosaic kernel of the same
walk as the one below took 841 us for a layer's q, k and v where the chain
takes 643 with its float32 transposes (PERF.md section 6, PR 54). What XLA
makes of the chain's *gradient* is another matter: a fusion a tap over whole
float32 arrays and a column reduction a tap, 4.9 ms a layer-row.

So on a TPU the gradient is one Mosaic kernel, `short_conv_bwd`, one pass over
HBM: it reads z and the cotangent (heads first, as the scan's kernel hands it
back: a program lays its tile's heads side by side along lanes) and writes dz
and the taps' gradient, and keeps nothing of the forward pass but z itself
(under a remat of the layer's first part the chain is therefore not run again:
its values are no one's residuals). A program holds one row's S positions of
one channel tile, `lcm(head, 128)` channels (384 for heads of 96 or of 192:
whole heads and whole lane rows), so the taps' shifts are shifts along
sublanes of what is in VMEM and need no halo. It walks the row from its end, a
few tiles of positions at a time (`rows_a_step`): it makes `pre`, `y` and the
norm again from z (a head's sums are masked sums along lanes: a head may
straddle two lane rows), forms `dpre`, carries `dpre`'s first rows to the step
before for `dz_t = sum_j taps[j] dpre_{t + (n - 1) - j}`, and sums
`dpre_t z_{t - k}` over the row for the taps' gradient, one (n, tile) block a
program; the rows of a batch are added up outside. A last tile that passes the
array's end (2,880 channels are 7.5 tiles) works on what Pallas pads it with
and its overhang is dropped: channels meet only inside a head, and heads end
where the array ends.

Off the TPU, and where the shapes do not fit the kernel (`mosaic_fits`), jax
differentiates the chain. The form is chosen by the platform the call is
compiled for and by the shapes: no argument of a model, environment variable
or configuration key.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.ops.gated_delta_rule import select_backend

F32 = jnp.float32
EPS = 1e-6  # under the root of a head's sum of squares
# Positions a step of a program's walk. With the L2 norm a step waits for its heads' sums along lanes, and the more
# rows it holds the more of them are in flight: a layer-row's q (4,096 x 2,880) took 578 us at 16 rows a step, 359 at
# 32, 264 at 64, 204 at 128, 219 at 256 on the v5e; without it (v, 4,096 x 5,760) 334, 307, 316, 383, 459: past
# 32 rows the step's arrays leave the registers for nothing (`tools/short_conv_bench.py --set`, PR 54).
ROWS_NORMALIZED, ROWS_PLAIN = 128, 32
PACK = 16  # the sublanes of a bf16 tile: the rows a load of z before the step takes
HALO = 8  # rows kept of the neighbouring step: an f32 tile, and at least taps - 1
LANES = 128
MAX_TILE = 512  # channels a program at most
VMEM_ROOM = 64 << 20  # of the v5e's 128 MiB: a program's blocks, two buffers each


def _shifted(z, n: int):
    """z (B, S, C) moved `n` positions later, zeros before the row's first."""
    return z if n == 0 else jnp.pad(z, ((0, 0), (n, 0), (0, 0)))[:, :z.shape[1]]


def _xla_short_conv(z, taps, heads: int, scale: float, normalize: bool):
    """The chain as XLA operations on whole arrays, in float32, rounded once at its end."""
    (b, s, _), n = z.shape, taps.shape[0]
    zf, w = z.astype(F32), taps.astype(F32)
    y = jax.nn.silu(sum(w[j] * _shifted(zf, n - 1 - j) for j in range(n)))
    y = y.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)  # heads first
    if normalize:
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + EPS)
    if scale != 1.0:
        y = y * scale
    return y.astype(z.dtype)


# --------------------------------------------------------------------------- a step's mathematics
def _head_sums(x, head: int):
    """[(rows, 1)]: the sum of x (rows, tile) over each head's channels. A head is `head` lanes from a
    multiple of `head`: inside one lane row or across the end of one."""
    pieces = [x[:, at:at + LANES] for at in range(0, x.shape[1], LANES)]
    lane = jax.lax.broadcasted_iota(jnp.int32, pieces[0].shape, 1)
    sums = []
    for lo in range(0, x.shape[1], head):
        total = None
        for v in range(lo // LANES, -(-(lo + head) // LANES)):
            a, b = max(lo - v * LANES, 0), min(lo + head - v * LANES, LANES)
            part = pieces[v] if (a, b) == (0, LANES) else jnp.where((lane >= a) & (lane < b), pieces[v], 0.0)
            total = part if total is None else total + part
        sums.append(jnp.sum(total, axis=1, keepdims=True))
    return sums


def _spread(per_head, head: int, tile: int):
    """(rows, tile): each head's (rows, 1) value on that head's lanes."""
    rows = per_head[0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, LANES), 1)
    out = []
    for v in range(tile // LANES):
        first, last = v * LANES // head, (v * LANES + LANES - 1) // head
        row = jnp.broadcast_to(per_head[last], (rows, LANES))
        for h in range(last - 1, first - 1, -1):
            row = jnp.where(lane < (h + 1) * head - v * LANES, per_head[h], row)
        out.append(row)
    return jnp.concatenate(out, axis=1) if len(out) > 1 else out[0]


def _earlier(before, cur, n: int):
    """[z_t, z_{t-1}, ..., z_{t-n+1}] for the positions of `cur`, `before` the HALO rows ahead of them."""
    ext = jnp.concatenate([before, cur], axis=0)
    return [cur] + [pltpu.roll(ext, k, 0)[HALO:] for k in range(1, n)]


def _later(cur, after, n: int):
    """[x_t, x_{t+1}, ..., x_{t+n-1}] for the positions of `cur`, `after` the HALO rows behind them."""
    ext = jnp.concatenate([cur, after], axis=0)
    return [cur] + [pltpu.roll(ext, ext.shape[0] - k, 0)[:cur.shape[0]] for k in range(1, n)]


def _taps_sum(w, xs):
    """sum_j w[j] xs[n - 1 - j], added up in the order of `_xla_short_conv`."""
    n = w.shape[0]
    return sum(w[j:j + 1] * xs[n - 1 - j] for j in range(n))


# Under `jax.jit` the step is a jaxpr kept by its operands' types, which Python walks once a process and not once
# a call and a trace of the train step (`ops/gated_delta_rule.py _once`, PR 52).
@functools.partial(jax.jit, static_argnames=("head", "scale", "normalize"))
def _step_bwd(before, cur, w, dout, ahead, *, head, scale, normalize):
    """A step's positions of a tile, f32: `cur` (rows, tile) of z with the HALO rows `before` it, the cotangent
    `dout` of these positions, and `ahead`, the dpre of the HALO positions after them. Returns (dz (rows, tile),
    [the taps' gradients of these positions, summed to (8, tile)], dpre's first HALO rows)."""
    n, tile = w.shape[0], cur.shape[1]
    earlier = _earlier(before, cur, n)
    pre = _taps_sum(w, earlier)
    s = jax.nn.sigmoid(pre)
    dy = dout * scale if scale != 1.0 else dout
    if normalize:  # out = u scale, u = y r, r = (sum y^2 + eps)^-1/2: dy = r (g - u sum(g u))
        y = pre * s
        r = jax.lax.rsqrt(_spread(_head_sums(y * y, head), head, tile) + EPS)
        u = y * r
        dy = r * (dy - u * _spread(_head_sums(dy * u, head), head, tile))
    dpre = dy * (s * (1.0 + pre * (1.0 - s)))
    dz = _taps_sum(w, _later(dpre, ahead, n))  # dz_t = sum_j w[j] dpre_{t + (n - 1 - j)}
    dtaps = [(dpre * earlier[n - 1 - j]).reshape(-1, 8, tile).sum(axis=0) for j in range(n)]
    return dz, dtaps, dpre[:HALO]


# --------------------------------------------------------------------------- the kernel
def rows_a_step(seq: int, normalize: bool) -> int:
    """The positions a step holds: of a row no whole number of the constant's, the most that divide both."""
    return math.gcd(seq, ROWS_NORMALIZED if normalize else ROWS_PLAIN)


def _bwd_kernel(z_ref, w_ref, do_ref, dz_ref, dw_ref, **how):
    seq, tile = z_ref.shape[1:]
    w = w_ref[...].astype(F32)
    rows = rows_a_step(seq, how["normalize"])
    n, steps = w.shape[0], seq // rows

    def step(i, carry):
        ahead, sums = carry
        at = pl.multiple_of((steps - 1 - i) * rows, rows)
        cur = z_ref[0, pl.ds(at, rows), :].astype(F32)
        before = z_ref[0, pl.ds(pl.multiple_of(jnp.maximum(at - PACK, 0), PACK), PACK), :].astype(F32)[PACK - HALO:]
        before = jnp.where(at > 0, before, 0.0)
        dout = jnp.concatenate([do_ref[0, h, pl.ds(at, rows), :] for h in range(do_ref.shape[1])], axis=1)
        dz, dtaps, ahead = _step_bwd(before, cur, w, dout.astype(F32), ahead, **how)
        dz_ref[0, pl.ds(at, rows), :] = dz.astype(dz_ref.dtype)
        return ahead, tuple(a + d for a, d in zip(sums, dtaps))

    zeros = jnp.zeros((HALO, tile), F32)
    _, sums = jax.lax.fori_loop(0, steps, step, (zeros, (zeros,) * n))
    for j in range(n):
        dw_ref[0, j:j + 1, :] = jnp.sum(sums[j], axis=0, keepdims=True)


def tile_of(head: int) -> int:
    """Channels a program holds: the fewest that are whole heads and whole lane rows."""
    return math.lcm(head, LANES)


def mosaic_fits(shape, head: int, taps: int, itemsize: int, normalize: bool) -> bool:
    """Whether the kernel takes z of `shape`: whole steps a row, a tile of a few lane rows that is no wider than
    the array, the program's blocks inside `VMEM_ROOM`."""
    _, seq, channels = shape
    tile = tile_of(head)
    return (rows_a_step(seq, normalize) % PACK == 0 and tile <= min(MAX_TILE, channels) and taps - 1 <= HALO
            and _block_bytes(seq, head, itemsize) <= VMEM_ROOM)


def _block_bytes(seq: int, head: int, itemsize: int) -> int:
    """What a program's blocks hold of VMEM: z, dz and the cotangent (a head's block is whole lane rows wide),
    two buffers each."""
    tile = tile_of(head)
    return 2 * seq * (2 * tile + tile // head * -(-head // LANES) * LANES) * itemsize


def _bwd(z, taps, dout, head, scale, normalize, interpret):
    """(dz (B, S, C), dtaps (n, C)) of z, taps and the cotangent `dout` (B, heads, S, head), heads first as it is
    handed back: a program takes its tile's heads each as a block and lays them side by side along lanes."""
    b, seq, channels = z.shape
    n, tile = taps.shape[0], tile_of(head)
    wide = pl.BlockSpec((1, seq, tile), lambda i, c: (i, 0, c))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_block_bytes(seq, head, z.dtype.itemsize) + (16 << 20))  # and a step's arrays
    with jax.named_scope(f"tile_{tile}"), jax.named_scope(f"rows_{seq}"):
        dz, dw = pl.pallas_call(
            functools.partial(_bwd_kernel, head=head, scale=scale, normalize=normalize),
            grid=(b, pl.cdiv(channels, tile)),
            in_specs=[wide, pl.BlockSpec((n, tile), lambda i, c: (0, c)),
                      pl.BlockSpec((1, tile // head, seq, head), lambda i, c: (i, c, 0, 0))],
            out_specs=[wide, pl.BlockSpec((1, n, tile), lambda i, c: (i, 0, c))],
            out_shape=[jax.ShapeDtypeStruct(z.shape, z.dtype), jax.ShapeDtypeStruct((b, n, channels), F32)],
            interpret=interpret, name="short_conv_bwd", compiler_params=params,
            cost_estimate=pl.CostEstimate(flops=80 * z.size, transcendentals=2 * z.size,
                                          bytes_accessed=3 * z.size * z.dtype.itemsize),
        )(z, taps, dout)
    return dz, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4, 5, 6))
def _kernel_gradient(z, taps, heads, scale, normalize, mesh, interpret):
    return _xla_short_conv(z, taps, heads, scale, normalize)


def _kernel_gradient_fwd(z, taps, heads, scale, normalize, mesh, interpret):
    return _xla_short_conv(z, taps, heads, scale, normalize), (z, taps)


def _kernel_gradient_bwd(heads, scale, normalize, mesh, interpret, res, dout):
    z, taps = res
    head = z.shape[2] // heads
    kernel = lambda z, taps, dout: _bwd(z, taps, dout, head, scale, normalize, interpret)  # noqa: E731
    if mesh is not None and mesh.size > 1:
        from jax.sharding import PartitionSpec

        from ray_tpu.parallel import ShardingRules

        # XLA cannot partition a Mosaic call: rows over (data, fsdp) and whole heads over tensor, as
        # `gated_delta_rule(mesh=)`. The taps are whole on every device and their gradient is a sum over rows.
        rows, _, over_heads = ShardingRules().mesh_axes(("batch", None, "heads"), mesh=mesh, shape=(*z.shape[:2], heads))
        wide, narrow = PartitionSpec(rows, None, over_heads), PartitionSpec(None, over_heads)
        first = PartitionSpec(rows, over_heads, None, None)

        def kernel(z, taps, dout, one=kernel):
            dz, dtaps = one(z, taps, dout)
            return dz, jax.lax.psum(dtaps, rows) if rows else dtaps

        kernel = jax.shard_map(kernel, mesh=mesh, in_specs=(wide, narrow, first), out_specs=(wide, narrow), check_vma=False)
    dz, dtaps = kernel(z, taps, dout)
    return dz, dtaps.astype(taps.dtype)


_kernel_gradient.defvjp(_kernel_gradient_fwd, _kernel_gradient_bwd)


# --------------------------------------------------------------------------- the call
def short_conv(z, taps, heads: int, *, scale: float = 1.0, normalize: bool = False, mesh=None,
               backend: Optional[str] = None, interpret: bool = False):
    """`out` (B, heads, S, C / heads) in z's type, of z (B, S, C) and `taps` (n, C): the top of the file.

    heads: C is `heads` heads of consecutive channels.
    normalize: L2-normalise y over each head; scale: a factor on the result.
    backend: "pallas" (the gradient by the kernel) | "xla" | None (`select_backend` for the platform the
      computation is compiled for, the mesh's where there is one; "xla" where the shapes do not fit the kernel).
    mesh: the jax.sharding.Mesh the surrounding jit shards over; on more than one device the kernel runs inside
      a shard_map."""
    if backend is None:
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None)
        if not mosaic_fits(z.shape, z.shape[2] // heads, taps.shape[0], z.dtype.itemsize, normalize):
            backend = "xla"
    if backend == "xla":
        return _xla_short_conv(z, taps, heads, float(scale), normalize)
    if backend != "pallas":
        raise ValueError(f"short_conv: backend {backend!r} is neither 'pallas' nor 'xla'")
    if z.shape[1] % PACK:
        raise ValueError(f"short_conv: the kernel walks a row {PACK} positions at a time at least, not {z.shape[1]}")
    return _kernel_gradient(z, taps, heads, float(scale), normalize, mesh, interpret)

"""The short convolutions of the zoo's linear mixers: a causal, depthwise convolution of a few taps with what
stands round it, in two forms. Which form is meant nothing in an input says (two gates and no SiLU against a SiLU
and a norm over heads): the caller does, by the function it calls.

`short_conv`, of a linear-attention layer's q, k or v, from the projection's output to the scan's operand
(Olmo-Hybrid, Solar-Open2):

    pre_t = sum_j taps[j] z_{t - (n - 1) + j}      causal, depthwise, n taps, zeros before the row's first position
            (+ bias, a number a channel, where the layer has one: a Mamba-2 mixer's convolution over x, B and C)
    y     = silu(pre)                               cut into heads, heads first: (B, S, H d) -> (B, H, S, d)
    out   = y * rsqrt(sum over a head's channels of y^2 + 1e-6) * scale       (`normalize`; else out = y * scale)

`gated_short_conv`, LFM2's operator between its two projections, of bcu = [b | c | u] (B, S, 3 D):

    z = b u;   mixed_t = sum_j taps[j] z_{t - (n - 1) + j};   y = c mixed                     (B, S, D)

z (B, S, C) or bcu arrives in the type the projection left it in (bf16 in a bf16 model) and the result leaves in
the same type; everything between them is float32 and nothing between them is rounded.

Forward either is a chain of XLA operations on every platform (`_xla_short_conv`, `_xla_gated_conv`):
XLA fuses the four shifted products, SiLU and the cast into one pass at HBM's
rate (47 + 47 MB of v in 122 us on a v5e), and a Mosaic kernel of the same
walk as the one below took 841 us for a layer's q, k and v where the chain
takes 643 with its float32 transposes (PERF.md section 6, PR 54). What XLA
makes of the chain's *gradient* is another matter: a fusion a tap over whole
float32 arrays and a column reduction a tap, 4.9 ms a layer-row; of the gated chain's, the float32 z and
`mixed` as residuals of 268 MB a layer that it then makes twice (PERF.md section 6, PR 62).

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

The gated form's gradient is `gated_conv_bwd` on the same walk (`_walk`: the loop from the row's end, the halo
carried ahead, the taps' gradient summed to one block; `_rows_at`, `_earlier`, `_later`, `_taps_sum`), with its own
step (`_step_gated_bwd`: dc = g mixed, dm = g c, dz from dm's later rows, db = dz u, du = dz b) and its own blocks:
b, c and u are three column blocks of the one `bcu` (the same operand under three index maps, no slice copied
out), a tile of the most lane rows that divide D (512 of LFM2's 2,048), and db, dc, du go back into one `dbcu` of
bcu's shape. A pipelined result is one block a program, so the program fills a VMEM slot with its three and copies
them out itself while the next program fills the other. It keeps `bcu` and the taps alone: 7 arrays of B x S x D
cross HBM, 940 MB a layer of the LFM2 cell in 1.39 ms (83 % of HBM's rate; PERF.md section 6, PR 62).

Off the TPU, and where the shapes do not fit the kernel (`mosaic_fits`, `gated_fits`), jax
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

from ray_tpu.ops.chunked_scan import select_backend

F32 = jnp.float32
EPS = 1e-6  # under the root of a head's sum of squares
# Positions a step of a program's walk. With the L2 norm a step waits for its heads' sums along lanes, and the more
# rows it holds the more of them are in flight: a layer-row's q (4,096 x 2,880) took 578 us at 16 rows a step, 359 at
# 32, 264 at 64, 204 at 128, 219 at 256 on the v5e; without it (v, 4,096 x 5,760) 334, 307, 316, 383, 459: past
# 32 rows the step's arrays leave the registers for nothing (`tools/short_conv_bench.py --set`, PR 54).
ROWS_NORMALIZED, ROWS_PLAIN = 128, 32
# The gated form's rows a step and the channels a program holds at most. Its step has no sum along lanes and HBM
# bounds it at every choice: a layer of the LFM2 cell (8 x 4,096 x 2,048 bf16, 940 MB) took 1,384 / 1,386 / 1,388 /
# 1,396 us at 16 / 32 / 64 / 128 rows a step of a 512-channel tile, 1,391-1,393 at 256 channels, 1,426-1,433 at 128
# (`tools/short_conv_bench.py --form gated --set`, PR 62).
ROWS_GATED, GATED_TILE = 32, 512
PACK = 16  # the sublanes of a bf16 tile: the rows a load of z before the step takes
HALO = 8  # rows kept of the neighbouring step: an f32 tile, and at least taps - 1
LANES = 128
MAX_TILE = 512  # channels a program at most
VMEM_ROOM = 64 << 20  # of the v5e's 128 MiB: a program's blocks, two buffers each


def _shifted(z, n: int):
    """z (B, S, C) moved `n` positions later, zeros before the row's first."""
    return z if n == 0 else jnp.pad(z, ((0, 0), (n, 0), (0, 0)))[:, :z.shape[1]]


def _xla_short_conv(z, taps, heads: int, scale: float, normalize: bool, bias=None):
    """The chain as XLA operations on whole arrays, in float32, rounded once at its end."""
    (b, s, _), n = z.shape, taps.shape[0]
    zf, w = z.astype(F32), taps.astype(F32)
    pre = sum(w[j] * _shifted(zf, n - 1 - j) for j in range(n))
    y = jax.nn.silu(pre if bias is None else pre + bias.astype(F32))
    y = y.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)  # heads first
    if normalize:
        y = y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + EPS)
    if scale != 1.0:
        y = y * scale
    return y.astype(z.dtype)


def _xla_gated_conv(bcu, taps):
    """The gated chain as XLA operations on whole arrays, in float32, rounded once at its end. What is shifted is
    `bcu` itself, not the product z = b u (the same float32 products: a shift moves values and rounds nothing):
    XLA then makes the whole chain one fusion that reads `bcu` and writes y, and no float32 array between them.
    Shifts of the product, or of the widened b and u, come out as a pass of their own that writes float32 arrays
    (`v5e:2x2` compiles, PR 62). Where jax differentiates this chain (off the TPU), db and du of a bf16 `bcu` are
    sums of a rounded term a tap, not one rounding of the float32 sum as the kernel's are."""
    d, n = bcu.shape[2] // 3, taps.shape[0]
    w = taps.astype(F32)

    def z(k):  # z_{t - k}
        moved = _shifted(bcu, k)
        return moved[..., :d].astype(F32) * moved[..., 2 * d:].astype(F32)

    mixed = sum(w[j] * z(n - 1 - j) for j in range(n))
    return (bcu[..., d:2 * d].astype(F32) * mixed).astype(bcu.dtype)


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
@functools.partial(jax.jit, static_argnames=("head", "scale", "normalize", "bias"))
def _step_bwd(before, cur, w, dout, ahead, *, head, scale, normalize, bias=False):
    """A step's positions of a tile, f32: `cur` (rows, tile) of z with the HALO rows `before` it, the cotangent
    `dout` of these positions, and `ahead`, the dpre of the HALO positions after them. With `bias` the last row of
    `w` is the bias, behind the taps. Returns (dz (rows, tile), [the taps' gradients of these positions (and behind
    them the bias's), summed to (8, tile)], dpre's first HALO rows)."""
    n, tile = w.shape[0] - bias, cur.shape[1]
    earlier = _earlier(before, cur, n)
    pre = _taps_sum(w[:n], earlier) + w[n:] if bias else _taps_sum(w, earlier)
    s = jax.nn.sigmoid(pre)
    dy = dout * scale if scale != 1.0 else dout
    if normalize:  # out = u scale, u = y r, r = (sum y^2 + eps)^-1/2: dy = r (g - u sum(g u))
        y = pre * s
        r = jax.lax.rsqrt(_spread(_head_sums(y * y, head), head, tile) + EPS)
        u = y * r
        dy = r * (dy - u * _spread(_head_sums(dy * u, head), head, tile))
    dpre = dy * (s * (1.0 + pre * (1.0 - s)))
    dz = _taps_sum(w[:n] if bias else w, _later(dpre, ahead, n))  # dz_t = sum_j w[j] dpre_{t + (n - 1 - j)}
    dtaps = [(dpre * earlier[n - 1 - j]).reshape(-1, 8, tile).sum(axis=0) for j in range(n)]
    if bias:
        dtaps.append(dpre.reshape(-1, 8, tile).sum(axis=0))
    return dz, dtaps, dpre[:HALO]


@jax.jit
def _step_gated_bwd(z_before, b, c, u, w, g, ahead):
    """A step's positions of a tile of the gated form, f32: b, c, u and the cotangent g of y (rows, tile), the
    HALO rows of z = b u before them, and `ahead`, the dmixed of the HALO positions after them. Returns (db, dc,
    du, [the taps' gradients of these positions, summed to (8, tile)], dmixed's first HALO rows)."""
    n, tile = w.shape[0], b.shape[1]
    earlier = _earlier(z_before, b * u, n)
    dm = g * c
    dz = _taps_sum(w, _later(dm, ahead, n))  # dz_t = sum_j w[j] dm_{t + (n - 1 - j)}
    dtaps = [(dm * earlier[n - 1 - j]).reshape(-1, 8, tile).sum(axis=0) for j in range(n)]
    return dz * u, g * _taps_sum(w, earlier), dz * b, dtaps, dm[:HALO]


# --------------------------------------------------------------------------- the kernel
def rows_a_step(seq: int, normalize: bool) -> int:
    """The positions a step holds: of a row no whole number of the constant's, the most that divide both."""
    return math.gcd(seq, ROWS_NORMALIZED if normalize else ROWS_PLAIN)


def _rows_at(ref, at, rows: int):
    """(the HALO rows before `at`, zeros before the row's first position; the `rows` positions from `at`) of a
    program's block of z, f32."""
    cur = ref[0, pl.ds(at, rows), :].astype(F32)
    before = ref[0, pl.ds(pl.multiple_of(jnp.maximum(at - PACK, 0), PACK), PACK), :].astype(F32)[PACK - HALO:]
    return jnp.where(at > 0, before, 0.0), cur


def _walk(w_ref, dw_ref, seq: int, tile: int, rows: int, step):
    """A program's walk of its row from the end, `rows` positions a step: `step(at, w, ahead) -> (the taps'
    gradients of the positions from `at`, each (8, tile); what the step before needs of this one, (HALO, tile))`
    reads and writes its own blocks; the taps' gradients are summed over the row into `dw_ref`. `w_ref` is the
    taps, or the taps with further rows a channel behind them (`short_conv_bwd`'s bias): a gradient a row."""
    w = w_ref[...].astype(F32)
    n, steps = w.shape[0], seq // rows

    def body(i, carry):
        ahead, sums = carry
        dtaps, ahead = step(pl.multiple_of((steps - 1 - i) * rows, rows), w, ahead)
        return ahead, tuple(a + d for a, d in zip(sums, dtaps))

    zeros = jnp.zeros((HALO, tile), F32)
    _, sums = jax.lax.fori_loop(0, steps, body, (zeros, (zeros,) * n))
    for j in range(n):
        dw_ref[0, j:j + 1, :] = jnp.sum(sums[j], axis=0, keepdims=True)


def _bwd_kernel(z_ref, w_ref, do_ref, dz_ref, dw_ref, **how):
    seq, tile = z_ref.shape[1:]
    rows = rows_a_step(seq, how["normalize"])

    def step(at, w, ahead):
        before, cur = _rows_at(z_ref, at, rows)
        dout = jnp.concatenate([do_ref[0, h, pl.ds(at, rows), :] for h in range(do_ref.shape[1])], axis=1)
        dz, dtaps, ahead = _step_bwd(before, cur, w, dout.astype(F32), ahead, **how)
        dz_ref[0, pl.ds(at, rows), :] = dz.astype(dz_ref.dtype)
        return dtaps, ahead

    _walk(w_ref, dw_ref, seq, tile, rows, step)


def tile_of(head: int) -> int:
    """Channels a program holds: the fewest that are whole heads and whole lane rows."""
    return math.lcm(head, LANES)


def _walk_fits(rows: int, tile: int, channels: int, taps: int, block_bytes: int) -> bool:
    """Whether a kernel of the walk takes the call: whole steps a row, a tile of a few lane rows that is no wider
    than the array, the taps inside the halo, the program's blocks inside `VMEM_ROOM`."""
    return rows % PACK == 0 and 0 < tile <= min(MAX_TILE, channels) and taps - 1 <= HALO and block_bytes <= VMEM_ROOM


def mosaic_fits(shape, head: int, taps: int, itemsize: int, normalize: bool) -> bool:
    """Whether `short_conv_bwd` takes z of `shape`."""
    _, seq, channels = shape
    return _walk_fits(rows_a_step(seq, normalize), tile_of(head), channels, taps, _block_bytes(seq, head, itemsize))


def _block_bytes(seq: int, head: int, itemsize: int) -> int:
    """What a program's blocks hold of VMEM: z, dz and the cotangent (a head's block is whole lane rows wide),
    two buffers each."""
    tile = tile_of(head)
    return 2 * seq * (2 * tile + tile // head * -(-head // LANES) * LANES) * itemsize


def _bwd(z, taps, dout, head, scale, normalize, interpret, bias=False):
    """(dz (B, S, C), dtaps (n, C)) of z, taps and the cotangent `dout` (B, heads, S, head), heads first as it is
    handed back: a program takes its tile's heads each as a block and lays them side by side along lanes. With
    `bias` the last row of `taps`, and of the gradient, is the bias's."""
    b, seq, channels = z.shape
    n, tile = taps.shape[0], tile_of(head)
    wide = pl.BlockSpec((1, seq, tile), lambda i, c: (i, 0, c))
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=_block_bytes(seq, head, z.dtype.itemsize) + (16 << 20))  # and a step's arrays
    with jax.named_scope(f"tile_{tile}"), jax.named_scope(f"rows_{seq}"):
        dz, dw = pl.pallas_call(
            functools.partial(_bwd_kernel, head=head, scale=scale, normalize=normalize, bias=bias),
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
def _kernel_gradient(z, taps, heads, scale, normalize, mesh, interpret, bias=None):
    return _xla_short_conv(z, taps, heads, scale, normalize, bias)


def _kernel_gradient_fwd(z, taps, heads, scale, normalize, mesh, interpret, bias=None):
    return _xla_short_conv(z, taps, heads, scale, normalize, bias), (z, taps, bias)


def _kernel_gradient_bwd(heads, scale, normalize, mesh, interpret, res, dout):
    z, taps, bias = res
    head = z.shape[2] // heads
    if bias is not None:  # a further row behind the taps, to the kernel and in its gradient
        taps = jnp.concatenate([taps, bias[None].astype(taps.dtype)])
    kernel = lambda z, taps, dout: _bwd(z, taps, dout, head, scale, normalize, interpret, bias is not None)  # noqa: E731
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
    if bias is None:
        return dz, dtaps.astype(taps.dtype), None
    return dz, dtaps[:-1].astype(taps.dtype), dtaps[-1].astype(bias.dtype)


_kernel_gradient.defvjp(_kernel_gradient_fwd, _kernel_gradient_bwd)


# --------------------------------------------------------------------------- the gated form's kernel
def _gated_block_bytes(seq: int, tile: int, itemsize: int) -> int:
    """What a program's blocks hold of VMEM: b, c, u, the cotangent and db, dc, du, two buffers each."""
    return 2 * 7 * seq * tile * itemsize


def gated_tile(seq: int, channels: int, itemsize: int) -> int:
    """Channels a program of the gated form holds: the most lane rows, `GATED_TILE` channels at most, that
    divide D (b, c and u are column blocks of one array, D apart) and whose blocks fit; 0 where none does."""
    fit = [t for t in range(LANES, GATED_TILE + 1, LANES)
           if channels % t == 0 and _gated_block_bytes(seq, t, itemsize) <= VMEM_ROOM]
    return max(fit, default=0)


def gated_fits(shape, taps: int, itemsize: int) -> bool:
    """Whether `gated_conv_bwd` takes bcu of `shape` (B, S, 3 D)."""
    _, seq, wide = shape
    tile = gated_tile(seq, wide // 3, itemsize)
    return wide % 3 == 0 and _walk_fits(math.gcd(seq, ROWS_GATED), tile, wide // 3, taps,
                                        _gated_block_bytes(seq, tile, itemsize))


def _gated_bwd_kernel(b_ref, c_ref, u_ref, w_ref, g_ref, dbcu_hbm, dw_ref, out, sem):
    """b, c, u: the program's tile of each third of `bcu`; `dbcu_hbm` the whole result in HBM. A pipelined result
    is one block a program, and this program's are three column blocks of one array: it fills `out[slot]` (db,
    dc, du of its tile) and copies them out itself, while the next program fills the other slot."""
    seq, tile = g_ref.shape[1:]
    rows, d = math.gcd(seq, ROWS_GATED), dbcu_hbm.shape[2] // 3
    row, col = pl.program_id(0), pl.program_id(1)
    program, programs = row * pl.num_programs(1) + col, pl.num_programs(0) * pl.num_programs(1)
    slot = program % 2

    def copy(slot, k, row=0, col=0):
        to = dbcu_hbm.at[row, :, pl.ds(pl.multiple_of(k * d + col * tile, LANES), tile)]
        return pltpu.make_async_copy(out.at[slot, k], to, sem.at[slot])

    def wait(slot):  # a slot's three copies are as large: any stands for each
        for k in range(3):
            copy(slot, k).wait()

    @pl.when(program >= 2)
    def _():
        wait(slot)  # what the program before the last copied out of this slot

    def step(at, w, ahead):
        (b_before, b), (u_before, u) = _rows_at(b_ref, at, rows), _rows_at(u_ref, at, rows)
        c, g = (ref[0, pl.ds(at, rows), :].astype(F32) for ref in (c_ref, g_ref))
        *thirds, dtaps, ahead = _step_gated_bwd(b_before * u_before, b, c, u, w, g, ahead)
        for k, dx in enumerate(thirds):
            out[slot, k, pl.ds(at, rows), :] = dx.astype(out.dtype)
        return dtaps, ahead

    _walk(w_ref, dw_ref, seq, tile, rows, step)
    for k in range(3):
        copy(slot, k, row, col).start()

    @pl.when(program + 1 == programs)
    def _():
        wait(slot)

        @pl.when(program >= 1)
        def _():
            wait(1 - slot)


# A function of its own in the step's program, as `ops/sum_rows.py`'s calls: under `jax.jit` the call with its
# kernel is traced once a process and lowered once a program, not once a conv layer and a trace of the train step.
@functools.partial(jax.jit, static_argnames=("interpret",))
def _gated_bwd(bcu, taps, g, interpret=False):
    """(dbcu (B, S, 3 D) in bcu's type, dtaps (n, D) f32) of bcu, taps and the cotangent g (B, S, D) of y."""
    batch, seq, wide = bcu.shape
    n, d = taps.shape[0], wide // 3
    tile = gated_tile(seq, d, bcu.dtype.itemsize)
    third = lambda k: pl.BlockSpec((1, seq, tile), lambda i, c: (i, 0, k * (d // tile) + c))  # noqa: E731
    params = None if interpret else pltpu.CompilerParams(
        dimension_semantics=("arbitrary", "arbitrary"),  # a program waits for the copies of the one before the last
        vmem_limit_bytes=_gated_block_bytes(seq, tile, bcu.dtype.itemsize) + (16 << 20))  # and a step's arrays
    with jax.named_scope(f"tile_{tile}"), jax.named_scope(f"rows_{seq}"):
        dbcu, dw = pl.pallas_call(
            _gated_bwd_kernel,
            grid=(batch, d // tile),
            in_specs=[third(0), third(1), third(2), pl.BlockSpec((n, tile), lambda i, c: (0, c)),
                      third(0)],  # the cotangent is (B, S, D): its tile stands where b's does
            out_specs=[pl.BlockSpec(memory_space=pl.ANY), pl.BlockSpec((1, n, tile), lambda i, c: (i, 0, c))],
            out_shape=[jax.ShapeDtypeStruct(bcu.shape, bcu.dtype), jax.ShapeDtypeStruct((batch, n, d), F32)],
            scratch_shapes=[pltpu.VMEM((2, 3, seq, tile), bcu.dtype), pltpu.SemaphoreType.DMA((2,))],
            interpret=interpret, name="gated_conv_bwd", compiler_params=params,
            cost_estimate=pl.CostEstimate(flops=30 * g.size, transcendentals=0,
                                          bytes_accessed=7 * g.size * bcu.dtype.itemsize),
        )(bcu, bcu, bcu, taps, g)
    return dbcu, dw.sum(axis=0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _gated_kernel_gradient(bcu, taps, interpret):
    return _xla_gated_conv(bcu, taps)


def _gated_kernel_gradient_fwd(bcu, taps, interpret):
    return _xla_gated_conv(bcu, taps), (bcu, taps)


def _gated_kernel_gradient_bwd(interpret, res, g):
    bcu, taps = res
    dbcu, dtaps = _gated_bwd(bcu, taps, g, interpret=interpret)
    return dbcu, dtaps.astype(taps.dtype)


_gated_kernel_gradient.defvjp(_gated_kernel_gradient_fwd, _gated_kernel_gradient_bwd)


# --------------------------------------------------------------------------- the call
def _backend(name: str, backend: Optional[str], mesh, fits: bool) -> str:
    """The form a call takes: what it asks for, else the kernel where the call is compiled for a TPU (the mesh's
    platform where there is one) and `fits`, the chain elsewhere."""
    if backend is None:
        backend = select_backend(mesh.devices.flat[0].platform if mesh is not None else None) if fits else "xla"
    if backend not in ("pallas", "xla"):
        raise ValueError(f"{name}: backend {backend!r} is neither 'pallas' nor 'xla'")
    return backend


def short_conv(z, taps, heads: int, *, scale: float = 1.0, normalize: bool = False, bias=None, mesh=None,
               backend: Optional[str] = None, interpret: bool = False):
    """`out` (B, heads, S, C / heads) in z's type, of z (B, S, C) and `taps` (n, C): the top of the file.

    heads: C is `heads` heads of consecutive channels. Channels that are no head's to the caller (a Mamba-2 mixer's
      B and C behind its x) are further heads of the same width here, and the caller's to put together again.
    bias: (C,) added to the convolution before the SiLU, or None.
    normalize: L2-normalise y over each head; scale: a factor on the result.
    backend: "pallas" (the gradient by the kernel) | "xla" | None (`select_backend` for the platform the
      computation is compiled for, the mesh's where there is one; "xla" where the shapes do not fit the kernel).
    mesh: the jax.sharding.Mesh the surrounding jit shards over; on more than one device the kernel runs inside
      a shard_map."""
    fits = mosaic_fits(z.shape, z.shape[2] // heads, taps.shape[0], z.dtype.itemsize, normalize)
    if _backend("short_conv", backend, mesh, fits) == "xla":
        return _xla_short_conv(z, taps, heads, float(scale), normalize, bias)
    if z.shape[1] % PACK:
        raise ValueError(f"short_conv: the kernel walks a row {PACK} positions at a time at least, not {z.shape[1]}")
    return _kernel_gradient(z, taps, heads, float(scale), normalize, mesh, interpret, bias)


def gated_short_conv(bcu, taps, *, mesh=None, backend: Optional[str] = None, interpret: bool = False):
    """y (B, S, D) in bcu's type, of bcu = [b | c | u] (B, S, 3 D) and `taps` (n, D): the gated form at the top
    of the file.

    backend: as `short_conv`'s. None also takes "xla" under a mesh of more than one device: the kernel is one
      device's program and has no shard_map round it."""
    fits = gated_fits(bcu.shape, taps.shape[0], bcu.dtype.itemsize)
    if _backend("gated_short_conv", backend, mesh, fits and (mesh is None or mesh.size == 1)) == "xla":
        return _xla_gated_conv(bcu, taps)
    if not fits:
        raise ValueError(f"gated_short_conv: the kernel takes no bcu of {bcu.shape} with {taps.shape[0]} taps")
    return _gated_kernel_gradient(bcu, taps, interpret)

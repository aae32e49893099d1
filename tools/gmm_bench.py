"""The grouped expert matmul alone, on the chip: time per call against the bf16 peak.

    chiprun -- python3 tools/gmm_bench.py
    chiprun -- python3 tools/gmm_bench.py --shapes 65536x2048x1024x64 --references

For each shape (rows x k x n x groups, bf16, rows already in expert order) the
three products a train step needs run alone, `--calls` back-to-back dispatches
closed by `block_until_ready`, median of `--rounds`: `fwd` (`(rows, k) x
(groups, k, n)`), `dlhs` (the gradient for the rows: the same product against
the transposed weights) and `drhs` (the gradient for the weights: per group,
rows^T x rows), each through `ray_tpu.ops.grouped_matmul` (the three Pallas
kernels `ray_tpu/models/moe.py` runs) and through `jax.lax.ragged_dot` with its
own transpose rules (the form the kernels replaced). `pct_of_peak` is 2 * rows * k * n
FLOPs over the time, as a share of the chip's bf16 peak. For the repo's kernels
`issued_over_needed` is the rows of products a call issues over `rows` (a block
of rows that two groups share is multiplied once for each: `issued_rows`; on a
tree before PR 33 a visit multiplied its whole row tile, and the same count is
taken at the tile's size), and `pct_of_peak_issued` the share of the peak on
that issued work: what the MXU does with what it is given. Group sizes are a
seeded multinomial draw (`even`: what random routing gives) and one with a
fifth of the rows on one expert (`skewed`). `--references` adds jax's own
Pallas grouped matmul (`jax.experimental.pallas.ops.tpu.megablox`, a few tile
sizes) for the same products: the yardstick for what a kernel of the repo's
own could reach. One JSON line per shape, product and implementation, on
stdout and in `chiprun_out/gmm_bench.jsonl`.

Runs on TPU chips only. No benchmark cell and no test runs this; it is how the
table in PERF.md (section 6, PR 28 and PR 33) is measured again. A copy of the
file dropped into an older checkout measures that checkout.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# OLMoE-1B-7B's expert layer at 2 x 4,096 tokens, 8 experts a token: gate and
# up projection, then the down projection.
DEFAULT_SHAPES = "65536x2048x1024x64,65536x1024x2048x64"
PEAK_BF16 = {"TPU v5 lite": 197e12}
MEGABLOX_TILES = ((512, 1024, 1024), (512, 512, 1024), (1024, 1024, 1024), (256, 1024, 1024))


def group_sizes(rows: int, groups: int, skewed: bool, seed: int = 0):
    import numpy as np

    p = np.full(groups, 1.0)
    if skewed:
        p[0] = 0.25 * (groups - 1)  # a fifth of all rows
    return np.random.default_rng(seed).multinomial(rows, p / p.sum()).astype(np.int32)


def issued_over_needed(product: str, sizes, rows: int, k: int, n: int) -> float:
    """Rows of products `grouped_matmul`'s kernel for `product` issues, over `rows`."""
    from ray_tpu.ops import grouped_matmul as gm

    if hasattr(gm, "issued_rows"):
        return gm.issued_rows(sizes, gm.DRHS_SUB_ROWS if product == "drhs" else gm.SUB_ROWS) / rows
    # Before PR 33: every (group, row tile) pair multiplied the whole tile.
    tiles = gm._tiles(rows, k, n, 2)
    tile = tiles.drhs_rows if product == "drhs" else tiles.rows
    ends = sizes.cumsum()
    return float((-(-ends // tile) - (ends - sizes) // tile)[sizes > 0].sum()) * tile / rows


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=DEFAULT_SHAPES, help="ROWSxKxNxGROUPS,... (bf16)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--calls", type=int, default=20)
    ap.add_argument("--references", action="store_true",
                    help="also time jax's megablox grouped matmul")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if device["platform"] != "tpu":
        raise SystemExit(f"gmm_bench.py measures TPU chips; jax came up on {device}")
    peak = PEAK_BF16[dev.device_kind]
    out_path = os.path.join(REPO, "chiprun_out", "gmm_bench.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))  # compiled and warm
        readings = []
        for _ in range(args.rounds):
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = fn(*xs)
            jax.block_until_ready(out)
            readings.append((time.perf_counter() - t0) / args.calls * 1e6)
        return median(readings)

    def products(gmm):
        """fwd, dlhs and drhs of `gmm(lhs, rhs, sizes)` through its own vjp."""
        return {
            "fwd": jax.jit(gmm),
            "dlhs": jax.jit(lambda a, b, s, g: jax.vjp(lambda a: gmm(a, b, s), a)[1](g)[0]),
            "drhs": jax.jit(lambda a, b, s, g: jax.vjp(lambda b: gmm(a, b, s), b)[1](g)[0]),
        }

    def implementations():
        from ray_tpu.ops.grouped_matmul import grouped_matmul

        yield "grouped_matmul", lambda a, b, s: grouped_matmul(a, b, s, backend="pallas")
        yield "ragged_dot", lambda a, b, s: jax.lax.ragged_dot(a, b, s)
        if args.references:
            from jax.experimental.pallas.ops.tpu.megablox import gmm as megablox

            for tiles in MEGABLOX_TILES:
                yield f"megablox{list(tiles)}", lambda a, b, s, t=tiles: megablox(
                    a, b, s, jnp.bfloat16, t)

    for shape in args.shapes.split(","):
        rows, k, n, groups = (int(x) for x in shape.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(0), 3)
        lhs = jax.random.normal(keys[0], (rows, k), jnp.float32).astype(jnp.bfloat16)
        rhs = jax.random.normal(keys[1], (groups, k, n), jnp.float32).astype(jnp.bfloat16)
        dout = jax.random.normal(keys[2], (rows, n), jnp.float32).astype(jnp.bfloat16)
        flops = 2.0 * rows * k * n
        for name, gmm in implementations():
            for product, fn in products(gmm).items():
                for routing in ("even", "skewed"):
                    sizes_np = group_sizes(rows, groups, routing == "skewed")
                    sizes = jnp.asarray(sizes_np)
                    xs = (lhs, rhs, sizes) + ((dout,) if product != "fwd" else ())
                    line = {"shape": [rows, k, n, groups], "dtype": "bfloat16", "product": product,
                            "implementation": name, "routing": routing,
                            "largest_group": int(sizes.max())}
                    try:
                        us = timed(fn, *xs)
                        line.update(us=round(us, 1), pct_of_peak=round(100 * flops / (us * 1e-6) / peak, 1),
                                    rounds=args.rounds, calls=args.calls, device=device)
                        if name == "grouped_matmul":
                            issued = issued_over_needed(product, sizes_np, rows, k, n)
                            line.update(issued_over_needed=round(issued, 4),
                                        pct_of_peak_issued=round(line["pct_of_peak"] * issued, 1))
                    except Exception as e:  # a tile size the compiler refuses: say so, go on
                        line["error"] = f"{type(e).__name__}: {e}"[:300]
                    emit(line)


if __name__ == "__main__":
    main()

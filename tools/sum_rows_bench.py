"""The sum of each token's k sorted rows alone, on the chip: time per call against HBM's rate.

    chiprun -- python3 tools/sum_rows_bench.py
    chiprun -- python3 tools/sum_rows_bench.py --shapes 8192x8x2048x64

For each shape (tokens x k x width x experts, bf16, rows already in expert
order) `out[t] = sum of rows[inverse[t * k : t * k + k]]` runs alone, `--calls`
back-to-back dispatches closed by `block_until_ready`, median of `--rounds`:
through `ray_tpu.ops.sum_rows` (the Pallas kernel `ray_tpu/models/moe.py` runs
as `combine` and as the gradient of `dispatch`) and through the XLA form it
replaced (a gather by `inverse`, then a sum over k). `gb_per_s` is the bytes a
call needs (every row read once, every sum written once) over the time, and
`pct_of_hbm` that as a share of the chip's HBM rate. For the kernel
`read_over_needed` is the rows its copies move over `tokens * k` (`rows_read`:
a run of a block's rows in an expert's group is read in whole 8-row tiles,
`tiles_over_needed`, and a block in whole chunks of 512 rows).
The routing is a seeded draw of k distinct experts a token (`even`: uniform;
`skewed`: one expert six times as likely as another, 4.3 times the mean load
at 8 of 64), and `load_max_over_mean` says what it came to. One JSON line per shape,
implementation and routing, on stdout and in `chiprun_out/sum_rows_bench.jsonl`.

Runs on TPU chips only. No benchmark cell and no test runs this; it is how the
table in PERF.md (section 6, PR 34) is measured again. A copy of the file
dropped into an older checkout measures the XLA form there.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# OLMoE-1B-7B's expert layer at 2 x 4,096 tokens: 8 of 64 experts a token, width 2,048.
DEFAULT_SHAPES = "8192x8x2048x64"
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}


def draw_experts(tokens: int, k: int, experts: int, skewed: bool, seed: int = 0):
    """(tokens, k) int32: k distinct experts a token, the k largest of seeded
    Gumbel draws; `skewed` makes expert 0 six times as likely as another."""
    import numpy as np

    logits = np.zeros(experts)
    if skewed:
        logits[0] = np.log(6.0)
    noise = np.random.default_rng(seed).gumbel(size=(tokens, experts))
    return np.argsort(-(logits + noise), axis=1)[:, :k].astype(np.int32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=DEFAULT_SHAPES, help="TOKENSxKxWIDTHxEXPERTS,... (bf16)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--calls", type=int, default=20)
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if device["platform"] != "tpu":
        raise SystemExit(f"sum_rows_bench.py measures TPU chips; jax came up on {device}")
    hbm = HBM_BYTES_PER_S[dev.device_kind]
    out_path = os.path.join(REPO, "chiprun_out", "sum_rows_bench.jsonl")
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

    def xla_form(rows, inverse, experts, k, n_experts):
        by_token = rows[inverse].reshape(-1, k, rows.shape[-1])
        return by_token.astype(jnp.float32).sum(axis=1).astype(rows.dtype)

    implementations = {"xla": xla_form}
    try:
        from ray_tpu.ops import sum_rows as sr

        implementations["sum_rows"] = lambda rows, inverse, experts, k, n_experts: sr.sum_rows(
            rows, inverse, sr.sorted_runs(experts, n_experts), k, backend="pallas")
    except ImportError:  # a tree before PR 34
        sr = None

    for shape in args.shapes.split(","):
        tokens, k, width, n_experts = (int(x) for x in shape.split("x"))
        rows = jax.random.normal(jax.random.PRNGKey(0), (tokens * k, width), jnp.float32).astype(jnp.bfloat16)
        needed_bytes = (tokens * k + tokens) * width * rows.dtype.itemsize
        for routing in ("even", "skewed"):
            drawn = draw_experts(tokens, k, n_experts, routing == "skewed")
            load = np.bincount(drawn.reshape(-1), minlength=n_experts)
            experts = jnp.asarray(drawn)
            # Where each (token, slot) pair goes in the stable sort by expert: `models/moe.py`'s
            # `inverse`, made here so that the file runs in any checkout.
            inverse = jnp.argsort(jnp.argsort(experts.reshape(-1), stable=True))
            want = None
            for name, fn in implementations.items():
                line = {"shape": [tokens, k, width, n_experts], "dtype": "bfloat16", "implementation": name,
                        "routing": routing, "load_max_over_mean": round(float(load.max() / load.mean()), 2)}
                try:
                    jitted = jax.jit(fn, static_argnums=(3, 4))
                    us = timed(jitted, rows, inverse, experts, k, n_experts)
                    got = np.asarray(jitted(rows, inverse, experts, k, n_experts), np.float32)
                    want = got if want is None else want
                    line.update(us=round(us, 1), gb_per_s=round(needed_bytes / us / 1e3, 1),
                                pct_of_hbm=round(100 * needed_bytes / (us * 1e-6) / hbm, 1),
                                max_abs_diff_from_xla=float(np.abs(got - want).max()),
                                rounds=args.rounds, calls=args.calls, device=device)
                    if name == "sum_rows":
                        chunk_rows = sr.chunk_rows(width, rows.dtype.itemsize)
                        line.update(
                            read_over_needed=round(sr.rows_read(drawn, chunk_rows) / (tokens * k), 4),
                            tiles_over_needed=round(sr.rows_read(drawn, sr.PIECE) / (tokens * k), 4))
                except Exception as e:  # a shape the compiler refuses: say so, go on
                    line["error"] = f"{type(e).__name__}: {e}"[:300]
                emit(line)


if __name__ == "__main__":
    main()

"""The two row movers of the expert layer alone, on the chip: time per call against HBM's rate.

    chiprun -- python3 tools/sum_rows_bench.py
    chiprun -- python3 tools/sum_rows_bench.py --shapes 8192x8x2048x64 --held 0

For each shape (tokens x k x width x experts [x held], bf16; the defaults are
four expert cells': LFM2's and GLM-4.7-Flash's layers, which hold 8 of 64
experts, SmallThinker's, which holds 16 of 64, and OLMoE's, which holds all)
two things run alone, `--calls` back-to-back dispatches closed by
`block_until_ready`, median of `--rounds`:

- the sum, `out[t] = sum of rows[inverse[t * k : t * k + k]]` over rows in
  expert order: `sum_rows` (the Pallas kernel `ray_tpu/models/moe.py` runs as
  `combine` and as the gradient of `dispatch`) and `xla`, the gather by
  `inverse` and sum over k that it replaced;
- the gather, `x[order // k]`: `xla_gather`, the instruction itself, and
  `gather_rows`, the kernel the prefix form runs (no line where the shape holds
  every expert: `models/moe.py` keeps XLA's gather there). With `--sweep`, XLA's
  gather once more for 8,192 / 32,768 / 65,536 indices out of each source.
  `xla_gather_of_two` is the same gather of two sources of one size in one
  program, what a backward pass does (the tokens for `dispatch` made again, the
  cotangent for `combine`'s transpose): `in_vmem` says, gather by gather in the
  compiled program's order, whether XLA's memory-space assignment marked the
  source `S(1)` (copied into VMEM first: 7-8 ns a row) or left it in HBM (a copy
  descriptor's 36-41 ns a row), and `us_over_one` what the second gather cost
  over `xla_gather` alone. A gather alone gets VMEM for any source under the
  v5e's 128 MiB, so its time says nothing of the step's (PERF.md section 6,
  PR 72); where the step's own schedule leaves a source is in its compiled text
  (`tests/aot_v5e.py row_gathers`).

Where a layer holds `held` of the experts (`--held N` for shapes that do not
say), the pairs of the others sort behind the held ones and the sorted rows
are the first `moe.held_row_bound` alone, as in the step; a routing that owns
more than the bound is said and skipped (the step takes the whole-length form).
`gb_per_s` is the bytes a call needs (the sum: every owned row read once,
every sum written once; the gather: every token read once, every owned row
written once) over the time, `pct_of_hbm` that as a share of the chip's HBM
rate. `read_over_needed` is the rows `sum_rows`' copies move over the owned
rows (`rows_read`: a run is read in whole 8-row tiles, `tiles_over_needed`,
and at 512-row chunks a block in whole chunks), `written_over_needed` the same
for `gather_rows`' writes (`rows_written`). `same_as_xla` says whether every
owned row (every sum) equals the XLA form's, bit for bit, and `sha` is a
digest of those bytes: two checkouts that print the same `sha` computed the
same numbers. The routing is a seeded draw of k distinct experts a token
(`even`: uniform; `skewed`: one expert six times as likely as another), and
`load_max_over_mean` says what it came to. One JSON line per shape,
implementation and routing, on stdout and in `chiprun_out/sum_rows_bench.jsonl`.

Runs on TPU chips only. No benchmark cell and no test runs this; it is how the
tables in PERF.md (section 6, PR 34 and PR 40) are measured again. A copy of
the file dropped into an older checkout measures what that checkout has (before
PR 40: no `gather_rows`, `sum_rows` at chunks of 512; before PR 34: XLA alone).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# The expert layers of `lfm2-24b-a2b-ep8-l5` (8 x 4,096 tokens, 4 of 64 experts a token, 8 held),
# `glm-4.7-flash-ep8-l5` (2 x 4,096, likewise), `smallthinker-21b-a3b-l4` (1 x 16,384 of 2,560, 6 of 64, 16 held)
# and `olmoe-1b-7b-l1` (2 x 4,096, 8 of 64, all held).
DEFAULT_SHAPES = "32768x4x2048x64x8,8192x4x2048x64x8,16384x6x2560x64x16,8192x8x2048x64x0"
HBM_BYTES_PER_S = {"TPU v5 lite": 819e9}
HELD_ROWS_OVER_EVEN, ROW_TILE = 2, 512  # `models/moe.py held_row_bound`, for a checkout without it


def draw_experts(tokens: int, k: int, experts: int, skewed: bool, seed: int = 0):
    """(tokens, k) int32: k distinct experts a token, the k largest of seeded
    Gumbel draws; `skewed` makes expert 0 six times as likely as another."""
    import numpy as np

    logits = np.zeros(experts)
    if skewed:
        logits[0] = np.log(6.0)
    noise = np.random.default_rng(seed).gumbel(size=(tokens, experts))
    return np.argsort(-(logits + noise), axis=1)[:, :k].astype(np.int32)


def sources_in_vmem(compiled_text: str):
    """For every row gather of a compiled program's text, in its order: whether the layout of the gather's
    source, the first parameter of the fused computation it stands in, carries `S(1)`."""
    marks, source = [], ""
    for line in compiled_text.splitlines():
        if " parameter(0)" in line:
            source = line
        elif " gather(" in line:
            marks.append("S(1)" in source.split(" parameter(0)")[0])
    return marks


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=DEFAULT_SHAPES, help="TOKENSxKxWIDTHxEXPERTS[xHELD],... (bf16)")
    ap.add_argument("--held", type=int, default=0, help="experts held, for shapes that do not say (0: all)")
    ap.add_argument("--sweep", action="store_true", help="XLA's gather by index count, out of each source")
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

    try:
        from ray_tpu.ops import sum_rows as sr
    except ImportError:  # a tree before PR 34
        sr = None
    has_gather = sr is not None and hasattr(sr, "gather_rows")

    def runs_of(local, held, partial):
        return sr.sorted_runs(local, held, True) if partial else sr.sorted_runs(local, held)

    def xla_sum(rows, order, inverse, local, k, held, partial):
        by_token = rows.at[inverse].get(mode="fill", fill_value=0) if partial else rows[inverse]
        return by_token.reshape(-1, k, rows.shape[-1]).astype(jnp.float32).sum(axis=1).astype(rows.dtype)

    def kernel_sum(rows, order, inverse, local, k, held, partial):
        return sr.sum_rows(rows, inverse, runs_of(local, held, partial), k, backend="pallas")

    def xla_gather(x, order, inverse, local, k, held, partial):
        return x[order // k]

    def kernel_gather(x, order, inverse, local, k, held, partial):
        return sr.gather_rows(x, order, inverse, runs_of(local, held, partial), k, backend="pallas")

    sums = {"xla": xla_sum, **({"sum_rows": kernel_sum} if sr else {})}
    gathers = {"xla_gather": xla_gather, **({"gather_rows": kernel_gather} if has_gather else {})}

    for shape in args.shapes.split(","):
        tokens, k, width, n_experts, *held = (int(x) for x in shape.split("x"))
        held = (held[0] if held else args.held) or n_experts
        partial = held < n_experts
        pairs = tokens * k
        n = pairs  # the rows of the sorted form
        if partial:
            n = min(pairs, -(-HELD_ROWS_OVER_EVEN * pairs * held // n_experts // ROW_TILE) * ROW_TILE)
        x = jax.random.normal(jax.random.PRNGKey(1), (tokens, width), jnp.float32).astype(jnp.bfloat16)
        rows = jax.random.normal(jax.random.PRNGKey(0), (n, width), jnp.float32).astype(jnp.bfloat16)
        for routing in ("even", "skewed"):
            drawn = draw_experts(tokens, k, n_experts, routing == "skewed")
            load = np.bincount(drawn.reshape(-1), minlength=n_experts)
            local = np.where(drawn < held, drawn, held).astype(np.int32) if partial else drawn
            owned = int((local < held).sum())
            base = {"shape": [tokens, k, width, n_experts], "held": held, "sorted_rows": n, "owned_rows": owned,
                    "dtype": "bfloat16", "routing": routing,
                    "load_max_over_mean": round(float(load.max() / load.mean()), 2)}
            if owned > n:
                emit({**base, "skipped": "owns more rows than the bound: the step takes the whole-length form"})
                continue
            # `models/moe.py`'s `order` and `inverse`, made here so that the file runs in any checkout.
            order = jnp.argsort(jnp.asarray(local).reshape(-1), stable=True)
            inverse = jnp.argsort(order)
            is_owned = jnp.arange(n) < owned
            operands = {"sum": jnp.where(is_owned[:, None], rows, 0), "gather": x}  # as `moe_mlp` masks them
            needed = {"sum": (owned + tokens) * width * 2, "gather": (tokens + owned) * width * 2}
            for what, implementations in (("sum", sums), ("gather", gathers)):
                want = None
                for name, fn in implementations.items():
                    if name == "gather_rows" and not partial:
                        continue
                    line = {**base, "implementation": name}
                    try:
                        jitted = jax.jit(fn, static_argnums=(4, 5, 6))
                        operand = (operands[what], order[:n], inverse, jnp.asarray(local), k, held, partial)
                        us = timed(jitted, *operand)
                        got = np.asarray(jitted(*operand).astype(jnp.float32))
                        got = got[:owned] if what == "gather" else got
                        want = got if want is None else want
                        line.update(us=round(us, 1), gb_per_s=round(needed[what] / us / 1e3, 1),
                                    pct_of_hbm=round(100 * needed[what] / (us * 1e-6) / hbm, 1),
                                    same_as_xla=bool(np.array_equal(got, want)),
                                    max_abs_diff_from_xla=float(np.abs(got - want).max()),
                                    sha=hashlib.sha1(got.tobytes()).hexdigest()[:12],
                                    rounds=args.rounds, calls=args.calls, device=device)
                        if name == "sum_rows":
                            try:
                                chunk = sr.chunk_rows(width, 2, k, n, pairs)
                                read = [sr.rows_read(local, c, held if partial else None) for c in (chunk, sr.PIECE)]
                            except TypeError:  # before PR 40: one chunk size, every pair owned
                                chunk = sr.chunk_rows(width, 2)
                                read = None if partial else [sr.rows_read(local, c) for c in (chunk, sr.PIECE)]
                            line.update(chunk_rows=chunk)
                            if read:
                                line.update(read_over_needed=round(read[0] / owned, 4),
                                            tiles_over_needed=round(read[1] / owned, 4))
                        if name == "gather_rows":
                            line.update(written_over_needed=round(sr.rows_written(local, held) / owned, 4))
                    except Exception as e:  # a shape the compiler refuses: say so, go on
                        line["error"] = f"{type(e).__name__}: {e}"[:300]
                    emit(line)
            if routing == "even":  # a second source of the same size beside the first, in one program
                one, two = jax.jit(lambda x, index: x[index]), jax.jit(lambda x, g, index: (x[index], g[index]))
                index, g = order[:n] // k, x + jnp.ones((), x.dtype)
                us_one, us_two = timed(one, x, index), timed(two, x, g, index)
                emit({**base, "implementation": "xla_gather_of_two", "us": round(us_two, 1),
                      "us_over_one": round(us_two - us_one, 1),
                      "ns_a_row_of_the_second": round((us_two - us_one) * 1e3 / n, 2),
                      "in_vmem": sources_in_vmem(two.lower(x, g, index).compile().as_text()),
                      "alone_in_vmem": sources_in_vmem(one.lower(x, index).compile().as_text()),
                      "source_mib": tokens * width * 2 / 2 ** 20, "rounds": args.rounds, "calls": args.calls,
                      "device": device})
            if args.sweep and routing == "even":
                gather = jax.jit(lambda x, index: x[index])
                for count in (8192, 32768, 65536):
                    if count <= pairs:
                        us = timed(gather, x, order[:count] // k)
                        emit({**base, "implementation": "xla_gather", "sweep": True, "source_rows": tokens,
                              "indices": count, "us": round(us, 1), "ns_a_row": round(us * 1e3 / count, 2),
                              "written_gb_per_s": round(count * width * 2 / us / 1e3, 1), "device": device})


if __name__ == "__main__":
    main()

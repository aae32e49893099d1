"""The indexer's loss kernel alone, on the chip: time per call by tile size and by half.

    chiprun -- python3 tools/index_loss_bench.py
    chiprun -- python3 tools/index_loss_bench.py --tiles 256x512,512x512 --part heads,indexer,empty

One call of `ops/lightning_indexer.py index_loss` with the three gradients it
keeps (`jax.value_and_grad` over q_i, k_i, w: the kernel, the sum of its dkI
shares and the re-ordering of its operands; nothing else runs, the backward
pass only scales) at `--shape BATCHxHEADSxKVHEADSxSEQxD --indexer HEADSxD
--topk K`, bf16, the Keye cell's by default. Inputs come from `--seed`; the
selection and its row statistic are `select`'s, the attention's log-sum-exp is
`flash_attention(keep=, return_lse=True)`'s, as in the model's `attend`.

For each of `--tiles` (QxK; `plan` is what the module chooses for the shape;
`--scores kept|twice` says what a program does with the pair's index scores,
by default what `_loss_bytes` lets it) and each of `--part`, a JSON line, on stdout and in
`chiprun_out/index_loss_bench.jsonl`: `call_us`, the host's clock over `--calls`
back-to-back dispatches closed by `block_until_ready`, median of `--rounds`;
`kernel_us`, the device time of the Mosaic call named `index_loss` in a trace of
`--rounds` calls, median (what `kernels.index_loss_ms` sums a step); the programs
of its grid; `compile_s`. `--part` times a half alone, by standing a stub where
the other half is (`_pair_probabilities`: a constant; `_pair_gradients`: the sum
of the probabilities): `heads` the 32 heads' products and exponentials, `indexer`
the index scores and their gradients, `empty` a program that does neither (the
grid's steps, the blocks' copies, the Q tile's scaling), `whole` the kernel. A
checkout whose kernel has no such halves (before PR 43) times `whole` alone.

Before the timings, once: the kernel against `_xla_index_loss` on a row of
`--check-seq` positions (same heads and widths, bf16): the largest error of the
loss and of each gradient, as a share of the yardstick's largest value.

Runs on TPU chips only. No benchmark cell and no test runs this; it is how PERF.md's
table (section 6, PR 43) is measured again. It reads the module's tiles through
`_loss_plan` where the tree has it and through `LOSS_TILE_Q` / `LOSS_TILE_K`
where it does not, so a copy of this file dropped into an older checkout
measures that checkout.
"""

from __future__ import annotations

import argparse
import glob
import importlib
import json
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("whole", "heads", "indexer", "empty")


def _kernel_us(trace_dir):
    """Device time, us, of each `index_loss` Mosaic call of the first chip, in time order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    return [ev.duration_ns / 1e3 for line in plane.lines if line.name == "XLA Ops"
            for ev in sorted(line.events, key=lambda ev: ev.start_ns)
            if ev.name.split(" = ", 1)[0].lstrip("%").startswith("index_loss")]


def _inputs(jax, jnp, li, fa, shape, indexer, topk, seed):
    batch, heads, kv_heads, seq, d = shape
    index_heads, d_i = indexer
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    normal = lambda key, *dims: jax.random.normal(key, dims, jnp.float32)
    q, k = normal(keys[0], batch, heads, seq, d).astype(jnp.bfloat16), normal(keys[1], batch, kv_heads, seq, d).astype(jnp.bfloat16)
    q_i = normal(keys[2], batch, index_heads, seq, d_i).astype(jnp.bfloat16)
    k_i = normal(keys[3], batch, seq, d_i).astype(jnp.bfloat16)
    w = normal(keys[4], batch, seq, index_heads) * (index_heads * d_i) ** -0.5
    keep, lse_i = jax.jit(lambda q_i, k_i, w: li.select(q_i, k_i, w, topk))(q_i, k_i, w)
    _, lse = jax.jit(lambda q, k, keep: fa.flash_attention(q, k, k, causal=True, keep=keep, return_lse=True))(q, k, keep)
    return jax.block_until_ready((q, k, lse, keep, q_i, k_i, w, lse_i))


def _stand_in(li, jnp, part):
    """The module's two halves, a stub where `part` leaves one out; () to put them back."""
    kept = {name: getattr(li, name) for name in ("_pair_probabilities", "_pair_gradients")}

    def constant(qs, k_ref, lse_ref, *, heads, **_):
        return jnp.full((k_ref.shape[2], qs.shape[1]), heads / 2048.0, jnp.float32)

    def summed(p, mask, qi_ref, ki_ref, w_ref, lsei_ref, dqi_acc, dw_acc, loss_acc, dki_ref, **_):
        loss_acc[...] += jnp.sum(p, axis=0, keepdims=True)
        dki_ref[0, 0] = jnp.zeros(dki_ref.shape[2:], jnp.float32)

    if part in ("indexer", "empty"):
        li._pair_probabilities = constant
    if part in ("heads", "empty"):
        li._pair_gradients = summed
    return lambda: [setattr(li, name, fn) for name, fn in kept.items()]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x32x4x16384x128", help="BATCHxHEADSxKVHEADSxSEQxD (bf16)")
    ap.add_argument("--indexer", default="16x64", help="HEADSxD of the indexer")
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiles", default="plan", help="QxK,... or `plan`: what the module chooses")
    ap.add_argument("--scores", default="plan", choices=("plan", "kept", "twice"),
                    help="the pair's index scores kept between their two uses or made twice; `plan`: kept where they fit")
    ap.add_argument("--part", default="whole", help=",".join(PARTS) + ", comma separated")
    ap.add_argument("--check-seq", type=int, default=2048, help="the row checked against the XLA form (0: no check)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--calls", type=int, default=4)
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    li = importlib.import_module("ray_tpu.ops.lightning_indexer")
    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if device["platform"] != "tpu":
        raise SystemExit(f"index_loss_bench.py measures TPU chips; jax came up on {device}")
    out_path = os.path.join(REPO, "chiprun_out", "index_loss_bench.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")

    shape = tuple(int(n) for n in args.shape.split("x"))
    indexer = tuple(int(n) for n in args.indexer.split("x"))
    halves = hasattr(li, "_pair_probabilities")
    planned = hasattr(li, "_loss_plan")
    the_plan = li._loss_plan if planned else None

    def set_tiles(tiles, scores):
        """Stand a plan of the asked tiles (and of the asked way with the scores) where the module's is."""
        if not planned:
            if tiles != "plan":
                tile_q, _, tile_k = tiles.partition("x")
                li.LOSS_TILE_Q, li.LOSS_TILE_K = int(tile_q), int(tile_k or tile_q)
            return

        def asked(*shape):
            plan = the_plan(*shape)
            tile_q, _, tile_k = tiles.partition("x")
            tile_q, tile_k = (plan.tile_q, plan.tile_k) if tiles == "plan" else (int(tile_q), int(tile_k or tile_q))
            tile_q, tile_k = int(np.gcd(shape[2], tile_q)), int(np.gcd(shape[2], tile_k))
            size = lambda keep: li._loss_bytes(tile_q, tile_k, keep, *shape[:2], *shape[3:])
            keep = {"kept": True, "twice": False}.get(scores, size(True) <= li.LOSS_VMEM_BYTES)
            return li.LossPlan(tile_q, tile_k, keep, size(keep))

        li._loss_plan = the_plan if (tiles, scores) == ("plan", "plan") else asked

    def plan_of(seq):
        if planned:
            return dict(li._loss_plan(shape[1], shape[2], seq, shape[4], *indexer, 2)._asdict())
        return {"tile_q": int(np.gcd(seq, li.LOSS_TILE_Q)), "tile_k": int(np.gcd(seq, li.LOSS_TILE_K))}

    def graded(backend):
        loss = lambda q_i, k_i, w, q, k, lse, keep, lse_i: li.index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, backend=backend)
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)))

    def call(fn, xs):
        q, k, lse, keep, q_i, k_i, w, lse_i = xs
        return fn(q_i, k_i, w, q, k, lse, keep, lse_i)

    if args.check_seq:
        short = _inputs(jax, jnp, li, fa, shape[:3] + (args.check_seq, shape[4]), indexer, min(args.topk, args.check_seq // 4), args.seed)
        want, want_g = call(graded("xla"), short)
        for tiles in args.tiles.split(","):
            set_tiles(tiles, args.scores)
            try:
                got, got_g = call(graded("pallas"), short)
            except Exception as e:  # tiles the compiler refuses: say so, go on
                emit({"check_seq": args.check_seq, "tiles": tiles, "error": str(e)[:300]})
                continue
            rel = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)).max() / jnp.abs(b.astype(jnp.float32)).max())
            emit({"check_seq": args.check_seq, "plan": plan_of(args.check_seq), "loss": float(got), "loss_xla": float(want),
                  "loss_rel_err": abs(float(got) - float(want)) / abs(float(want)),
                  **{f"d{name}_max_err_over_max": rel(a, b) for name, a, b in zip(("q_i", "k_i", "w"), got_g, want_g)}})

    xs = _inputs(jax, jnp, li, fa, shape, indexer, args.topk, args.seed)
    seq = shape[3]
    cases = []
    for tiles in args.tiles.split(","):
        for part in args.part.split(","):
            if part not in PARTS or (part != "whole" and not halves):
                emit({"tiles": tiles, "part": part, "error": "this checkout's kernel has no such half to time alone"})
                continue
            set_tiles(tiles, args.scores)
            put_back = _stand_in(li, jnp, part) if halves else (lambda: None)
            fn = graded("pallas")
            t0 = time.perf_counter()
            try:
                jax.block_until_ready(call(fn, xs))
            except Exception as e:  # tiles the compiler refuses: say so, go on
                emit({"tiles": tiles, "part": part, "error": str(e)[:300]})
                continue
            finally:
                put_back()
            plan = plan_of(seq)
            tile_q, tile_k = plan["tile_q"], plan["tile_k"]
            pairs = sum(-(-((i + 1) * tile_q) // tile_k) for i in range(seq // tile_q))
            cases.append({"plan": plan, "part": part, "fn": fn, "compile_s": time.perf_counter() - t0,
                          "programs": shape[0] * pairs * (1 if halves else shape[1]), "call_us": []})
    for _ in range(args.rounds):
        for c in cases:
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = call(c["fn"], xs)
            jax.block_until_ready(out)
            c["call_us"].append((time.perf_counter() - t0) / args.calls * 1e6)
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(args.rounds):
            for c in cases:
                jax.block_until_ready(call(c["fn"], xs))
        jax.profiler.stop_trace()
        kernel_us = _kernel_us(trace_dir)
    if len(kernel_us) != args.rounds * len(cases):
        raise SystemExit(f"{len(kernel_us)} `index_loss` calls in the trace, {args.rounds * len(cases)} expected")
    for n, c in enumerate(cases):
        emit({"shape": list(shape), "indexer": list(indexer), "topk": args.topk, "seed": args.seed, "dtype": "bfloat16",
              "plan": c["plan"], "part": c["part"], "programs": c["programs"],
              "call_us": round(median(c["call_us"]), 1), "kernel_us": round(median(kernel_us[n::len(cases)]), 1),
              "compile_s": round(c["compile_s"], 2), "rounds": args.rounds, "calls": args.calls, "device": device})


if __name__ == "__main__":
    main()

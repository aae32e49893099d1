"""The flash attention kernels alone, on the chip: time per call by tile size.

    chiprun -- python3 tools/flash_bench.py
    chiprun -- python3 tools/flash_bench.py --shapes 32x2048x128 --tiles 512,256,128x256
    chiprun -- python3 tools/flash_bench.py --shapes 32x16384x128 --kv-heads 4 --keep-topk 2048 \
        --tiles plan --part whole,products,softmax,empty    # the Keye cell's call (PERF.md section 6, PR 44)
    chiprun -- python3 tools/flash_bench.py --shapes 32x16384x128 --kv-heads 4 --block-diffusion 4 \
        --tiles plan    # the SDAR cell's call: two copies of 8,192 under the block-diffusion mask (PR 47)

For each shape (batch*heads x seq x head_dim, bf16) and each tile size
(`block_q = block_k`, or `QxK`), the forward kernel and forward + backward
(`jax.vjp`: both Mosaic calls and the XLA `delta` reduction between them) run
`--rounds` times each under the profiler, tile sizes alternating inside a
round so drift of the machine lands on every tile size alike. `fwd_us` /
`bwd_us` are medians of the device time of the `flash_fwd` / `flash_bwd`
Mosaic calls in that trace: what `kernels.flash_*_ms` of the benchmark sums
per step. `*_host_us` is the host's clock over `--calls` back-to-back
dispatches closed by `block_until_ready`; it carries each program's launch
(≈ 0.17 ms a call on the v5e, PR 26), so it is the cross-check, not the number.
`--kv-heads N` gives k and v N heads for the shape's BH query heads, and
`--keep-topk K` a selection from `--seed` (`pack_keep`): for every query K keys
of its past, or all of it where it is shorter, the cell's kind of `keep`; both
send the call to the pair-streamed kernels. `--block-diffusion B` reads the shape's SEQ as a clean and a noised
copy of SEQ / 2 in blocks of B and runs the kernels under `BlockDiffusion` (a mask by structure, `causal=`), and
adds to the line the kept pairs a head and each pass's share of the MXU's peak on them (`fwd_mxu_pct`, two products
forward; `bwd_mxu_pct`, four backward: the compute floor of `kernels.flash_roofline`). `--part` times the forward kernel's
halves alone, by standing a stub where the other is (`_softmax_step`: the scores
cast and no statistic; `_scores_t` / `_values_t`: a constant and a sum of eight
rows): `products` its two products (`scores`, `values`: the first, the second
alone), `softmax` its maximum, exponential and sum, `empty` none of them (the
grid's steps, the loop over key blocks, the blocks' copies, the mask, `o^T`'s
scaling), `whole` the kernel; a checkout whose forward has no such halves
(before PR 44) times `whole` alone, and a half has no backward pass to time.
One JSON line per shape, tile size and part, on stdout and in
`chiprun_out/flash_bench.jsonl`. `--references` adds one line each for jax's
own kernels at the same shape (`jax.experimental.pallas.ops.tpu`: the
reference flash attention and splash attention, default block sizes): the
summed device time of all their Mosaic calls, forward, and backward as
(forward + backward) less forward.

Runs on TPU chips only: anything else is a failed run, as in `benchmark/run.py`. No
benchmark cell and no test runs this; it is how a kernel change re-measures
the table in PERF.md (section 6, PR 26) before it touches a train step. It imports
only `flash_attention` (and `kernel_plan` where the tree has it), so a copy
of this file dropped into an older checkout measures that checkout.
"""

from __future__ import annotations

import argparse
import functools
import glob
import importlib
import json
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# Both benchmark configurations' per-chip attention: gpt2-medium 8 rows x 16
# heads, gpt2-xl-fsdp4 4 rows x 25 heads, 1,024 tokens, head_dim 64.
DEFAULT_SHAPES = "128x1024x64,100x1024x64"
DEFAULT_TILES = "1024,512,256,128"
PARTS = ("whole", "products", "scores", "values", "softmax", "empty")
HALVES = ("_scores_t", "_softmax_step", "_values_t")


def _parse_tile(text):
    """`N` or `QxK`; `plan`: no tile asked for, the module's own choice."""
    if text == "plan":
        return None, None
    q, _, k = text.partition("x")
    return int(q), int(k or q)


def _selection(jax, jnp, fa, seq, topk, seed):
    """(1, seq, spans * 128) int32: for each query the `topk` keys of its past
    with the largest of seeded uniform scores, a block of 1,024 queries at a time."""
    rows = min(seq, 1024)

    def block(start):
        scores = jax.random.uniform(jax.random.fold_in(jax.random.PRNGKey(seed), start), (rows, seq))
        past = start + jnp.arange(rows)[:, None] >= jnp.arange(seq)[None, :]
        scores = jnp.where(past, scores, -1.0)
        tau = jax.lax.top_k(scores, min(topk, seq))[0][:, -1:]
        return fa.pack_keep((scores >= tau) & past)

    return jax.jit(lambda: jax.lax.map(block, jnp.arange(0, seq, rows)).reshape(1, seq, -1))()


def _check(jax, jnp, fa, bh, kv_heads, seq, d, args):
    """The kernels against `xla_attention` on a row of `seq`, bf16, every tile of `--tiles`: the largest
    error of o, of the row's log-sum-exp and of the three gradients, each over the yardstick's largest value."""
    keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
    q, k, v, do = (jax.random.normal(kk, (1, heads, seq, d), jnp.float32).astype(jnp.bfloat16)
                   for kk, heads in zip(keys, (bh, kv_heads, kv_heads, bh)))
    topk = min(args.keep_topk, seq // 4)
    extra = {"keep": _selection(jax, jnp, fa, seq, topk, args.seed)} if topk else {}

    causal = fa.BlockDiffusion(seq // 2, args.block_diffusion) if args.block_diffusion else not args.non_causal

    def run(attn, **kw):
        def f(q, k, v):
            o, lse = attn(q, k, v, causal=causal, return_lse=True, **extra, **kw)
            return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), (o, lse)
        grads, (o, lse) = jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))(q, k, v)
        return {"o": o, "lse": lse, **dict(zip(("dq", "dk", "dv"), grads))}

    want = run(fa.xla_attention)
    line = {"check_seq": seq, "kv_heads": kv_heads, "keep_topk": topk, "seed": args.seed}
    for tiles in args.tiles.split(","):
        tile_q, tile_k = _parse_tile(tiles)
        try:
            got = run(fa.flash_attention, backend="pallas", block_q=tile_q, block_k=tile_k)
        except Exception as e:  # a tile size the compiler refuses
            line[tiles] = str(e)[:200]
            continue
        f32 = lambda x: x.astype(jnp.float32)
        line[tiles] = {name: float(jnp.abs(f32(got[name]) - f32(want[name])).max()
                                                  / jnp.abs(f32(want[name])).max()) for name in want}
    return line


def _stand_in(jax, fa, jnp, part):
    """The forward kernel's halves, a stub where `part` leaves one out; () to put them back."""
    kept = {name: getattr(fa, name) for name in HALVES}

    def constant(k, qs):
        return jnp.full((k.shape[0], qs.shape[0]), 0.5, jnp.float32)

    def eight_rows(v_t, p):
        rows = p[:8].astype(jnp.float32).sum(axis=0, keepdims=True)
        return jnp.broadcast_to(rows, (v_t.shape[0], p.shape[1]))

    def cast(s, m_prev, l_prev):
        return s, m_prev, l_prev, jnp.ones_like(m_prev)

    def again():
        # `_block_step` is jitted and keeps the trace it made of whatever stood there; jit's cache of traces is keyed
        # by the function it wraps, so it is a new function (a new partial) that forgets it.
        if hasattr(fa, "_block_step"):
            step = fa._block_step.__wrapped__
            fa._block_step = jax.jit(functools.partial(getattr(step, "func", step)))

    if part in ("values", "softmax", "empty"):
        fa._scores_t = constant
    if part in ("scores", "softmax", "empty"):
        fa._values_t = eight_rows
    if part in ("products", "scores", "values", "empty"):
        fa._softmax_step = cast
    again()
    return lambda: [setattr(fa, name, fn) for name, fn in kept.items()] and again()


def _kernel_events(trace_dir):
    """[(instruction name, duration_ns)] of the first chip's Mosaic calls, in
    time order. An `XLA Ops` event is named by its instruction's text."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    events = [(ev.start_ns, ev.name.split(" = ", 1)[0], ev.duration_ns)
              for line in plane.lines if line.name == "XLA Ops"
              for ev in line.events if "tpu_custom_call" in ev.name]
    return [(name, dur) for _, name, dur in sorted(events)]


def _reference_kernels(jax, bh, seq, d, causal):
    """{name: attention(q, k, v)} of jax's own TPU kernels, (1, bh, seq, d) layout."""
    from jax.experimental.pallas.ops.tpu import flash_attention as jax_flash
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as splash_kernel, splash_attention_mask as splash_mask)

    scale = d ** -0.5
    one = splash_mask.CausalMask((seq, seq)) if causal else splash_mask.FullMask((seq, seq))
    splash = splash_kernel.make_splash_mha(
        splash_mask.MultiHeadMask([one] * bh), head_shards=1, q_seq_shards=1)
    return {
        "jax_flash_attention": lambda q, k, v: jax_flash.flash_attention(
            q, k, v, causal=causal, sm_scale=scale),
        "jax_splash_attention": lambda q, k, v: splash(
            (q[0] * scale).astype(q.dtype), k[0], v[0])[None],
    }


def _time_references(jax, args, shape, causal, tensors, device):
    """One line per reference kernel: Mosaic device time a call, from a trace
    of `--rounds` forward calls and one of as many forward + backward calls."""
    bh, seq, d = shape
    q, k, v, do = tensors
    lines = []
    for name, attn in _reference_kernels(jax, bh, seq, d, causal).items():
        line = {"shape": [bh, seq, d], "dtype": "bfloat16", "causal": causal, "reference": name}
        try:
            fwd = jax.jit(attn)
            both = jax.jit(lambda q, k, v, do, attn=attn: jax.vjp(attn, q, k, v)[1](do))
            jax.block_until_ready((fwd(q, k, v), both(q, k, v, do)))
            per_call = []
            for fn, xs in ((fwd, (q, k, v)), (both, (q, k, v, do))):
                with tempfile.TemporaryDirectory() as trace_dir:
                    options = jax.profiler.ProfileOptions()
                    options.python_tracer_level = 0
                    jax.profiler.start_trace(trace_dir, profiler_options=options)
                    for _ in range(args.rounds):
                        jax.block_until_ready(fn(*xs))
                    jax.profiler.stop_trace()
                    events = _kernel_events(trace_dir)
                per_call.append((sum(dur for _, dur in events) / args.rounds / 1e3,
                                 len(events) // args.rounds))
            (fwd_us, fwd_calls), (both_us, both_calls) = per_call
            line.update(fwd_us=round(fwd_us, 2), bwd_us=round(both_us - fwd_us, 2),
                        mosaic_calls_fwd=fwd_calls, mosaic_calls_fwd_bwd=both_calls,
                        rounds=args.rounds, device=device)
        except Exception as e:  # an installation without it, a shape it refuses: say so
            line["error"] = f"{type(e).__name__}: {e}"[:300]
        lines.append(line)
    return lines


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=DEFAULT_SHAPES, help="BHxSEQxD,... (bf16)")
    ap.add_argument("--tiles", default=DEFAULT_TILES, help="N or QxK, comma separated")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--calls", type=int, default=40)
    ap.add_argument("--non-causal", action="store_true")
    ap.add_argument("--kv-heads", type=int, default=0, help="key/value heads under the shape's BH query heads (0: as many)")
    ap.add_argument("--keep-topk", type=int, default=0, help="a selection of this many keys a query (0: none)")
    ap.add_argument("--block-diffusion", type=int, default=0,
                    help="blocks of this many tokens: SEQ is two copies of SEQ / 2 under the block-diffusion mask (0: none)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--part", default="whole", help=",".join(PARTS) + ", comma separated")
    ap.add_argument("--fwd", default="plan",
                    help="HEADSxQ,...: the query heads a program of the pair-streamed forward takes and its Q tile, "
                         "stood where `_fwd_pairs_plan` is; `plan`: what the module chooses")
    ap.add_argument("--forward-only", action="store_true", help="time no backward pass")
    ap.add_argument("--check-seq", type=int, default=2048,
                    help="the row on which the pair-streamed kernels are checked against the XLA form first (0: no check)")
    ap.add_argument("--references", action="store_true",
                    help="also time jax's reference flash and splash attention")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    # `ray_tpu.ops.flash_attention` the attribute is the function; the module:
    fa = importlib.import_module("ray_tpu.ops.flash_attention")

    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if device["platform"] != "tpu":
        raise SystemExit(f"flash_bench.py measures TPU chips; jax came up on {device}")
    causal = not args.non_causal
    out_path = os.path.join(REPO, "chiprun_out", "flash_bench.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")

    def timed(fn, *xs):
        jax.block_until_ready(fn(*xs))  # compiled and warm
        t0 = time.perf_counter()
        for _ in range(args.calls):
            out = fn(*xs)
        jax.block_until_ready(out)
        return (time.perf_counter() - t0) / args.calls * 1e6

    halves = all(hasattr(fa, name) for name in HALVES)
    for shape in args.shapes.split(","):
        bh, seq, d = (int(n) for n in shape.split("x"))
        kv_heads = args.kv_heads or bh
        mask = fa.BlockDiffusion(seq // 2, args.block_diffusion) if args.block_diffusion else causal
        if args.check_seq and hasattr(fa, "pack_keep"):
            emit(_check(jax, jnp, fa, bh, kv_heads, min(args.check_seq, seq), d, args))
        keys = jax.random.split(jax.random.PRNGKey(args.seed), 4)
        normal = lambda kk, heads: jax.random.normal(kk, (1, heads, seq, d), jnp.float32).astype(jnp.bfloat16)
        q, k, v, do = normal(keys[0], bh), normal(keys[1], kv_heads), normal(keys[2], kv_heads), normal(keys[3], bh)
        extra = {"keep": _selection(jax, jnp, fa, seq, args.keep_topk, args.seed)} if args.keep_topk else {}
        what = {"kv_heads": kv_heads, "keep_topk": args.keep_topk, "seed": args.seed}
        cases = []
        the_plan = getattr(fa, "_fwd_pairs_plan", None)
        for tile_q, tile_k, forward, part in ((*_parse_tile(t), f, part) for t in args.tiles.split(",")
                                              for f in args.fwd.split(",") for part in args.part.split(",")):
            about = {"shape": [bh, seq, d], **what, "causal": str(mask) if args.block_diffusion else causal, "block_q": tile_q, "block_k": tile_k, "part": part}
            if part not in PARTS or (part != "whole" and not halves):
                print(json.dumps({**about, "error": "this checkout's forward has no such half to time alone"}), flush=True)
                continue
            if forward != "plan" and the_plan is not None:
                asked = tuple(int(n) for n in forward.split("x"))
                fa._fwd_pairs_plan = lambda *_, asked=asked: asked
                about["fwd_plan"] = list(asked)
            attn = lambda q, k, v, tq=tile_q, tk=tile_k: fa.flash_attention(
                q, k, v, causal=mask, backend="pallas", block_q=tq, block_k=tk, **extra)
            fwd = jax.jit(attn)
            # A half alone leaves nothing a backward pass could use.
            both = (jax.jit(lambda q, k, v, do, attn=attn: jax.vjp(attn, q, k, v)[1](do))
                    if part == "whole" and not args.forward_only else None)
            put_back = _stand_in(jax, fa, jnp, part) if halves else (lambda: None)
            t0 = time.perf_counter()
            try:
                jax.block_until_ready((fwd(q, k, v), both(q, k, v, do) if both else None))
            except Exception as e:  # a tile size the compiler refuses: say so, go on
                print(json.dumps({**about, "error": str(e)[:300]}), flush=True)
                continue
            finally:
                put_back()
                if the_plan is not None:
                    fa._fwd_pairs_plan = the_plan
            cases.append({"about": about, "fwd": fwd, "both": both,
                          "compile_s": time.perf_counter() - t0, "fwd_us": [], "both_us": []})
        for _ in range(args.rounds):
            for c in cases:
                c["fwd_us"].append(timed(c["fwd"], q, k, v))
                if c["both"]:
                    c["both_us"].append(timed(c["both"], q, k, v, do))
        # Device time: one trace, every round runs each case's forward, then
        # its forward + backward, so the kernel events come in a known order.
        with tempfile.TemporaryDirectory() as trace_dir:
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=options)
            for _ in range(args.rounds):
                for c in cases:
                    jax.block_until_ready(c["fwd"](q, k, v))
                    if c["both"]:
                        jax.block_until_ready(c["both"](q, k, v, do))
            jax.profiler.stop_trace()
            events = _kernel_events(trace_dir)
        # Per round and case: the forward call's kernel, then (a whole kernel) the vjp's two.
        expected = [name for _ in range(args.rounds) for c in cases
                    for name in (("flash_fwd", "flash_fwd", "flash_bwd") if c["both"] else ("flash_fwd",))]
        if len(events) != len(expected) or any(want not in name for want, (name, _) in zip(expected, events)):
            raise SystemExit(f"unexpected Mosaic calls in the trace: {[e[0] for e in events][:12]} "
                             f"({len(events)} events, {len(expected)} expected)")
        durs = iter(dur for _, dur in events)
        for c in cases:
            c["fwd_ns"], c["bwd_ns"] = [], []
        for _ in range(args.rounds):
            for c in cases:
                c["fwd_ns"].append(next(durs))
                if c["both"]:
                    c["fwd_ns"].append(next(durs))
                    c["bwd_ns"].append(next(durs))
        for c in cases:
            fwd_us = median(c["fwd_ns"]) / 1e3
            line = {
                **c["about"], "dtype": "bfloat16",
                "fwd_us": round(fwd_us, 2), "fwd_us_per_head": round(fwd_us / bh, 3),
                "fwd_host_us": round(median(c["fwd_us"]), 2),
                "compile_s": round(c["compile_s"], 2),
                "rounds": args.rounds, "calls": args.calls, "device": device,
            }
            if c["both"]:
                bwd_us = median(c["bwd_ns"]) / 1e3
                line.update(bwd_us=round(bwd_us, 2), bwd_us_per_head=round(bwd_us / bh, 3),
                            fwd_bwd_host_us=round(median(c["both_us"]), 2))
            tiles = (c["about"]["block_q"], c["about"]["block_k"])
            if hasattr(fa, "kernel_plan"):
                selected = {"kv_heads": kv_heads, "keep": bool(args.keep_topk)} if extra or kv_heads != bh else {}
                plan = fa.kernel_plan((1, bh, seq, d), mask, *tiles, **selected)
                line["plan"] = plan._asdict()
                if hasattr(fa, "_walk_scope") and (args.block_diffusion or fa._streams_pairs(seq, d, 2, kv_heads != bh, bool(extra))):
                    # What a program of the pair-streamed backward walks and scores: `tiles_<walked>of<all>`, and since
                    # PR 48 `keys_<scored>of<walked>`, blocks of 128 keys inside the pairs' live spans.
                    walk = fa._walk_scope(fa._pair_schedule(seq, plan, mask), seq, plan.tile_q, plan.tile_k)
                    line["walk"] = walk if isinstance(walk, str) else "/".join(walk)
                if args.block_diffusion:
                    n = seq // 2 // mask.block
                    line["kept_pairs"] = (n * n + n) * mask.block ** 2
                    for name, products in (("fwd", 2), ("bwd", 4)):
                        if name + "_us" in line:
                            floor_us = products * 2 * d * line["kept_pairs"] * bh / 197e12 * 1e6  # the v5e's bf16 peak
                            line[name + "_mxu_pct"] = round(100 * floor_us / line[name + "_us"], 2)
                if hasattr(fa, "_fwd_pairs_plan") and (args.block_diffusion or fa._streams_pairs(
                        seq, d, 2, kv_heads != bh, bool(extra))):
                    # What the pair-streamed forward takes for itself: (query heads a program, its Q tile).
                    line.setdefault("fwd_plan", list(fa._fwd_pairs_plan(bh // kv_heads, bh, d, 2, plan)))
            emit(line)
        if args.references:
            for line in _time_references(jax, args, (bh, seq, d), causal, (q, k, v, do), device):
                emit(line)


if __name__ == "__main__":
    main()

"""The flash attention kernels alone, on the chip: time per call by tile size.

    chiprun -- python3 tools/flash_bench.py
    chiprun -- python3 tools/flash_bench.py --shapes 32x2048x128 --tiles 512,256,128x256

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
One JSON line per shape and tile size, on stdout and in
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


def _parse_tile(text):
    q, _, k = text.partition("x")
    return int(q), int(k or q)


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

    for shape in args.shapes.split(","):
        bh, seq, d = (int(n) for n in shape.split("x"))
        keys = jax.random.split(jax.random.PRNGKey(0), 4)
        q, k, v, do = (jax.random.normal(kk, (1, bh, seq, d), jnp.float32).astype(jnp.bfloat16)
                       for kk in keys)
        cases = []
        for tile_q, tile_k in (_parse_tile(t) for t in args.tiles.split(",")):
            attn = lambda q, k, v, tq=tile_q, tk=tile_k: fa.flash_attention(
                q, k, v, causal=causal, backend="pallas", block_q=tq, block_k=tk)
            fwd = jax.jit(attn)
            both = jax.jit(lambda q, k, v, do, attn=attn: jax.vjp(attn, q, k, v)[1](do))
            t0 = time.perf_counter()
            try:
                jax.block_until_ready((fwd(q, k, v), both(q, k, v, do)))
            except Exception as e:  # a tile size the compiler refuses: say so, go on
                print(json.dumps({"shape": [bh, seq, d], "causal": causal, "block_q": tile_q,
                                  "block_k": tile_k, "error": str(e)[:300]}), flush=True)
                continue
            cases.append({"tile": (tile_q, tile_k), "fwd": fwd, "both": both,
                          "compile_s": time.perf_counter() - t0, "fwd_us": [], "both_us": []})
        for _ in range(args.rounds):
            for c in cases:
                c["fwd_us"].append(timed(c["fwd"], q, k, v))
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
                    jax.block_until_ready(c["both"](q, k, v, do))
            jax.profiler.stop_trace()
            events = _kernel_events(trace_dir)
        # Per round and case: the forward call's kernel, then the vjp's two.
        if len(events) != 3 * args.rounds * len(cases) or any(
                ("flash_bwd" in name) != (n % 3 == 2) for n, (name, _) in enumerate(events)):
            raise SystemExit(f"unexpected Mosaic calls in the trace: {[e[0] for e in events][:12]} "
                             f"({len(events)} events, {3 * args.rounds * len(cases)} expected)")
        durs = [dur for _, dur in events]
        for n, c in enumerate(cases):
            mine = [durs[3 * (r * len(cases) + n):][:3] for r in range(args.rounds)]
            fwd_us = median([d for f1, f2, _ in mine for d in (f1, f2)]) / 1e3
            bwd_us = median([b for _, _, b in mine]) / 1e3
            line = {
                "shape": [bh, seq, d], "dtype": "bfloat16", "causal": causal,
                "block_q": c["tile"][0], "block_k": c["tile"][1],
                "fwd_us": round(fwd_us, 2), "bwd_us": round(bwd_us, 2),
                "fwd_us_per_head": round(fwd_us / bh, 3),
                "bwd_us_per_head": round(bwd_us / bh, 3),
                "fwd_host_us": round(median(c["fwd_us"]), 2),
                "fwd_bwd_host_us": round(median(c["both_us"]), 2),
                "compile_s": round(c["compile_s"], 2),
                "rounds": args.rounds, "calls": args.calls, "device": device,
            }
            if hasattr(fa, "kernel_plan"):
                line["plan"] = fa.kernel_plan((1, bh, seq, d), causal, *c["tile"])._asdict()
            emit(line)
        if args.references:
            for line in _time_references(jax, args, (bh, seq, d), causal, (q, k, v, do), device):
                emit(line)


if __name__ == "__main__":
    main()

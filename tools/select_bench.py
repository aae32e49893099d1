"""The selection kernel alone, on the chip: time per call by form and by part.

    chiprun -- python3 tools/select_bench.py
    chiprun -- python3 tools/select_bench.py --form queries_down,module --part whole,product,search,lse,pack,empty

One call of `ops/lightning_indexer.py select` (the Mosaic call named `select`
and the XLA re-ordering of its operands; nothing else runs) at `--shape
BATCHxHEADSxSEQxD --topk K`, bf16 `q_i` / `k_i` and f32 weights made from
`--seed`, the Keye cell's by default: 16 indexer heads of 64 over a row of
16,384, 2,048 keys a query.

`--form`: `module` is the kernel the checkout's module holds; `queries_down` is
the form PR 42 brought and PR 49 took out of the module, kept here and nowhere
else so that the split PERF.md section 6 gives of it can be measured again: a
program 64 queries down the sublanes against 2,048 keys across the lanes.

`--part` times a stage alone, by standing a stub where the others are: `product`
the scores (the indexer heads' products, relu, weight and sum, the causal test,
the sortable form, the store), `search` the 32 rounds of compare-and-count,
`lse` the row statistic's passes, `pack` the packed words, `empty` none of them
(the grid's steps and the blocks' copies), `whole` the kernel. A stage that runs
alone reads whatever the scratch holds: its time does not depend on the values
(the rounds are 32 whatever they find), its results mean nothing.

For each form and part a JSON line, on stdout and in
`chiprun_out/select_bench.jsonl`: `kernel_us`, the device time of the Mosaic
call named `select` in a trace of `--rounds` calls, median (what
`kernels.select_ms` sums a step); `call_us`, the host's clock over `--calls`
back-to-back dispatches closed by `block_until_ready`, median of `--rounds`;
the programs of the grid; `compile_s`. Before the timings, once a form: `keep`
against `_xla_select`'s bit for bit and `lse`'s largest distance from it, on a
row of `--check-seq`; and the two forms' `keep` against each other at the
timed shape (`keep_sha`, a digest two checkouts can compare).

Runs on TPU chips only. No benchmark cell and no test runs this.
"""

from __future__ import annotations

import argparse
import functools
import glob
import hashlib
import importlib
import json
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("whole", "product", "search", "lse", "pack", "empty")
STAGES = ("product", "search", "lse", "pack")


def _kernel_us(trace_dir):
    """Device time, us, of each `select` Mosaic call of the first chip, in time order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    return [ev.duration_ns / 1e3 for line in plane.lines if line.name == "XLA Ops"
            for ev in sorted(line.events, key=lambda ev: ev.start_ns)
            if ev.name.split(" = ", 1)[0].lstrip("%").startswith("select")]


def _inputs(jax, jnp, shape, seed):
    batch, heads, seq, d = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    normal = lambda key, *dims: jax.random.normal(key, dims, jnp.float32)
    q_i = normal(keys[0], batch, heads, seq, d).astype(jnp.bfloat16)
    k_i = normal(keys[1], batch, seq, d).astype(jnp.bfloat16)
    w = normal(keys[2], batch, seq, heads) * (heads * d) ** -0.5
    return jax.block_until_ready((q_i, k_i, w))


# --------------------------------------------------------------------------- the form PR 42 brought
QUERIES_DOWN_ROWS = 64
QUERIES_DOWN_CHUNK = 2048


def _queries_down_kernel(q_ref, k_ref, w_ref, keep_ref, lse_ref, keys, *, topk, rows, chunk, seq, heads, stages):
    """`ops/lightning_indexer.py _select_kernel` as PR 42 to PR 48 had it, line for line, each stage under a
    test of `stages` (all four: that kernel)."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    from ray_tpu.ops.flash_attention import KEEP_BITS, KEEP_SPAN, LANES
    from ray_tpu.ops.lightning_indexer import _threshold, sortable, unsortable

    i = pl.program_id(1)
    row = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
    live = ((i + 1) * rows + chunk - 1) // chunk
    col0 = jax.lax.broadcasted_iota(jnp.int32, (1, chunk), 1)

    def fill(c, _):
        k_t = k_ref[0, c]
        acc = jnp.zeros((rows, chunk), jnp.float32)
        for j in range(heads):
            s = jax.lax.dot_general(q_ref[0, j], k_t, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
            acc = acc + w_ref[0, j] * jnp.maximum(s, 0.0)
        keys[c] = sortable(jnp.where(c * chunk + col0 <= row, acc, -jnp.inf))
        return 0

    if "product" in stages:
        jax.lax.fori_loop(0, live, fill, 0)

    def over_chunks(f, init):
        return jax.lax.fori_loop(0, live, lambda c, carry: f(carry, keys[c], c * chunk + col0 <= row), init)

    def count_at_least(t):
        return over_chunks(lambda n, ks, _: n + jnp.sum((ks >= t).astype(jnp.int32), axis=1, keepdims=True),
                           jnp.zeros((rows, 1), jnp.int32))

    tau = jnp.zeros((rows, 1), jnp.int32)
    if "search" in stages:
        tau = _threshold(count_at_least, topk, jnp.zeros((rows, 1), jnp.int32))
    lse_ref[0] = tau.astype(jnp.float32)
    if "lse" in stages:
        kept_scores = lambda ks, causal: jnp.where((ks >= tau) & causal, unsortable(ks), -jnp.inf)
        top = over_chunks(lambda m, ks, causal: jnp.maximum(m, jnp.max(kept_scores(ks, causal), axis=1, keepdims=True)),
                          jnp.full((rows, 1), -jnp.inf, jnp.float32))
        total = over_chunks(
            lambda z, ks, causal: z + jnp.sum(jnp.exp(kept_scores(ks, causal) - top), axis=1, keepdims=True),
            jnp.zeros((rows, 1), jnp.float32))
        lse_ref[0] = top + jnp.log(total)

    if "pack" not in stages:
        keep_ref[0] = jnp.zeros(keep_ref.shape[1:], jnp.int32)
        return
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, LANES), 1)
    for span in range(keep_ref.shape[2] // LANES):
        word = jnp.zeros((rows, LANES), jnp.int32)
        for b in range(KEEP_BITS):
            first = span * KEEP_SPAN + b * LANES
            if first >= seq:
                break
            c, at = divmod(first, chunk)
            bit = (keys[c, :, at:at + LANES] >= tau) & (first + lane <= row)
            word = word | (bit.astype(jnp.int32) << b)
        keep_ref[0, :, span * LANES:(span + 1) * LANES] = word


def _queries_down_select(q_i, k_i, w, topk, stages, interpret=False):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ray_tpu.ops.flash_attention import KEEP_SPAN, LANES

    batch, heads, seq, d = q_i.shape
    rows, chunk = int(np.gcd(seq, QUERIES_DOWN_ROWS)), int(np.gcd(seq, QUERIES_DOWN_CHUNK))
    spans = -(-seq // KEEP_SPAN)
    k_t = k_i.reshape(batch, seq // chunk, chunk, d).transpose(0, 1, 3, 2)
    weights = w.astype(jnp.float32).transpose(0, 2, 1)[..., None]
    with jax.named_scope("select"):
        keep, lse = pl.pallas_call(
            functools.partial(_queries_down_kernel, topk=topk, rows=rows, chunk=chunk, seq=seq, heads=heads,
                              stages=stages),
            grid=(batch, seq // rows),
            in_specs=[pl.BlockSpec((1, heads, rows, d), lambda b, i: (b, 0, i, 0)),
                      pl.BlockSpec((1, seq // chunk, d, chunk), lambda b, i: (b, 0, 0, 0)),
                      pl.BlockSpec((1, heads, rows, 1), lambda b, i: (b, 0, i, 0))],
            out_specs=[pl.BlockSpec((1, rows, spans * LANES), lambda b, i: (b, i, 0)),
                       pl.BlockSpec((1, rows, 1), lambda b, i: (b, i, 0))],
            out_shape=[jax.ShapeDtypeStruct((batch, seq, spans * LANES), jnp.int32),
                       jax.ShapeDtypeStruct((batch, seq, 1), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((seq // chunk, rows, chunk), jnp.int32)],
            interpret=interpret,
            name="select",
            compiler_params=None if interpret else pltpu.CompilerParams(dimension_semantics=("parallel", "parallel")),
        )(q_i, k_t, weights)
    return keep, lse[..., 0]


# --------------------------------------------------------------------------- the module's form, a stage alone
def _module_stages(li, jnp, stages):
    """Stubs where the module's kernel calls the stages `stages` leaves out; () puts them back. A checkout
    whose kernel is not made of `_select_scores`, `_select_search`, `_select_lse` and `_select_pack` (before
    PR 49) has no stage to time alone: None."""
    names = {"product": "_select_scores", "search": "_select_search", "lse": "_select_lse", "pack": "_select_pack"}
    if not all(hasattr(li, name) for name in names.values()):
        return None
    kept = {name: getattr(li, name) for name in names.values()}

    def no_scores(q_ref, k_hbm, w_ref, keys, k_buf, sem, row, live, **_):
        return jnp.zeros((1, row.shape[1]), jnp.int32)

    def no_search(keys, live, topk, queries):
        return jnp.zeros((1, queries), jnp.int32)

    def no_lse(keys, live, tau, top):
        return (tau + top).astype(jnp.float32)

    def no_pack(keep_ref, keys, tau, row, **_):
        keep_ref[0] = jnp.zeros(keep_ref.shape[1:], jnp.int32)

    stubs = {"product": no_scores, "search": no_search, "lse": no_lse, "pack": no_pack}
    for stage, name in names.items():
        if stage not in stages:
            setattr(li, name, stubs[stage])
    return lambda: [setattr(li, name, fn) for name, fn in kept.items()]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shape", default="1x16x16384x64", help="BATCHxHEADSxSEQxD of the indexer's queries (bf16)")
    ap.add_argument("--topk", type=int, default=2048)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--form", default="module", help="module, queries_down; comma separated")
    ap.add_argument("--part", default="whole", help=",".join(PARTS) + ", comma separated")
    ap.add_argument("--set", default="", help="NAME=INT,...: module constants to stand in (SELECT_CHUNK=1024)")
    ap.add_argument("--check-seq", type=int, default=2048, help="the row checked against the XLA form (0: no check)")
    ap.add_argument("--rounds", type=int, default=5)
    ap.add_argument("--calls", type=int, default=4)
    ap.add_argument("--rehearse", action="store_true",
                    help="off the chip, in interpret mode: the check and one call of every case, no timing")
    args = ap.parse_args(argv)

    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp
    import numpy as np

    li = importlib.import_module("ray_tpu.ops.lightning_indexer")
    dev = jax.devices()[0]
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": jax.device_count()}
    if device["platform"] != "tpu" and not args.rehearse:
        raise SystemExit(f"select_bench.py measures TPU chips; jax came up on {device}")
    out_path = os.path.join(REPO, "chiprun_out", "select_bench.jsonl")
    os.makedirs(os.path.dirname(out_path), exist_ok=True)

    def emit(line):
        text = json.dumps(line)
        print(text, flush=True)
        with open(out_path, "a") as f:
            f.write(text + "\n")

    stood = {}
    for item in filter(None, args.set.split(",")):
        name, _, value = item.partition("=")
        stood[name] = int(value)
        setattr(li, name, int(value))
    shape = tuple(int(n) for n in args.shape.split("x"))
    forms = args.form.split(",")

    def build(form, part):
        """(the jitted call, a function that puts the module back), or an error's text."""
        stages = frozenset(STAGES if part == "whole" else () if part == "empty" else (part,))
        if form == "queries_down":
            return jax.jit(lambda q_i, k_i, w: _queries_down_select(q_i, k_i, w, args.topk, stages, args.rehearse)), (lambda: None)
        put_back = (lambda: None) if part == "whole" else _module_stages(li, jnp, stages)
        if put_back is None:
            return "this checkout's kernel has no such stage to time alone", None
        return jax.jit(lambda q_i, k_i, w: li.select(q_i, k_i, w, args.topk, backend="pallas", interpret=args.rehearse)), put_back

    if args.check_seq:
        short = _inputs(jax, jnp, shape[:2] + (args.check_seq, shape[3]), args.seed)
        topk, args.topk = args.topk, min(args.topk, args.check_seq // 4)
        want_keep, want_lse = jax.jit(lambda q_i, k_i, w: li.select(q_i, k_i, w, args.topk, backend="xla"))(*short)
        for form in forms:
            fn, _ = build(form, "whole")
            try:
                keep, lse = fn(*short)
            except Exception as e:  # a form the compiler refuses: say so, go on
                emit({"check_seq": args.check_seq, "form": form, "error": str(e)[:300]})
                continue
            emit({"check_seq": args.check_seq, "form": form, "topk": args.topk,
                  "keep_equal_to_xla": bool((keep == want_keep).all()),
                  "lse_max_abs_err": float(jnp.abs(lse - want_lse).max())})
        args.topk = topk

    xs = _inputs(jax, jnp, shape, args.seed)
    batch, _, seq, _ = shape
    cases = []
    for form in forms:
        for part in args.part.split(","):
            if part not in PARTS:
                emit({"form": form, "part": part, "error": "no such part"})
                continue
            fn, put_back = build(form, part)
            if put_back is None:
                emit({"form": form, "part": part, "error": fn})
                continue
            t0 = time.perf_counter()
            try:
                keep, lse = jax.block_until_ready(fn(*xs))
            except Exception as e:  # a form the compiler refuses: say so, go on
                emit({"form": form, "part": part, "error": str(e)[:300]})
                continue
            finally:
                put_back()
            case = {"form": form, "part": part, "fn": fn, "compile_s": time.perf_counter() - t0, "call_us": []}
            if form == "queries_down":
                case["plan"] = {"rows": int(np.gcd(seq, QUERIES_DOWN_ROWS)), "chunk": int(np.gcd(seq, QUERIES_DOWN_CHUNK))}
            elif hasattr(li, "_select_plan"):
                case["plan"] = dict(li._select_plan(seq, *shape[1::2], 2)._asdict())
            else:
                case["plan"] = {"rows": int(np.gcd(seq, li.SELECT_ROWS)), "chunk": int(np.gcd(seq, li.SELECT_CHUNK))}
            case["programs"] = batch * seq // case["plan"].get("queries", case["plan"].get("rows"))
            if part == "whole":
                case["keep_sha"] = hashlib.sha256(np.asarray(keep).tobytes()).hexdigest()[:16]
                case["lse_sum"] = float(jnp.sum(lse))
                case["keys_per_query_max"] = int(jnp.max(jnp.sum(jax.lax.population_count(keep), axis=-1)))
            cases.append(case)
    if args.rehearse:
        for c in cases:
            c.pop("fn")
            emit({"rehearsal": True, **c})
        return
    for _ in range(args.rounds):
        for c in cases:
            t0 = time.perf_counter()
            for _ in range(args.calls):
                out = c["fn"](*xs)
            jax.block_until_ready(out)
            c["call_us"].append((time.perf_counter() - t0) / args.calls * 1e6)
    with tempfile.TemporaryDirectory() as trace_dir:
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=options)
        for _ in range(args.rounds):
            for c in cases:
                jax.block_until_ready(c["fn"](*xs))
        jax.profiler.stop_trace()
        kernel_us = _kernel_us(trace_dir)
    if len(kernel_us) != args.rounds * len(cases):
        raise SystemExit(f"{len(kernel_us)} `select` calls in the trace, {args.rounds * len(cases)} expected")
    for n, c in enumerate(cases):
        c.pop("fn")
        emit({"shape": list(shape), "topk": args.topk, "seed": args.seed, "dtype": "bfloat16", **stood, **c,
              "call_us": round(median(c["call_us"]), 1), "kernel_us": round(median(kernel_us[n::len(cases)]), 1),
              "compile_s": round(c["compile_s"], 2), "rounds": args.rounds, "calls": args.calls, "device": device})


if __name__ == "__main__":
    main()

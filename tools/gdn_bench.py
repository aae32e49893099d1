"""The gated delta-rule kernels alone, on the chip: time a call by chunk, by heads a program and by part, beside their floors.

    chiprun -- python3 tools/gdn_bench.py
    chiprun -- python3 tools/gdn_bench.py --chunk 64,128 --part whole,no_inverse,empty --check-seq 512
    chiprun -- python3 tools/gdn_bench.py --chunk 128 --heads 1,2,3,5,plan --part whole,six_pass,no_inverse

One call of `ops/gated_delta_rule.py gated_delta_rule` and of its gradient (the Mosaic calls named
`gdn_fwd` and `gdn_bwd` and the XLA running sums round them; nothing else runs) at `--shape
BATCHxHEADSxSEQxDKxDV`, bf16 q, k (L2-normalised), v and f32 gates made from `--seed`, one linear layer
of the Olmo-Hybrid cell by default: a row of 4,096, 30 heads, key width 96, value width 192.

How a bf16 operand's products are issued (PR 52): a product of a bf16 array (q, k, do as the layer hands them)
with an f32 one (the state, `N`, a cotangent) is three MXU passes, the bf16 array against the three bf16 parts
of the f32 one (`_mm`, `_bf16_parts`): the six-pass product of its cast to f32 less the three passes that would
multiply the cast's zero middle and low parts. A product of two f32 arrays is six passes, one of two bf16
arrays one. `passes` in a line is what a chunk of a head issues, in units of 128^3, forward and backward, counted in
the chunk's jaxpr as the module stands (`whole`: `mxu_passes`, 101.6 + 148.9 at the cell's widths).

`--heads` times the kernels at so many heads a program (`heads_per_program` stood in: a divisor of the heads;
a count that does not compile, for VMEM, gives a line with `error`), `plan` at what the module's rule picks.

`--part` times the kernels with a stub where a stage is: `no_inverse` stands `I - A` where `(I + A)^-1`
is made (the doubling's `2 (log2 C - 1)` products of C^3 gone, everything else as it is), `empty` stands zeros
where a chunk's mathematics is (the grid's steps, the blocks' copies and the state's stores), `six_pass` casts
a bf16 operand up where it meets an f32 array, so that product is six passes again (PR 51's form: read beside
`whole`, the three-pass products apart from the heads a program), `whole` the kernels. A stubbed kernel's
results mean nothing (`six_pass`'s do); its time does not depend on the values.

For each chunk, heads a program and part a JSON line, on stdout and in `chiprun_out/gdn_bench.jsonl`: `fwd_us` and
`bwd_us`, the device time of the Mosaic calls in a trace of `--rounds` calls of the gradient, median
(what `kernels.gdn_fwd_ms` and `kernels.gdn_bwd_ms` sum a step); `call_us`, the host's clock over one
call of the gradient closed by `block_until_ready`, median of `--rounds`; `compile_s`; and for `whole`
the floors of `benchmark/models/olmo_hybrid.py` for this one layer (`floor_flops_us`, `floor_bytes_us`)
and the share `max(floors) / (fwd_us + bwd_us)`. Before the timings, once a chunk: o and the five
gradients against the token-by-token f32 recurrence on a row of `--check-seq`, the largest distance
over the reference's largest value (`check`), for bf16 and for f32 operands.

`--rehearse` walks it off the chip in interpret mode with no timing (`--shape 1x2x64x24x40 --chunk 16
--check-seq 48`). Runs on TPU chips only otherwise. No benchmark cell and no test but the rehearsal's runs this.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PARTS = ("whole", "no_inverse", "empty", "six_pass")


def _kernel_us(trace_dir, name):
    """Device time, us, of each Mosaic call named `name` on the first chip, in time order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    return [ev.duration_ns / 1e3 for line in plane.lines if line.name == "XLA Ops"
            for ev in sorted(line.events, key=lambda ev: ev.start_ns)
            if ev.name.split(" = ", 1)[0].lstrip("%").startswith(name)]


def inputs(jax, jnp, shape, seed, dtype):
    """q, k, v, g, beta as a linear layer hands them: q and k L2-normalised over a head, q scaled by
    d_k^-1/2; g = -exp(A_log) softplus(.), A in U(0, 16); beta = 2 sigmoid(.), on both sides of 1."""
    batch, heads, seq, dk, dv = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (batch, heads, seq, dk))) * dk ** -0.5
    k = unit(jax.random.normal(keys[1], (batch, heads, seq, dk)))
    v = jax.random.normal(keys[2], (batch, heads, seq, dv))
    a = jax.random.uniform(keys[3], (1, heads, 1), minval=1e-3, maxval=16.0)
    g = -a * jax.nn.softplus(jax.random.normal(keys[4], (batch, heads, seq)) - 3.0)
    beta = 2.0 * jax.nn.sigmoid(jax.random.normal(keys[5], (batch, heads, seq)))
    return jax.block_until_ready((q.astype(dtype), k.astype(dtype), v.astype(dtype), g, beta))


def recurrence(jax, jnp, q, k, v, g, beta):
    """The recurrence token by token in f32, the benchmark's own (`delta_rule_recurrence`): the yardstick."""
    from benchmark.models.olmo_hybrid import delta_rule_recurrence

    f32 = jnp.float32
    with jax.default_matmul_precision("highest"):
        return jax.vmap(jax.vmap(delta_rule_recurrence))(q.astype(f32), k.astype(f32), v.astype(f32), g, beta)


def check(jax, jnp, gdn, shape, seed, chunk, interpret):
    """{dtype: {o, dq, dk, dv, dg, dbeta: largest distance from the recurrence's over its largest value}}."""
    out = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        args = inputs(jax, jnp, shape, seed, dtype)
        w = jax.random.normal(jax.random.PRNGKey(seed + 1), (*shape[:3], shape[4]))
        mine = lambda *a: gdn.gated_delta_rule(*a, backend="pallas", chunk=chunk, interpret=interpret)  # noqa: E731
        both = [jax.jit(lambda *a, f=f: (f(*a), jax.grad(lambda *a: (f(*a).astype(jnp.float32) * w).sum(),
                                                         argnums=(0, 1, 2, 3, 4))(*a)))(*args)
                for f in (mine, lambda *a: recurrence(jax, jnp, *a))]
        (o, grads), (o_ref, grads_ref) = both
        far = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())  # noqa: E731
        out[jnp.dtype(dtype).name] = {"o": far(o, o_ref), **{
            name: far(a, b.astype(jnp.float32)) for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), grads, grads_ref)}}
    return out


def passes_issued(jax, jnp, gdn, chunk, dk, dv):
    """[forward, backward]: the MXU passes, in units of 128^3 multiply-adds, of the products in the jaxpr of one
    chunk of one head with bf16 operands as the module stands (a stub in place counts): six for a product at
    full f32 precision, one for any other (a three-pass product is three of those)."""
    sd = lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(shape, dtype)  # noqa: E731
    wide = lambda d: sd((chunk, d), jnp.bfloat16)  # noqa: E731
    chunk_args = (wide(dk), wide(dk), wide(dv), sd((1, chunk)), sd((1, chunk)), sd((dk, dv)))
    out = []
    for f, args in ((gdn._chunk_fwd, chunk_args), (gdn._chunk_bwd, (*chunk_args, wide(dv), sd((dk, dv))))):
        passes = 0.0
        for eqn in jax.make_jaxpr(f)(*args).eqns:
            if eqn.primitive.name == "dot_general":
                ((ca,), (cb,)), _ = eqn.params["dimension_numbers"]
                a, b = (v.aval.shape for v in eqn.invars)
                full = eqn.params["precision"] is not None  # `_mm` asks for HIGHEST or for nothing
                passes += (6 if full else 1) * a[1 - ca] * a[ca] * b[1 - cb] / 128 ** 3
        out.append(passes)
    return out


def stub(gdn, jnp, part, heads):
    """Stand the part's stub and the heads a program in the module, and in the walk's (`ops/chunked_scan.py`, where a
    checkout has it: since PR 63 the plan, the doubling and `_mm` live there and the rule's module reads them from
    it); returns what puts them back."""
    homes = [gdn] + [m for m in (sys.modules.get("ray_tpu.ops.chunked_scan"),) if m is not None]
    names = ("_unit_lower_inverses", "_chunk_fwd", "_chunk_bwd", "_mm", "heads_per_program")
    kept = {name: getattr(gdn, name) for name in names}
    stubs = {}
    if heads != "plan":
        stubs["heads_per_program"] = lambda *_: int(heads)
    if part == "six_pass":
        f32 = lambda x, other: x.astype(jnp.float32) if other.dtype == jnp.float32 else x  # noqa: E731
        stubs["_mm"] = lambda a, b, dims=gdn.NN: kept["_mm"](f32(a, b), f32(b, a), dims)
    elif part == "no_inverse":
        eye = lambda a: (gdn._iotas(a.shape[0])[0] == gdn._iotas(a.shape[0])[1]).astype(a.dtype)  # noqa: E731
        stubs["_unit_lower_inverses"] = lambda mats: [eye(a) - a for a in mats]
    elif part == "empty":
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        stubs["_chunk_fwd"] = lambda q, k, v, gam, beta, s, first=None: (f32(v), s)
        stubs["_chunk_bwd"] = lambda q, k, v, gam, beta, s, do, ds, first=None: (
            f32(q), f32(k), f32(do), gam, beta, ds + s)
    put = lambda fs: [setattr(home, name, f) for home in homes for name, f in fs.items() if hasattr(home, name)]  # noqa: E731
    put(stubs)
    return lambda: put(kept)


def main():
    p = argparse.ArgumentParser(prog="tools/gdn_bench.py")
    p.add_argument("--shape", default="1x30x4096x96x192")
    p.add_argument("--chunk", default="64")
    p.add_argument("--part", default="whole")
    p.add_argument("--heads", default="plan")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--check-seq", type=int, default=512)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from benchmark.models import olmo_hybrid as arithmetic
    from ray_tpu.ops import gated_delta_rule as gdn

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit(f"tools/gdn_bench.py: not on a TPU: {jax.devices()}")
    shape = tuple(int(n) for n in args.shape.split("x"))
    batch, heads, seq, dk, dv = shape
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def say(**line):
        line = {"device": jax.devices()[0].device_kind, "shape": args.shape, **line}
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "gdn_bench.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")

    operands = inputs(jax, jnp, shape, args.seed, jnp.bfloat16)
    for chunk in (int(c) for c in args.chunk.split(",")):
        say(chunk=chunk, check=check(jax, jnp, gdn, (batch, min(heads, 4), args.check_seq, dk, dv),
                                      args.seed, chunk, args.rehearse))
        for held, part in ((h, p) for h in args.heads.split(",") for p in args.part.split(",")):
            assert part in PARTS, part
            restore = stub(gdn, jnp, part, held)
            jax.clear_caches()  # the kernels keep the chunk's jaxprs by function (`_once`): a stub under one is not seen
            per_program = gdn.heads_per_program(batch * heads, seq + -seq % chunk, chunk, dk, dv, 2)
            at = {"chunk": chunk, "heads_per_program": per_program, "part": part}
            try:
                loss = lambda *a: gdn.gated_delta_rule(  # noqa: E731
                    *a, backend="pallas", chunk=chunk, interpret=args.rehearse).astype(jnp.float32).sum()
                t = time.perf_counter()
                call = jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3, 4))).lower(*operands).compile()
                compile_s = time.perf_counter() - t
                passes = passes_issued(jax, jnp, gdn, chunk, dk, dv)
            except Exception as e:  # so many heads do not fit the kernel's VMEM: the line says so
                say(**at, error=f"{type(e).__name__}: {str(e)[-300:]}")
                continue
            finally:
                restore()
            jax.block_until_ready(call(*operands))
            if args.rehearse:
                say(**at, passes=passes, rehearsal=True, compile_s=round(compile_s, 2))
                continue
            clock = []
            for _ in range(args.rounds):
                t = time.perf_counter()
                jax.block_until_ready(call(*operands))
                clock.append((time.perf_counter() - t) * 1e6)
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                for _ in range(args.rounds):
                    jax.block_until_ready(call(*operands))
                jax.profiler.stop_trace()
                fwd, bwd = (median(_kernel_us(trace_dir, name)) for name in ("gdn_fwd", "gdn_bwd"))
            line = {**at, "programs": batch * heads // per_program * (-(-seq // chunk)), "passes": passes,
                    "fwd_us": fwd, "bwd_us": bwd, "call_us": median(clock), "compile_s": round(compile_s, 2)}
            if part == "whole":
                one_layer = {"linear_key_head_dim": dk, "linear_value_head_dim": dv, "linear_num_value_heads": heads,
                             "layer_types": ["linear_attention"], "dtype": "bfloat16"}
                flops = arithmetic.gdn_flops_per_step(one_layer, batch, seq, chunk=chunk)
                nbytes = arithmetic.gdn_bytes_per_step(one_layer, batch, seq)
                line["floor_flops_us"], line["floor_bytes_us"] = flops / 197e12 * 1e6, nbytes / 819e9 * 1e6
                line["roofline_pct"] = 100 * max(line["floor_flops_us"], line["floor_bytes_us"]) / (fwd + bwd)
            say(**line)


if __name__ == "__main__":
    main()

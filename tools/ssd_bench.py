"""The state-space duality kernels alone, on the chip: time a call by chunk, by heads a program and by part, beside their floors.

    chiprun -- python3 tools/ssd_bench.py
    chiprun -- python3 tools/ssd_bench.py --chunk 128,256 --heads 1,2,4,8,plan --part whole,empty

One call of `ops/ssd.py ssd` and of its gradient (the Mosaic calls named `ssd_fwd` and `ssd_bwd`, and the XLA
operations round them: the decay and v = dt x under `ssd_gates`, the running sum, `D x`) at `--shape
BATCHxHEADSxGROUPSxSEQxNxP`, bf16 x, B, C and f32 dt made from `--seed`, one mamba layer of the granite-4.0-h-micro cell
by default: a row of 4,096, 64 heads of 64 on one B and C of 128. `A` is 1 .. 16 over the heads, dt exp U(log 1e-3,
log 1e-1), as the cell's seeded weights give them.

`--heads` times the kernels at so many heads a program (`Rule.max_heads` stood in and the walk's VMEM estimate set
aside; a count that does not compile, for VMEM, gives a line with `error`), `plan` at what the module picks. `--part empty` stands zeros where a chunk's
mathematics is (the grid's steps, the blocks' copies and the state's stores), `whole` the kernels.

For each chunk, heads a program and part a JSON line, on stdout and in `chiprun_out/ssd_bench.jsonl`: `fwd_us` and
`bwd_us`, the device time of the Mosaic calls in a trace of `--rounds` calls of the gradient, median (what
`kernels.ssd_fwd_ms` and `kernels.ssd_bwd_ms` sum a step); `call_us`, the host's clock over one call of the gradient
closed by `block_until_ready`, median; `compile_s`; and for `whole` the floors of `benchmark/models/granite_hybrid.py`
for this one layer (`floor_flops_us`, `floor_bytes_us`) and the share `max(floors) / (fwd_us + bwd_us)`. Before the
timings, once a chunk: y and the six gradients against the position-by-position f32 recurrence on a row of
`--check-seq`, the largest distance over the reference's largest value (`check`), for bf16 and for f32 operands.

`--rehearse` walks it off the chip in interpret mode with no timing (`--shape 1x4x1x64x16x8 --chunk 16 --check-seq
48`). Runs on TPU chips only otherwise. No benchmark cell and no test but the rehearsal's runs this.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [REPO, os.path.dirname(os.path.abspath(__file__))]
NAMES = ("x", "b", "c", "dt", "a_log", "d")


def inputs(jax, jnp, shape, seed, dtype):
    batch, heads, groups, seq, n, p = shape
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.nn.silu(jax.random.normal(keys[0], (batch, heads, seq, p)))
    b, c = (jax.nn.silu(jax.random.normal(k, (batch, groups, seq, n))) for k in keys[1:3])
    dt = jnp.exp(jax.random.uniform(keys[3], (batch, heads, seq), minval=math.log(1e-3), maxval=math.log(1e-1)))
    a_log, d = jnp.log(jnp.linspace(1.0, 16.0, heads)), 1.0 + 0.1 * jax.random.normal(keys[4], (heads,))
    return jax.block_until_ready((x.astype(dtype), b.astype(dtype), c.astype(dtype), dt, a_log, d))


def recurrence(jax, jnp, x, b, c, dt, a_log, d):
    """The recurrence position by position in f32, the benchmark's own (`ssd_recurrence`): the yardstick."""
    from benchmark.models.granite_hybrid import ssd_recurrence

    f32 = jnp.float32
    x, b, c = x.astype(f32), *(jnp.repeat(z.astype(f32), x.shape[1] // b.shape[1], axis=1) for z in (b, c))
    a_head = jax.vmap(lambda x, b, c, dt, a, d: ssd_recurrence(x, b, c, dt, a, d)[0])
    with jax.default_matmul_precision("highest"):
        return jax.vmap(a_head, in_axes=(0, 0, 0, 0, None, None))(x, b, c, dt, -jnp.exp(a_log), d)


def check(jax, jnp, ssd, shape, seed, chunk, interpret):
    """{dtype: {y, dx, db, dc, ddt, da_log, dd: largest distance from the recurrence's over its largest value}}."""
    out = {}
    for dtype in (jnp.bfloat16, jnp.float32):
        args = inputs(jax, jnp, shape, seed, dtype)
        w = jax.random.normal(jax.random.PRNGKey(seed + 1), args[0].shape)
        mine = lambda *a: ssd.ssd(*a, backend="pallas", chunk=chunk, interpret=interpret)  # noqa: E731
        (y, grads), (y_ref, grads_ref) = [
            jax.jit(lambda *a, f=f: (f(*a), jax.grad(lambda *a: (f(*a) * w).sum(), argnums=tuple(range(6)))(*a)))(*args)
            for f in (mine, lambda *a: recurrence(jax, jnp, *a))]
        far = lambda a, b: float(jnp.abs(a.astype(jnp.float32) - b).max() / jnp.abs(b).max())  # noqa: E731
        out[jnp.dtype(dtype).name] = {"y": far(y, y_ref), **{
            "d" + name: far(a, b.astype(jnp.float32)) for name, a, b in zip(NAMES, grads, grads_ref)}}
    return out


def stub(ssd, walk, jnp, part, heads):
    """Stand the part's stub and the heads a program in the module (so many whatever the walk's VMEM estimate says:
    the compiler has the last word); returns what puts them back."""
    kept = {name: getattr(ssd, name) for name in ("_chunk_fwd", "_chunk_bwd", "RULE")}
    budget = walk.VMEM_BUDGET
    if heads != "plan":
        ssd.RULE, walk.VMEM_BUDGET = ssd.RULE._replace(max_heads=int(heads)), 1 << 30
    if part == "empty":
        f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
        ssd._chunk_fwd = lambda q, k, v, gam, beta, s, first=None: (f32(v), s)
        ssd._chunk_bwd = lambda q, k, v, gam, beta, s, do, ds, first=None: (f32(q), f32(k), f32(do), gam, None, ds + s)
    return lambda: [setattr(ssd, name, f) for name, f in kept.items()] + [setattr(walk, "VMEM_BUDGET", budget)]


def main():
    p = argparse.ArgumentParser(prog="tools/ssd_bench.py")
    p.add_argument("--shape", default="1x64x1x4096x128x64")
    p.add_argument("--chunk", default="128,256")
    p.add_argument("--part", default="whole")
    p.add_argument("--heads", default="plan")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--check-seq", type=int, default=512)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import jax
    import jax.numpy as jnp

    from benchmark.models import granite_hybrid as arithmetic
    from gdn_bench import _kernel_us
    from ray_tpu.ops import chunked_scan as walk
    from ray_tpu.ops import ssd

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit(f"tools/ssd_bench.py: not on a TPU: {jax.devices()}")
    shape = tuple(int(n) for n in args.shape.split("x"))
    batch, heads, groups, seq, n, width = shape
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def say(**line):
        line = {"device": jax.devices()[0].device_kind, "shape": args.shape, **line}
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "ssd_bench.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")

    operands = inputs(jax, jnp, shape, args.seed, jnp.bfloat16)
    for chunk in (int(c) for c in args.chunk.split(",")):
        say(chunk=chunk, check=check(jax, jnp, ssd, (batch, min(heads, 8), groups, args.check_seq, n, width),
                                      args.seed, chunk, args.rehearse))
        for held, part in ((h, p) for h in args.heads.split(",") for p in args.part.split(",")):
            restore = stub(ssd, walk, jnp, part, held)
            jax.clear_caches()  # the kernels keep the chunk's jaxprs by function (`_once`): a stub under one is not seen
            k, v = (jax.ShapeDtypeStruct((batch * h, seq + -seq % chunk, w), jnp.bfloat16)
                    for h, w in ((groups, n), (heads, width)))
            per_program, *scopes = walk.plan(ssd.RULE, k, v, chunk)
            at = {"chunk": chunk, "heads_per_program": per_program, "scope": "/".join(scopes), "part": part}
            try:
                loss = lambda *a: ssd.ssd(*a, backend="pallas", chunk=chunk, interpret=args.rehearse).sum()  # noqa: E731
                t = time.perf_counter()
                call = jax.jit(jax.grad(loss, argnums=tuple(range(6)))).lower(*operands).compile()
                compile_s = time.perf_counter() - t
            except Exception as e:  # so many heads do not fit the kernel's VMEM: the line says so
                say(**at, error=f"{type(e).__name__}: {str(e)[-300:]}")
                continue
            finally:
                restore()
            jax.block_until_ready(call(*operands))
            if args.rehearse:
                say(**at, rehearsal=True, compile_s=round(compile_s, 2))
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
                fwd, bwd = (median(_kernel_us(trace_dir, name)) for name in ("ssd_fwd", "ssd_bwd"))
            line = {**at, "passes": [ssd.mxu_passes(chunk, n, width, jnp.bfloat16, backward) for backward in (False, True)],
                    "fwd_us": fwd, "bwd_us": bwd, "call_us": median(clock), "compile_s": round(compile_s, 2)}
            if part == "whole":
                one_layer = {"mamba_n_heads": heads, "mamba_n_groups": groups, "mamba_d_state": n, "mamba_d_head": width,
                             "layer_types": ["mamba"], "dtype": "bfloat16", "ssd_chunk": chunk}
                flops = arithmetic.ssd_flops_per_step(one_layer, batch, seq)
                nbytes = arithmetic.ssd_bytes_per_step(one_layer, batch, seq)
                line["floor_flops_us"], line["floor_bytes_us"] = flops / 197e12 * 1e6, nbytes / 819e9 * 1e6
                line["roofline_pct"] = 100 * max(line["floor_flops_us"], line["floor_bytes_us"]) / (fwd + bwd)
            say(**line)


if __name__ == "__main__":
    main()

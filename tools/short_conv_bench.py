"""The linear mixer's elementwise passes alone, on the chip: the chain of XLA operations the model ran until PR 54
beside `ops/short_conv.py`, forward and with the gradient; and LFM2's gated short convolution likewise (PR 62).

    chiprun -- python3 tools/short_conv_bench.py
    chiprun -- python3 tools/short_conv_bench.py --form chain,module --set ROWS_NORMALIZED=64 --set ROWS_PLAIN=16
    chiprun -- python3 tools/short_conv_bench.py --form gated_chain,gated --set ROWS_GATED=64 --set GATED_TILE=256
    python3 tools/short_conv_bench.py --rehearse --shape 2x64x4x96x192
    python3 tools/short_conv_bench.py --rehearse --form gated_chain,gated --gated-shape 2x64x256x3

`--shape BATCHxSEQxHEADSxDKxDV` (one linear layer of the Olmo-Hybrid cell by default: a row of 4,096, 30 heads of 96 /
192, so z = [W_q x | W_k x | W_v x] is 1 x 4,096 x 11,520 bf16). Inputs from `--seed`: the three bf16 projections,
f32 taps, bf16 cotangents heads-first as `gdn_bwd` hands them.

`--form`, what takes the projections to the scan's q, k, v (B, H, S, d) bf16:
  chain      `models/olmo_hybrid.py` as it stood at PR 53, kept here alone: widen, four shifted products, SiLU, to
             heads-first, the L2 norm and q's scale in f32, the cast, and jax's gradient of that
  module     `short_conv(backend="pallas")` for q, k and v: the same chain forward, `short_conv_bwd` for the gradient
  xla        `short_conv(backend="xla")`: the module's form off the TPU (the chain, differentiated by jax)
and `out_chain`, `linear_out`'s gated norm at (B, S, H x DV) as it stands (o heads-first and W_z x in, the gated
product out): measured, not replaced (PERF.md section 6, PR 54).

`--gated-shape BATCHxSEQxDxTAPS` (one conv layer of the LFM2 cell by default: 8 x 4,096 x 2,048, 3 taps, so bcu =
W_in h is 8 x 4,096 x 6,144 bf16), what takes bcu to y (B, S, D) bf16:
  gated_chain  `models/lfm2.py short_conv`'s lines under `conv_mix` as they stood at PR 61, kept here alone: widen,
               z = b u, three shifted products of z, the gate c, the cast, and jax's gradient of that
  gated        `gated_short_conv(backend="pallas")`: the chain with `bcu` shifted forward, `gated_conv_bwd` for the
               gradient; `--set ROWS_GATED=` / `--set GATED_TILE=` stand the rows a step and the widest tile in
Their `needed_mb` is 4 + 7 passes of B x S x D x 2 B (bcu read and y written; bcu and dy read, dbcu written).

A JSON line a form, on stdout and in `chiprun_out/short_conv_bench.jsonl`: `fwd_us`, the device's busy time of one
call of the forward program, and `vjp_us`, of the program that makes the gradients to the projections and the taps
from the cotangents (whatever of the forward pass it needs inside it: all of it for `chain`, none for `module`),
medians over `--rounds` traced calls; `call_us` the host's clock over the second; `needed_mb` (z read and q, k, v
written forward; z and the cotangents read and dz written backward: 2 + 3 passes of B x S x C x 2 B) and `gbps`,
those bytes over `fwd_us + vjp_us`, beside HBM's 819; `fwd_ops` / `vjp_ops`, the longest device operations of a
call. Before the timings `module` and `xla` are compared with `chain` on float32 operands (`check`: the largest
distance of outputs and gradients over the reference's largest value).

Every operand is an argument of the jitted program and committed to HBM; XLA still prefetches a whole operand into
VMEM where one fits (`copy-start` / `copy-done` in a line's operations), which a train step's memory has no room for.

`--rehearse` walks it off the chip in interpret mode with no timing. Runs on TPU chips only otherwise. No benchmark
cell and no test but the rehearsal's runs this.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ("chain", "module", "xla", "out_chain", "gated_chain", "gated")


def _device_ops(trace_dir):
    """[(name, us)] of every operation on the first chip's `XLA Ops` line, in time order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    return [(ev.name.split(" = ", 1)[0].lstrip("%"), ev.duration_ns / 1e3) for line in plane.lines
            if line.name == "XLA Ops" for ev in sorted(line.events, key=lambda ev: ev.start_ns)]


def forms(jax, jnp, sc, heads, dk, interpret):
    """{form: f(zq, zk, zv, wq, wk, wv) -> (q, k, v)}."""
    f32 = jnp.float32

    def heads_first(x):
        b, s, _ = x.shape
        return x.reshape(b, s, heads, -1).transpose(0, 2, 1, 3)

    def chain(zq, zk, zv, wq, wk, wv):
        def conv_silu(z, taps):
            n = taps.shape[0]
            z, w = z.astype(f32), taps.astype(f32)
            return jax.nn.silu(sum(w[j] * sc._shifted(z, n - 1 - j) for j in range(n)))

        q, k, v = (heads_first(conv_silu(z, w)) for z, w in ((zq, wq), (zk, wk), (zv, wv)))
        unit = lambda z: z * jax.lax.rsqrt(jnp.sum(z * z, axis=-1, keepdims=True) + 1e-6)  # noqa: E731
        q, k = unit(q) * dk ** -0.5, unit(k)
        return q.astype(zq.dtype), k.astype(zk.dtype), v.astype(zv.dtype)

    def module(backend):
        def f(zq, zk, zv, wq, wk, wv):
            one = functools.partial(sc.short_conv, heads=heads, backend=backend, interpret=interpret)
            return one(zq, wq, normalize=True, scale=dk ** -0.5), one(zk, wk, normalize=True), one(zv, wv)
        return f

    return {"chain": chain, "module": module("pallas"), "xla": module("xla")}


def gated_forms(jax, jnp, sc, interpret):
    """{form: f(bcu, taps) -> y}."""
    f32 = jnp.float32

    def gated_chain(bcu, taps):
        d, n = bcu.shape[2] // 3, taps.shape[0]
        b, c, u = (bcu[..., i * d:(i + 1) * d].astype(f32) for i in range(3))
        z, w = b * u, taps.astype(f32)
        return (c * sum(w[j] * sc._shifted(z, n - 1 - j) for j in range(n))).astype(bcu.dtype)

    return {"gated_chain": gated_chain,
            "gated": functools.partial(sc.gated_short_conv, backend="pallas", interpret=interpret)}


def out_chain(jax, jnp, eps=1e-6):
    """`linear_out`'s gated norm as it stands: (o (B, H, S, dv), z (B, S, H dv), scale (dv,)) -> (B, S, H dv)."""
    from ray_tpu.models.llama import rms_norm

    def f(o, z, scale):
        b, h, s, dv = o.shape
        o = rms_norm(o.transpose(0, 2, 1, 3), scale, eps)
        return (o.reshape(b, s, h * dv) * jax.nn.silu(z.astype(jnp.float32))).astype(z.dtype)
    return f


def main():
    p = argparse.ArgumentParser(prog="tools/short_conv_bench.py")
    p.add_argument("--shape", default="1x4096x30x96x192")
    p.add_argument("--gated-shape", default="8x4096x2048x3")
    p.add_argument("--form", default="chain,module,out_chain")
    p.add_argument("--set", action="append", default=[], metavar="NAME=INT", help="stand a constant of ops/short_conv.py in")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import short_conv as sc

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit(f"tools/short_conv_bench.py: not on a TPU: {jax.devices()}")
    for name, value in (item.split("=") for item in args.set):
        assert hasattr(sc, name), name
        setattr(sc, name, int(value))
    batch, seq, heads, dk, dv = (int(n) for n in args.shape.split("x"))
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def say(**line):
        shape = args.gated_shape if line["form"].startswith("gated") else args.shape
        line = {"device": jax.devices()[0].device_kind, "shape": shape, "set": args.set, **line}
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "short_conv_bench.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 12)
    bf16 = jnp.bfloat16
    wide = lambda key, d: jax.random.normal(key, (batch, seq, heads * d)).astype(bf16)  # noqa: E731
    taps = lambda key, d: jax.random.normal(key, (4, heads * d)) * 0.5  # noqa: E731
    first = lambda key, d: (jax.random.normal(key, (batch, heads, seq, d)) * 0.1).astype(bf16)  # noqa: E731
    operands = (wide(keys[0], dk), wide(keys[1], dk), wide(keys[2], dv), taps(keys[3], dk), taps(keys[4], dk), taps(keys[5], dv))
    cotangents = (first(keys[6], dk), first(keys[7], dk), first(keys[8], dv))
    by_form = forms(jax, jnp, sc, heads, dk, args.rehearse)
    by_form["out_chain"] = out_chain(jax, jnp)
    out_operands = (first(keys[9], dv), wide(keys[10], dv), 1.0 + 0.1 * jax.random.normal(keys[11], (dv,)))
    out_cotangent = wide(keys[6], dv)
    g_batch, g_seq, g_d, g_taps = (int(n) for n in args.gated_shape.split("x"))
    by_form.update(gated_forms(jax, jnp, sc, args.rehearse))
    gated_operands = (jax.random.normal(keys[0], (g_batch, g_seq, 3 * g_d)).astype(bf16),
                      jax.random.normal(keys[3], (g_taps, g_d)) * 0.5)
    gated_cotangent = (jax.random.normal(keys[6], (g_batch, g_seq, g_d)) * 0.1).astype(bf16)
    references = {"module": "chain", "xla": "chain", "gated": "gated_chain"}

    def operands_of(form):
        """(operands, cotangents) of a form."""
        return ((out_operands, out_cotangent) if form == "out_chain" else
                (gated_operands, gated_cotangent) if form.startswith("gated") else (operands, cotangents))

    def programs(form):
        """(forward, vjp, operands, cotangents) of a form: the vjp returns the gradients to every operand."""
        f = by_form[form]
        return jax.jit(f), jax.jit(lambda ops, cts: jax.vjp(f, *ops)[1](cts)), *operands_of(form)

    def distance(form):
        """The form's outputs and gradients against its chain's on f32 operands (so both round nothing at the end)."""
        f32 = lambda xs: jax.tree.map(lambda x: x.astype(jnp.float32), xs)  # noqa: E731
        ops, cts = operands_of(form)
        both = []
        for f in (by_form[references[form]], by_form[form]):
            both.append(jax.jit(lambda ops, cts, f=f: (f(*ops), jax.vjp(f, *ops)[1](cts)))(f32(ops), f32(cts)))
        far = lambda a, b: float(jnp.abs(a - b).max() / jnp.abs(b).max())  # noqa: E731
        (o, g), (o_ref, g_ref) = both[1], both[0]
        wide = len(g) // 2  # the gradients to the wide operands, then to their taps
        return {"out": max(jax.tree.leaves(jax.tree.map(far, o, o_ref))), "dz": max(map(far, g[:wide], g_ref[:wide])),
                "dtaps": max(map(far, g[wide:], g_ref[wide:]))}

    z_bytes = batch * seq * heads * (2 * dk + dv) * 2
    for form in args.form.split(","):
        assert form in FORMS, form
        line = {"form": form}
        if form in references:
            line["check"] = distance(form)
        fwd, vjp, ops, cts = programs(form)
        t = time.perf_counter()
        jax.block_until_ready((fwd(*ops), vjp(ops, cts)))
        line["compile_s"] = round(time.perf_counter() - t, 2)
        if args.rehearse:
            say(**line, rehearsal=True)
            continue
        clock = []
        for _ in range(args.rounds):
            t = time.perf_counter()
            jax.block_until_ready(vjp(ops, cts))
            clock.append((time.perf_counter() - t) * 1e6)
        times = {}
        for name, program in (("fwd", lambda: fwd(*ops)), ("vjp", lambda: vjp(ops, cts))):
            with tempfile.TemporaryDirectory() as trace_dir:
                jax.profiler.start_trace(trace_dir)
                for _ in range(args.rounds):
                    jax.block_until_ready(program())
                jax.profiler.stop_trace()
                events = _device_ops(trace_dir)
            per_call = len(events) // args.rounds
            calls = [events[i * per_call:(i + 1) * per_call] for i in range(args.rounds)]
            times[name] = median(sum(us for _, us in call) for call in calls)
            line[name + "_ops"] = sorted(((n, round(us, 1)) for n, us in calls[-1]), key=lambda x: -x[1])[:12]
            line[name + "_n_ops"] = per_call
        needed = (10 * batch * seq * heads * dv * 2 if form == "out_chain" else
                  11 * g_batch * g_seq * g_d * 2 if form.startswith("gated") else 5 * z_bytes)
        say(**line, fwd_us=times["fwd"], vjp_us=times["vjp"], call_us=median(clock), needed_mb=needed / 1e6,
            gbps=needed / (times["fwd"] + times["vjp"]) / 1e3)


if __name__ == "__main__":
    main()

"""The residual streams' passes alone, on the chip: `ops/hyper_connections.py`'s three passes over the streams,
forward and with the gradient, as jax differentiated them until PR 67 beside the module's backward rules.

    chiprun -- python3 tools/mhc_bench.py
    chiprun -- python3 tools/mhc_bench.py --form module --part post --tile 64,128,256 --set ROWS=8
    python3 tools/mhc_bench.py --rehearse --shape 2x4x48x128

`--shape BATCHxSTREAMSxSEQxD` (a sublayer of the Xing4.0 cell by default: 1 x 4 x 4,096 x 3,584 bf16 streams, y bf16,
u and the maps float32, Phi 24 x 4 x 3,584 float32). Inputs from `--seed`; the maps are `maps`' own of those streams.

`--form`, who differentiates:
  autodiff   the three passes as plain `jax.numpy` under jax's own gradient, as the module stood at PR 66 (kept here
             alone; the product at `Precision.HIGHEST` on a float32 copy of the streams)
  xla        the module's rules written as one pass in `jax.numpy` (`backend="xla"`: what runs off the TPU)
  module     the module on a TPU (`backend="pallas"`): `mhc_post_bwd` and `mhc_pre_bwd`, `pre_mix`'s rule in `jax.numpy`
`--part`, which pass:
  post       `post_res_mix(x, y, H_post, H_res)` and the gradients to all four
  columns    m = r (vec(X) Phi), the product and the norm inside `maps`, and the gradients to x and Phi
  pre        `pre_mix(x, H_pre)` and the gradients to both
  sublayer   `maps`, `pre_mix`, a cast in the sublayer's place, `post_res_mix`: the gradients to x, Phi, alpha and
             the biases from a cotangent of X', so the sum of the streams' three cotangents is inside it
`--tile A,B,...` runs `module` once for each `TOKEN_TILE`; `--set NAME=INT` stands a constant of the module in.

A JSON line a form, part and tile, on stdout and in `chiprun_out/mhc_bench.jsonl`: `fwd_us`, the device's busy time
of one call of the forward program, and `vjp_us`, of the program that makes the gradients from the cotangent
(whatever of the forward pass it needs inside it), medians over `--rounds` traced calls; `call_us` the host's clock
over the second; `needed_mb`, the bytes one pass over its operands and results moves (`NEEDED`), and `gbps`, the
backward's over `vjp_us`, beside HBM's 819; `fwd_ops` / `vjp_ops`, the longest device operations of a call. Before
the timings `xla` and `module` are compared with `autodiff` on the same operands (`check`: the largest distance of
each gradient over the reference's largest value; bf16 streams round each cotangent once, so 1e-2 is rounding).

`--rehearse` walks it off the chip in interpret mode with no timing. Runs on TPU chips only otherwise. No benchmark
cell and no test but the rehearsal's runs this.
"""

from __future__ import annotations

import argparse
import functools
import glob
import json
import operator
import os
import sys
import tempfile
import time
from statistics import median

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FORMS = ("autodiff", "xla", "module")
PARTS = ("post", "columns", "pre", "sublayer")
KW = dict(norm_eps=1e-6, rounds=20, eps=1e-6, clamp=(-30.0, 30.0))


def _device_ops(trace_dir):
    """[(name, us)] of every operation on the first chip's `XLA Ops` line, in time order."""
    from jax.profiler import ProfileData

    (path,) = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    plane = next(p for p in ProfileData.from_file(path).planes if p.name.startswith("/device:TPU:"))
    return [(ev.name.split(" = ", 1)[0].lstrip("%"), ev.duration_ns / 1e3) for line in plane.lines
            if line.name == "XLA Ops" for ev in sorted(line.events, key=lambda ev: ev.start_ns)]


def autodiff_passes(jax, jnp, mhc):
    """(columns, pre_mix, post_res_mix, maps) as `ops/hyper_connections.py` held them at PR 66."""
    f32 = jnp.float32
    total = lambda planes: functools.reduce(operator.add, planes)  # noqa: E731

    def columns(x, phi, norm_eps=KW["norm_eps"]):
        xf = x.astype(f32)
        r = jax.lax.rsqrt(jnp.mean(xf * xf, axis=(1, 3)) + norm_eps)
        return r * jnp.einsum("bnsd,cnd->cbs", xf, phi.astype(f32), precision=jax.lax.Precision.HIGHEST)

    def pre_mix(x, h_pre):
        return total([h_pre[i][..., None] * x[:, i].astype(f32) for i in range(x.shape[1])])

    def post_res_mix(x, y, h_post, h_res):
        n = x.shape[1]
        xs, yf = [x[:, j].astype(f32) for j in range(n)], y.astype(f32)
        out = [h_post[i][..., None] * yf + total([h_res[i, j][..., None] * xs[j] for j in range(n)]) for i in range(n)]
        return jnp.stack(out, axis=1).astype(x.dtype)

    def maps(x, phi, alpha, bias):
        n, m = x.shape[1], columns(x, phi)
        bias = bias[:, None, None]
        logits = (alpha[2] * m[2 * n:] + bias[2 * n:]).reshape(n, n, *m.shape[1:])
        return (jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n]), 2.0 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n]),
                mhc.sinkhorn(logits, KW["rounds"], KW["eps"], KW["clamp"]))

    return columns, pre_mix, post_res_mix, maps


def passes(jax, jnp, mhc, form, interpret):
    """{part: f(*operands)} of a form."""
    if form == "autodiff":
        columns, pre_mix, post_res_mix, maps = autodiff_passes(jax, jnp, mhc)
    else:
        how = dict(backend="pallas" if form == "module" else "xla", interpret=interpret)
        columns = lambda x, phi: mhc._columns(x, phi, KW["norm_eps"], form == "module", interpret)  # noqa: E731
        pre_mix, post_res_mix = mhc.pre_mix, functools.partial(mhc.post_res_mix, **how)
        maps = functools.partial(mhc.maps, **KW, **how)

    def sublayer(x, phi, alpha, bias):
        h_pre, h_post, h_res = maps(x, phi, alpha, bias)
        return post_res_mix(x, pre_mix(x, h_pre).astype(x.dtype), h_post, h_res)

    return {"post": post_res_mix, "columns": columns, "pre": pre_mix, "sublayer": sublayer}


def main():
    p = argparse.ArgumentParser(prog="tools/mhc_bench.py")
    p.add_argument("--shape", default="1x4x4096x3584")
    p.add_argument("--form", default="autodiff,xla,module")
    p.add_argument("--part", default="post,columns,pre,sublayer")
    p.add_argument("--tile", default="", help="TOKEN_TILE values for `module`, comma-separated")
    p.add_argument("--set", action="append", default=[], metavar="NAME=INT", help="stand a constant of ops/hyper_connections.py in")
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--rounds", type=int, default=5)
    p.add_argument("--rehearse", action="store_true")
    args = p.parse_args()
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path.insert(0, REPO)
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops import hyper_connections as mhc

    if not args.rehearse and jax.devices()[0].platform != "tpu":
        sys.exit(f"tools/mhc_bench.py: not on a TPU: {jax.devices()}")
    for name, value in (item.split("=") for item in args.set):
        assert hasattr(mhc, name), name
        setattr(mhc, name, int(value))
    batch, n, seq, d = (int(v) for v in args.shape.split("x"))
    out_dir = os.path.join(REPO, "chiprun_out")
    os.makedirs(out_dir, exist_ok=True)

    def say(**line):
        line = {"device": jax.devices()[0].device_kind, "shape": args.shape, "set": args.set, **line}
        print(json.dumps(line), flush=True)
        with open(os.path.join(out_dir, "mhc_bench.jsonl"), "a") as fh:
            fh.write(json.dumps(line) + "\n")

    keys = jax.random.split(jax.random.PRNGKey(args.seed), 8)
    bf16, f32 = jnp.bfloat16, jnp.float32
    columns = mhc.n_maps(n)
    x = jax.random.normal(keys[0], (batch, n, seq, d)).astype(bf16)
    y = jax.random.normal(keys[1], (batch, seq, d)).astype(bf16)
    phi = jax.random.normal(keys[2], (columns, n, d)) * (n * d) ** -0.5
    alpha, bias = jnp.asarray([0.7, 1.1, 0.9]), jax.random.normal(keys[3], (columns,))
    h_pre, h_post, h_res = jax.jit(functools.partial(mhc.maps, **KW, backend="xla"))(x, phi, alpha, bias)
    stream_bytes, plane_bytes = x.size * 2, y.size * 2
    # (operands, the cotangent of the result, the bytes one pass forward and one backward move)
    cases = {
        "post": ((x, y, h_post, h_res), (jax.random.normal(keys[4], x.shape) * 0.1).astype(bf16),
                 (2 * stream_bytes + plane_bytes, 3 * stream_bytes + 2 * plane_bytes)),
        "columns": ((x, phi), jax.random.normal(keys[5], (columns, batch, seq)) * 0.1, (stream_bytes, 2 * stream_bytes)),
        "pre": ((x, h_pre), jax.random.normal(keys[6], (batch, seq, d)) * 0.1,
                (stream_bytes + 2 * plane_bytes, 2 * stream_bytes + 2 * plane_bytes)),
        "sublayer": ((x, phi, alpha, bias), (jax.random.normal(keys[4], x.shape) * 0.1).astype(bf16),
                     (4 * stream_bytes + 3 * plane_bytes, 7 * stream_bytes + 5 * plane_bytes)),
    }

    def gradients(f, ops, ct):
        return jax.jit(lambda ops, ct: jax.vjp(f, *ops)[1](ct))(ops, ct)

    def distance(form, part):
        """The form's gradients of `part` against `autodiff`'s on the same operands, each over the reference's largest."""
        ops, ct, _ = cases[part]
        got, want = (gradients(passes(jax, jnp, mhc, f, args.rehearse)[part], ops, ct) for f in (form, "autodiff"))
        return [float(jnp.abs(a.astype(f32) - b.astype(f32)).max() / jnp.abs(b.astype(f32)).max()) for a, b in zip(got, want)]

    tiles = [int(t) for t in args.tile.split(",")] if args.tile else [mhc.TOKEN_TILE]
    for form in args.form.split(","):
        assert form in FORMS, form
        for tile in (tiles if form == "module" else tiles[:1]):
            mhc.TOKEN_TILE = tile
            jax.clear_caches()  # the kernels' callers are jitted and read the constants when traced
            for part in args.part.split(","):
                assert part in PARTS, part
                line = {"form": form, "part": part, "tile": mhc.token_tile(seq) if form == "module" else None}
                if form != "autodiff":
                    line["check"] = distance(form, part)
                f = passes(jax, jnp, mhc, form, args.rehearse)[part]
                ops, ct, (fwd_bytes, vjp_bytes) = cases[part]
                fwd, vjp = jax.jit(f), jax.jit(lambda ops, ct, f=f: jax.vjp(f, *ops)[1](ct))
                t = time.perf_counter()
                jax.block_until_ready((fwd(*ops), vjp(ops, ct)))
                line["compile_s"] = round(time.perf_counter() - t, 2)
                if args.rehearse:
                    say(**line, rehearsal=True)
                    continue
                clock = []
                for _ in range(args.rounds):
                    t = time.perf_counter()
                    jax.block_until_ready(vjp(ops, ct))
                    clock.append((time.perf_counter() - t) * 1e6)
                times = {}
                for name, program in (("fwd", lambda: fwd(*ops)), ("vjp", lambda: vjp(ops, ct))):
                    with tempfile.TemporaryDirectory() as trace_dir:
                        jax.profiler.start_trace(trace_dir)
                        for _ in range(args.rounds):
                            jax.block_until_ready(program())
                        jax.profiler.stop_trace()
                        events = _device_ops(trace_dir)
                    per_call = len(events) // args.rounds
                    calls = [events[i * per_call:(i + 1) * per_call] for i in range(args.rounds)]
                    times[name] = median(sum(us for _, us in call) for call in calls)
                    line[name + "_ops"] = sorted(((n, round(us, 1)) for n, us in calls[-1]), key=lambda x: -x[1])[:10]
                    line[name + "_n_ops"] = per_call
                say(**line, fwd_us=times["fwd"], vjp_us=times["vjp"], call_us=median(clock),
                    needed_mb={"fwd": fwd_bytes / 1e6, "vjp": vjp_bytes / 1e6},
                    gbps={"fwd": fwd_bytes / times["fwd"] / 1e3, "vjp": vjp_bytes / times["vjp"] / 1e3})


if __name__ == "__main__":
    main()

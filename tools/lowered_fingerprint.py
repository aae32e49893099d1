"""Does a change leave another cell's compiled program alone? The lowered text of the scalar delta rule's kernels, the
short convolution's gradient kernel and the Olmo-Hybrid step (the cell's configuration over `fsdp=4` and the nano one),
for a `v5e:2x2` described without a chip, hashed. A Mosaic call's payload is MLIR bytecode that carries file paths and
line numbers, so a docstring edit or another checkout directory changes the lowered text's bytes: each payload is
parsed and printed again without locations before the hash. Run it from two checkouts and compare the lines (PR 59):

    python3 tools/lowered_fingerprint.py            # this checkout
    python3 tools/lowered_fingerprint.py <root>     # another one (`git archive <commit> | tar -x -C <root>`)
    python3 tools/lowered_fingerprint.py <root> sdar-nano keye-vl2-nano ...   # and the steps of these configurations

Further arguments name files of `benchmark/configs/`: each one's whole step is lowered as its cell builds it and hashed
the same way (PR 61 compared fifteen, every accepted model's nano and full-size step, across a change to `stack.py`,
`gqa_experts.py` and `training.py`: a full-size step lowers in 5-20 s, nothing is compiled).
"""
import base64
import hashlib
import importlib
import json
import os
import re
import sys


def normalized(text: str) -> str:
    """`text` with every Mosaic payload replaced by its kernel's MLIR without locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    def decode(match):
        ctx = mlir.make_ir_context()
        ctx.allow_unregistered_dialects = True  # the payload is in the versioned `stable_mosaic` dialect
        with ctx:
            module = ir.Module.parse(base64.b64decode(match.group(1)))
            return "MOSAIC<" + module.operation.get_asm(enable_debug_info=False) + ">"

    return re.sub(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22', decode, text)


def main():
    root = os.path.abspath(sys.argv[1] if len(sys.argv) > 1 else ".")
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    import aot_v5e
    from ray_tpu.ops import gated_delta_rule as gdn
    from ray_tpu.ops.short_conv import short_conv

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    digest = lambda lowered: hashlib.sha256(normalized(lowered.as_text()).encode()).hexdigest()[:16]  # noqa: E731
    wide, f32 = (1, 30, 4096), jnp.float32
    scan = lambda *a: gdn.gated_delta_rule(*a, backend="pallas").astype(f32).sum()  # noqa: E731
    conv = lambda z, taps: short_conv(  # noqa: E731
        z, taps, heads=30, normalize=True, scale=96 ** -0.5, backend="pallas").astype(f32).sum()
    out = {
        "gdn": digest(jax.jit(jax.grad(scan, argnums=(0, 1, 2, 3, 4))).lower(
            sd((*wide, 96)), sd((*wide, 96)), sd((*wide, 192)), sd(wide, f32), sd(wide, f32))),
        "heads_per_program": gdn.heads_per_program(30, 4096, gdn.CHUNK, 96, 192, 2),
        "short_conv": digest(jax.jit(jax.grad(conv, argnums=(0, 1))).lower(sd((1, 4096, 2880)), sd((4, 2880), f32))),
    }
    for name in ("olmo-hybrid-7b-fsdp4", "olmo-hybrid-nano", *sys.argv[2:]):
        with open(os.path.join("benchmark", "configs", name + ".json")) as fh:
            c = json.load(fh)
        model = importlib.import_module("benchmark.models." + c["model"])
        (to_config,) = [f for attr, f in vars(model).items() if attr.endswith("_config")]  # as `aot_v5e._step_case`
        out["step:" + name] = digest(aot_v5e._lowered_step(
            topo, c["layout"]["mesh"] or {"data": 1}, to_config(c), c["batch"]["global_rows"], c["batch"]["seq"],
            c["learning_rate"]))
    print("LOWERED " + json.dumps(out))


if __name__ == "__main__":
    main()

"""Does a change leave every cell's compiled program, and every model's first parameters, alone? Two sides, each one
line to compare between two checkouts (PR 59, PR 61, PR 63):

    python3 tools/lowered_fingerprint.py [<root> [<configuration> ...]]            # LOWERED {...}
    python3 tools/lowered_fingerprint.py --params [<root> [<configuration> ...]]   # PARAMS {...}

`<root>` is the checkout read (this one by default; another: `git archive <commit> | tar -x -C <root>`), a
`<configuration>` a file of `benchmark/configs/` without its `.json`: with none named, every file there, so a new
configuration needs no edit here.

LOWERED: the lowered text, hashed, of the two chunked scans' kernels (`gdn_fwd` / `gdn_bwd`, `kda_fwd` / `kda_bwd`:
the call and its gradient), of the short convolution's gradient kernel, and of each configuration's whole step as its
cell builds it, for a `v5e:2x2` described without a chip. A Mosaic call's payload is MLIR bytecode that carries file
paths and line numbers, so a docstring edit or another checkout directory changes the lowered text's bytes: each
payload is parsed and printed again without locations before the hash. A full-size step lowers in 5-20 s, nothing is
compiled (nineteen configurations: about 4 min). The step is lowered from the abstract state `aot_v5e._lowered_step`
lays out as the root's own `create_train_state` does: since PR 64 with `TrainState.compute`, so an expert
configuration's step differs from a root's of before, and a dense one's does not.

PARAMS: for each configuration `shapes`, a hash of `jax.eval_shape(init_params)` (paths, shapes, dtypes) with the
trees `param_logical_axes` and `frozen_params` return, leaf by leaf; for a nano configuration also `seed0` and
`seed1`, hashes of the bytes of `init_params(config, PRNGKey(seed))` under jit, as `create_train_state` makes them.
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


def sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def tree_digest(tree) -> str:
    """A hash of a tree of arrays: every leaf's path, shape, dtype and bytes, in the tree's own order."""
    import jax
    import numpy as np

    h = hashlib.sha256()
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.shape} {a.dtype}\n".encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:16]


def configuration(name: str):
    """(the file's dict, the model's configuration as the cell's harness builds it)."""
    with open(os.path.join("benchmark", "configs", name + ".json")) as fh:
        c = json.load(fh)
    model = importlib.import_module("benchmark.models." + c["model"])
    (to_config,) = [f for attr, f in vars(model).items() if attr.endswith("_config")]  # as `aot_v5e._step_case`
    return c, to_config(c)


def params_side(names):
    import jax

    from ray_tpu.models.training import model_for

    out = {}
    for name in names:
        _, cfg = configuration(name)
        model = model_for(cfg)
        shapes = jax.eval_shape(lambda: model.init_params(cfg, jax.random.PRNGKey(0)))
        frozen = getattr(model, "frozen_params", None)
        # Every tree leaf by leaf in jax's own order (a dict's keys sorted): the order a dict was written in is no part.
        leaves = lambda tree: jax.tree_util.tree_flatten_with_path(  # noqa: E731
            tree, is_leaf=lambda x: isinstance(x, tuple))[0]
        described = [f"{jax.tree_util.keystr(path)} {leaf.shape} {leaf.dtype}" for path, leaf in leaves(shapes)]
        for tree in (model.param_logical_axes(cfg), frozen(cfg) if frozen else None):
            described += [f"{jax.tree_util.keystr(path)} {leaf!r}" for path, leaf in leaves(tree)]
        line = {"shapes": sha("\n".join(described))}
        if "nano" in name:
            init = jax.jit(lambda key: model.init_params(cfg, key))
            line.update({f"seed{seed}": tree_digest(init(jax.random.PRNGKey(seed))) for seed in (0, 1)})
        out[name] = line
    print("PARAMS " + json.dumps(out))


def lowered_side(names):
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    import aot_v5e
    from ray_tpu.ops import gated_delta_rule as gdn
    from ray_tpu.ops import kda
    from ray_tpu.ops.short_conv import short_conv

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)  # noqa: E731
    digest = lambda lowered: sha(normalized(lowered.as_text()))  # noqa: E731
    f32, every = jnp.float32, (0, 1, 2, 3, 4)
    wide, held = (1, 30, 4096), (1, 8, 4096)  # a layer-row of the Olmo-Hybrid cell, of the Solar-Open2 cell
    scan = lambda *a: gdn.gated_delta_rule(*a, backend="pallas").astype(f32).sum()  # noqa: E731
    vector = lambda *a: kda.kimi_delta_rule(*a, backend="pallas").astype(f32).sum()  # noqa: E731
    conv = lambda z, taps: short_conv(  # noqa: E731
        z, taps, heads=30, normalize=True, scale=96 ** -0.5, backend="pallas").astype(f32).sum()
    out = {
        "gdn": digest(jax.jit(jax.grad(scan, argnums=every)).lower(
            sd((*wide, 96)), sd((*wide, 96)), sd((*wide, 192)), sd(wide, f32), sd(wide, f32))),
        "kda": digest(jax.jit(jax.grad(vector, argnums=every)).lower(
            sd((*held, 128)), sd((*held, 128)), sd((*held, 128)), sd((*held, 128), f32), sd(held, f32))),
        "heads_per_program": gdn.heads_per_program(30, 4096, gdn.CHUNK, 96, 192, 2),
        "short_conv": digest(jax.jit(jax.grad(conv, argnums=(0, 1))).lower(sd((1, 4096, 2880)), sd((4, 2880), f32))),
    }
    for name in names:
        c, cfg = configuration(name)
        out["step:" + name] = digest(aot_v5e._lowered_step(
            topo, c["layout"]["mesh"] or {"data": 1}, cfg, c["batch"]["global_rows"], c["batch"]["seq"],
            c["learning_rate"]))
    print("LOWERED " + json.dumps(out))


def main():
    args = [a for a in sys.argv[1:] if a != "--params"]
    root = os.path.abspath(args[0] if args else ".")
    os.chdir(root)
    sys.path[:0] = [root, os.path.join(root, "tests")]
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import jax

    jax.config.update("jax_enable_compilation_cache", False)
    names = args[1:] or sorted(f[:-len(".json")] for f in os.listdir(os.path.join("benchmark", "configs"))
                               if f.endswith(".json"))
    (params_side if "--params" in sys.argv[1:] else lowered_side)(names)


if __name__ == "__main__":
    main()

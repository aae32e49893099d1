"""The readings the limits of `benchmark/models/olmo_hybrid.py check` lie between, on the chips at the published
widths under the cell's mesh, every one of them through `check` itself, a JSON line a seed and a side (PERF.md
section 6, PR 51):

    system        the program, as the cell checks it: has to come out `ok`
    below         the reference computed in the nearest precision below the stated one (parameters, state, decay,
                  norms and logits in bf16) in the program's place: has to come out not `ok`, by one limit
    state_bf16    the program with the state each chunk starts from rounded to bf16 (the kernels' own mathematics,
                  `ops/gated_delta_rule.py _chunk_fwd` and `_chunk_bwd`, patched): not `ok`
    decay_bf16    the program with the running log-decay rounded to bf16: not `ok`
    decay_dropped the program with no decay (g = 0): not `ok`

One process drives the host's chips (`fsdp=4` over `jax.devices()`), parameters as the cell makes them (seeded,
sharded), no optimizer state; tokens uniform from the seed, a row a chip. The f32 reference runs once a seed.

    chiprun --chips 4 --timeout 3000 -- python3 tools/olmo_hybrid_readings.py --sides system,below,state_bf16 3141592653
    python3 tools/olmo_hybrid_readings.py --config olmo-hybrid-nano --sides system,below,state_bf16,decay_dropped 1 2   # here, on the CPU
"""
import argparse
import contextlib
import json
import os
import sys

sys.path.insert(0, ".")
READINGS = ("loss_abs_err", "grad_norm_rel_err", "leaf_grad_norm_rel_err", "loss_reference", "grad_norm_reference",
            "leaf_grad_norm_reference", "ok")


def light_system(bench, c, mesh, seed):
    """`bench.System` without optimizer and step: the parameters as the cell starts from them, laid out as it lays them."""
    import jax

    from ray_tpu.models import olmo_hybrid as program
    from ray_tpu.models.training import TrainState, param_shardings
    from ray_tpu.parallel import ShardingRules

    system = bench.System.__new__(bench.System)
    system.c, system.mesh, system.cfg = c, mesh, bench.olmo_hybrid_config(c)
    shardings = param_shardings(system.cfg, mesh, ShardingRules())
    params = jax.jit(lambda key: program.init_params(system.cfg, key), out_shardings=shardings)(jax.random.PRNGKey(seed))
    system.state = TrainState(params=params, opt_state=(), step=0)  # no compute copy: `check` differentiates at `params`
    return system


@contextlib.contextmanager
def planted(fault):
    """The chunk's mathematics with `fault` while the block runs: the XLA form and both kernels call these."""
    import jax.numpy as jnp

    from ray_tpu.ops import gated_delta_rule as gdn

    kept = {name: getattr(gdn, name) for name in ("_chunk_fwd", "_chunk_bwd", "_chunk_gates")}
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if fault == "state_bf16":
        gdn._chunk_fwd = lambda q, k, v, gam, beta, s, *made: kept["_chunk_fwd"](q, k, v, gam, beta, bf16(s), *made)
        gdn._chunk_bwd = lambda q, k, v, gam, beta, s, do, ds, *made: kept["_chunk_bwd"](
            q, k, v, gam, beta, bf16(s), do, bf16(ds), *made)
    else:
        gam_of = bf16 if fault == "decay_bf16" else jnp.zeros_like
        gdn._chunk_gates = lambda k, gam, beta: kept["_chunk_gates"](k, gam_of(gam), beta)
    try:
        yield
    finally:
        for name, f in kept.items():
            setattr(gdn, name, f)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="olmo-hybrid-7b-fsdp4")
    parser.add_argument("--sides", default="system,below")
    parser.add_argument("seeds", nargs="+", type=int)
    args = parser.parse_args()
    if args.config.endswith("-nano"):
        os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")
    import jax
    import numpy as np

    from benchmark.harness.manifest import Manifest
    from benchmark.models import olmo_hybrid as bench
    from ray_tpu._private.accelerators.jax_process import configure_compile_cache
    from ray_tpu.models import shard_batch
    from ray_tpu.parallel import MeshSpec

    configure_compile_cache()
    c = Manifest().config(args.config)
    mesh = MeshSpec(**c["layout"]["mesh"]).build(jax.devices()[:4])
    for seed in args.seeds:
        system = light_system(bench, c, mesh, seed % (1 << 31))
        tokens = shard_batch({"tokens": np.random.default_rng(seed).integers(
            0, c["vocab_size"] - 1, (c["batch"]["global_rows"], c["batch"]["seq"] + 1), dtype=np.int32)}, mesh)["tokens"]
        _, of_reference = bench.losses_and_norms(system)
        reference = jax.device_get(jax.jit(of_reference)(system.state.params, tokens))
        for side in args.sides.split(","):
            if side == "system":
                out = bench.check(system, tokens, reference=reference)
            elif side == "below":
                in_bf16 = bench.losses_and_norms(system, "bfloat16")[1]
                out = bench.check(system, tokens, reference=reference,
                                  program=lambda params, tokens: in_bf16(params, tokens)[:3])
            else:
                with planted(side):  # `check` traces the program inside the call
                    out = bench.check(system, tokens, reference=reference, program=bench.losses_and_norms(system)[0])
            print("READING " + json.dumps({"seed": seed, "side": side, **{name: out[name] for name in READINGS}}),
                  flush=True)
        del system


if __name__ == "__main__":
    main()

"""The readings the limits of `benchmark/models/granite_hybrid.py check` lie between, on the chip at the published
widths, every one of them through `check` itself, a JSON line a seed and a side (PERF.md section 6, PR 73):

    system        the program, as the cell checks it: has to come out `ok`
    below         the reference computed in the nearest precision below the stated one (parameters, state, decay, dt,
                  norms and logits in bf16) in the program's place: has to come out not `ok`
    state_bf16    the program with the state each chunk starts from rounded to bf16 (the kernels' own mathematics,
                  `ops/ssd.py _chunk_fwd` and `_chunk_bwd`, patched)
    decay_bf16    the program with the running log-decay rounded to bf16 where the chunk reads it
    dt_bf16       the program with dt rounded to bf16 behind its softplus
    embedding_1, attention_1, residual_1, logits_1
                  the program with that multiplier of the four set to 1

Parameters as the cell makes them (seeded), no optimizer state; tokens the first row of the cell's own traffic
(`fed4k`'s documents from the seed, packed). The f32 reference runs once a seed.

    chiprun --chips 1 --timeout 3000 -- python3 tools/granite_hybrid_readings.py --sides system,below,state_bf16,decay_bf16,dt_bf16 7973
    python3 tools/granite_hybrid_readings.py --config granite-hybrid-nano --sides system,below,logits_1 1 2   # here, on the CPU
"""
import argparse
import contextlib
import copy
import dataclasses
import json
import sys

sys.path.insert(0, ".")
READINGS = ("loss_abs_err", "grad_norm_rel_err", "leaf_grad_rel_err", "state_rel_err", "loss_reference",
            "grad_norm_reference", "ssd.decay_log_min", "ssd.state_rms", "ok")
MULTIPLIERS = {"embedding_1": "embedding_multiplier", "attention_1": "attention_multiplier",
               "residual_1": "residual_multiplier", "logits_1": "logits_scaling"}


def light_system(bench, c, seed):
    """`bench.System` without optimizer and step: the parameters as the cell starts from them."""
    import jax

    from ray_tpu.models import granite_hybrid as program
    from ray_tpu.models.training import TrainState

    system = bench.System.__new__(bench.System)
    system.c, system.mesh, system.cfg = c, None, bench.granite_hybrid_config(c)
    params = jax.jit(lambda key: program.init_params(system.cfg, key))(jax.random.PRNGKey(seed))
    system.state = TrainState(params=params, opt_state=(), step=0)
    return system


def first_row(c, mix, seed):
    """The first row the cell's loop is dealt for `seed`: `loops/fed.py prepare`'s documents, packed."""
    from benchmark.harness import traffic

    row_tokens, eot_id = c["batch"]["seq"] + 1, c["vocab_size"] - 1
    blocks = traffic.make_document_blocks(mix["documents"], seed, row_tokens, mix["block_rows"], row_tokens, eot_id)
    rows = traffic.pack_documents(blocks[0], row_tokens=row_tokens, eot_id=eot_id)["tokens"]
    return rows[:c["batch"]["global_rows"]]


@contextlib.contextmanager
def planted(fault):
    """The scan's mathematics with `fault` while the block runs: the XLA form and both kernels call these."""
    import jax.numpy as jnp

    from ray_tpu.ops import ssd

    kept = {(ssd, name): getattr(ssd, name) for name in ("_chunk_fwd", "_chunk_bwd", "_chunk_gates", "_gates")}
    bf16 = lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)  # noqa: E731
    if fault == "state_bf16":
        ssd._chunk_fwd = lambda q, k, v, gam, beta, s, *made: kept[ssd, "_chunk_fwd"](q, k, v, gam, beta, bf16(s), *made)
        ssd._chunk_bwd = lambda q, k, v, gam, beta, s, do, ds, *made: kept[ssd, "_chunk_bwd"](
            q, k, v, gam, beta, bf16(s), do, bf16(ds), *made)
    elif fault == "decay_bf16":
        ssd._chunk_gates = lambda k, gam, beta=None: kept[ssd, "_chunk_gates"](k, bf16(gam), beta)
    elif fault == "dt_bf16":
        ssd._gates = lambda x, dt, a_log: kept[ssd, "_gates"](x, bf16(dt), a_log)
    else:
        raise ValueError(f"no such fault: {fault}")
    try:
        yield
    finally:
        for (module, name), f in kept.items():
            setattr(module, name, f)


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="granite-4.0-h-micro-l10")
    parser.add_argument("--sides", default="system,below")
    parser.add_argument("seeds", nargs="+", type=int)
    args = parser.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark.harness.manifest import Manifest
    from benchmark.models import granite_hybrid as bench
    from ray_tpu._private.accelerators.jax_process import configure_compile_cache

    configure_compile_cache()
    manifest = Manifest()
    c, mix = manifest.config(args.config), manifest.traffic("fed4k")
    for seed in args.seeds:
        system = light_system(bench, c, seed)
        tokens = jnp.asarray(first_row(c, mix, seed))
        reference = jax.jit(bench.losses_and_grads(system)[1])(system.state.params, tokens)
        for side in args.sides.split(","):
            if side == "system":
                out = bench.check(system, tokens, reference=reference)
            elif side == "below":
                in_bf16 = bench.losses_and_grads(system, "bfloat16")[1]
                out = bench.check(system, tokens, reference=reference, program=in_bf16)
            elif side in MULTIPLIERS:
                faulty = copy.copy(system)
                faulty.cfg = dataclasses.replace(system.cfg, **{MULTIPLIERS[side]: 1.0})
                out = bench.check(system, tokens, reference=reference, program=bench.losses_and_grads(faulty)[0])
            else:
                with planted(side):  # `check` traces the program inside the call
                    out = bench.check(system, tokens, reference=reference, program=bench.losses_and_grads(system)[0])
            print("READING " + json.dumps({"seed": seed, "side": side, **{name: out[name] for name in READINGS}}),
                  flush=True)
        del system, reference


if __name__ == "__main__":
    main()

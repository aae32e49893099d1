"""The readings the limits of `benchmark/models/smallthinker.py check` lie between, on the chip at the published widths,
every one of them through `check` itself, a JSON line a seed and a side (PERF.md section 6, PR 70):

    system          the program, as the cell checks it: has to come out `ok`
    below           the reference computed in the nearest precision below the stated one (parameters, norms,
                    rotation, router and logits in bf16) in the program's place: has to come out not `ok`, by one limit
    tap             the reference with the router reading the post-attention normed state (the tap moved)
    silu            the reference with SwiGLU experts
    window_<n>      the reference with a window of n keys (4095, 4097: the off-by-one)
    rope_in_full    the reference with the rotation in the full layers too

A planted fault in the reference is the fault alone (the program under the same fault reads that and its own
rounding on top), so a limit it passes is one the program under it would pass. Parameters as the cell makes them
(seeded), no optimizer state; tokens the first row of the cell's own traffic (`fed16k`'s documents from the seed,
packed). The f32 reference runs once a seed.

    chiprun --chips 1 --timeout 3000 -- python3 tools/smallthinker_readings.py --sides system,below,tap,silu 7978
    python3 tools/smallthinker_readings.py --config smallthinker-nano --sides system,below,tap,silu,window_15 1 2   # on the CPU
"""
import argparse
import json
import sys

sys.path.insert(0, ".")
from solar_open2_readings import first_row  # noqa: E402  (the first row a fed cell's loop is dealt for a seed)
READINGS = ("loss_abs_err", "grad_norm_rel_err", "leaf_grad_rel_err", "expert_choices_flipped_share", "relu_live_abs_err",
            "loss_reference", "grad_norm_reference", "over_limit", "ok")
FAULTS = {"below": {"dtype": "bfloat16"}, "tap": {"router_reads": "post_attention"}, "silu": {"act": "silu"},
          "rope_in_full": {"rope_in_full": True}}


def light_system(bench, c, seed):
    """`bench.System` without optimizer and step: the parameters as the cell starts from them."""
    import jax

    from ray_tpu.models import smallthinker as program
    from ray_tpu.models.training import TrainState

    system = bench.System.__new__(bench.System)
    system.c, system.mesh, system.cfg = c, None, bench.smallthinker_config(c)
    params = jax.jit(lambda key: program.init_params(system.cfg, key))(jax.random.PRNGKey(seed))
    system.state = TrainState(params=params, opt_state=(), step=0)  # no compute copy: `check` differentiates at `params`
    return system


def in_the_programs_place(system, of_reference):
    """A reference's program as `check(program=)` takes one: its loss and gradients, its own choices and live count
    as the program's, and the program's routing report beside them."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import smallthinker as program

    c, cfg = system.c, system.cfg

    def stands_in(params, tokens):
        loss, norm, leaves, stats = of_reference(params, tokens)
        hidden = jnp.maximum(stats["held_pairs"], 1) * c["moe_ffn_hidden_size"]
        return loss, norm, leaves, {
            **program.routing_stats(params, tokens[:, :-1], cfg),
            "experts": jax.lax.top_k(stats["chosen"].astype(jnp.float32), c["moe_num_active_primary_experts"])[1],
            "relu_live_share": stats["relu_live"] / hidden}

    return stands_in


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="smallthinker-21b-a3b-l4")
    parser.add_argument("--traffic", default="fed16k")
    parser.add_argument("--sides", default="system,below")
    parser.add_argument("seeds", nargs="+", type=int)
    args = parser.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark.harness.manifest import Manifest
    from benchmark.models import smallthinker as bench
    from ray_tpu._private.accelerators.jax_process import configure_compile_cache

    configure_compile_cache()
    manifest = Manifest()
    c, mix = manifest.config(args.config), manifest.traffic(args.traffic)
    for seed in args.seeds:
        system = light_system(bench, c, seed)
        tokens = jnp.asarray(first_row(c, mix, seed))
        reference = jax.jit(bench.losses_and_grads(system)[1])(system.state.params, tokens)
        for side in args.sides.split(","):
            if side == "system":
                program = None
            else:
                faults = {"window": int(side[7:])} if side.startswith("window_") else FAULTS.get(side)
                if faults is None:
                    raise ValueError(f"no such side: {side}")
                program = in_the_programs_place(system, bench.losses_and_grads(system, **faults)[1])
            out = bench.check(system, tokens, reference=reference, program=program)
            print("READING " + json.dumps({"seed": seed, "side": side, **{name: out[name] for name in READINGS},
                                           "load_max_over_mean_by_layer": out["routing"]["load_max_over_mean_by_layer"],
                                           "held_pairs_per_layer": out["routing"]["held_pairs_per_layer"],
                                           "relu_live_share_by_layer": out["routing"]["relu_live_share_by_layer"]}), flush=True)
        del system, reference


if __name__ == "__main__":
    main()

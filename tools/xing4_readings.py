"""The readings the limits of `benchmark/models/xing4.py check` lie between, on the chip at the published widths,
every one of them through `check` itself, a JSON line a seed and a side (PERF.md section 6, PR 66):

    system      the program, as the cell checks it: has to come out `ok`
    below       the reference computed in the nearest precision below the stated one (parameters, maps, norms,
                rotation, router and logits in bf16) in the program's place: has to come out not `ok`, by one limit
    static      the reference with the maps' dynamic part left out (a_pre = a_post = a_res = 0)
    dynamic     the reference with the maps' static part left out (every bias 0)
    rounds_<n>  the reference with n Sinkhorn rounds in 20's place
    no_clamp    the reference without the clip of H_res's logits (no reading moves where none passes 30)

A planted fault in the reference is the fault alone (the program under the same fault reads that and its own
rounding on top), so a limit it passes is one the program under it would pass. Parameters as the cell makes them
(seeded), no optimizer state; tokens the first row of the cell's own traffic (`fed4k`'s documents from the seed,
packed). The f32 reference runs once a seed.

    chiprun --chips 1 --timeout 3000 -- python3 tools/xing4_readings.py --sides system,below,static,rounds_1 7978
    python3 tools/xing4_readings.py --config xing4-nano --sides system,below,static,dynamic,rounds_1 1 2   # on the CPU
"""
import argparse
import json
import sys

sys.path.insert(0, ".")
from solar_open2_readings import first_row  # noqa: E402  (the first row a fed cell's loop is dealt for a seed)
READINGS = ("loss_abs_err", "grad_norm_rel_err", "leaf_grad_rel_err", "expert_choices_flipped_share", "res_sum_err",
            "streams", "loss_reference", "grad_norm_reference", "over_limit", "ok")
FAULTS = {"below": {"dtype": "bfloat16"}, "static": {"dynamic": False}, "dynamic": {"static": False},
          "no_clamp": {"clamp": False}}


def light_system(bench, c, seed):
    """`bench.System` without optimizer and step: the parameters as the cell starts from them."""
    import jax

    from ray_tpu.models import xing4 as program
    from ray_tpu.models.training import TrainState

    system = bench.System.__new__(bench.System)
    system.c, system.mesh, system.cfg = c, None, bench.xing4_config(c)
    params = jax.jit(lambda key: program.init_params(system.cfg, key))(jax.random.PRNGKey(seed))
    system.state = TrainState(params=params, opt_state=(), step=0)  # no compute copy: `check` differentiates at `params`
    return system


def in_the_programs_place(system, of_reference):
    """A reference's program as `check(program=)` takes one: its loss and gradients, its own choices as the
    program's and its own H_res's sums; the other routing statistics are the program's."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import xing4 as program

    def stands_in(params, tokens):
        loss, norm, leaves, stats = of_reference(params, tokens)
        return loss, norm, leaves, {
            **program.routing_stats(params, tokens, system.cfg),
            "experts": jax.lax.top_k(stats["chosen"].astype(jnp.float32), system.c["num_experts_per_tok"])[1],
            "res_sum_err": stats["res_sum_err"][None]}

    return stands_in


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="xing4-29b-a4b-ep8-l5")
    parser.add_argument("--traffic", default="fed4k")
    parser.add_argument("--sides", default="system,below")
    parser.add_argument("seeds", nargs="+", type=int)
    args = parser.parse_args()
    import jax
    import jax.numpy as jnp

    from benchmark.harness.manifest import Manifest
    from benchmark.models import xing4 as bench
    from ray_tpu._private.accelerators.jax_process import configure_compile_cache

    configure_compile_cache()
    manifest = Manifest()
    c, mix = manifest.config(args.config), manifest.traffic(args.traffic)
    for seed in args.seeds:
        system = light_system(bench, c, seed)
        tokens = jnp.asarray(first_row(c, mix, seed))
        reference = jax.jit(bench.losses_and_grads(system)[1])(system.state.params, tokens)
        for side in args.sides.split(","):
            faults = {"rounds": int(side[7:])} if side.startswith("rounds_") else FAULTS.get(side)
            if side != "system" and faults is None:
                raise ValueError(f"no such side: {side}")
            program = None if side == "system" else in_the_programs_place(system, bench.losses_and_grads(system, **faults)[1])
            out = bench.check(system, tokens, reference=reference, program=program)
            print("READING " + json.dumps({"seed": seed, "side": side, **{name: out[name] for name in READINGS},
                                           "held_pairs_per_layer": out["routing"]["held_pairs_per_layer"]}), flush=True)
        del system, reference


if __name__ == "__main__":
    main()

"""The readings the limits of `benchmark/models/sdar.py check` lie between, on the chip at the published widths,
every one of them through `check` itself, a JSON line a seed and a side (PERF.md section 6, PR 47):

    system      the program, as the cell checks it: has to come out `ok`
    below       the reference computed in the nearest precision below the stated one (parameters, norms, rotation,
                router and logits in bf16) in the program's place: has to come out not `ok`, by one limit
    block8, nonstrict, dropped
                the program under a planted fault of the mask: blocks of 8 for 4; a noised query that also sees the
                clean copy of its own block (the strict quadrant read as the other); one tile pair of the noised
                copy's rows on the clean copy's keys dropped from both kernels' schedules: each not `ok`

Parameters as the cell makes them (seeded, the routers tiled), no optimizer state; tokens uniform from the seed.

    chiprun --chips 1 --timeout 3000 -- python3 tools/sdar_readings.py --sides system,below,block8,nonstrict,dropped 3141592653 2718281828
    python3 tools/sdar_readings.py --config sdar-nano --sides ... 1 2     # here, on the CPU
"""
import argparse
import contextlib
import importlib
import json
import sys
from typing import NamedTuple

sys.path.insert(0, ".")

flash = importlib.import_module("ray_tpu.ops.flash_attention")
BlockDiffusion = flash.BlockDiffusion  # the true one, whatever stands under its name
READINGS = ("ce_abs_err_mean_stated", "qk_grad_rel_dist_stated", "ce_abs_err_mean", "ce_abs_err_max", "loss_abs_err",
            "grad_norm_rel_err", "qk_grad_norm_rel_err", "expert_choices_flipped_share", "loss_reference",
            "over_limit", "draw_faults", "ok")


class Planted(NamedTuple):
    """`BlockDiffusion(seq, block)` with one fault; a type of its own, so that no trace of the true mask is taken for it."""

    seq: int
    block: int
    fault: str

    @property
    def true(self):
        return BlockDiffusion(self.seq, 8 if self.fault == "block8" else self.block)

    def kept(self, rows, cols):
        kept = self.true.kept(rows, cols)
        if self.fault == "nonstrict":  # a noised query sees the clean copy of its own block too
            own = (rows - self.seq) // self.block == cols // self.block
            kept = kept | ((rows >= self.seq) & (cols < self.seq) & own)
        return kept

    def tile_class(self, r0, r1, c0, c1):
        row, col = self.seq + self.seq // 2 + 1, 1  # a noised query in the row's middle, on the first clean keys
        if self.fault == "dropped" and r0 <= row < r1 and c0 <= col < c1:
            return flash.EMPTY
        return self.true.tile_class(r0, r1, c0, c1)

    def dense(self, queries, keys):
        return BlockDiffusion.dense(self, queries, keys)


@contextlib.contextmanager
def planted(fault):
    """The model's mask with `fault` while the block runs (`models/sdar.py _parts` asks this module for it)."""
    flash.BlockDiffusion = lambda seq, block: Planted(seq, block, fault)
    try:
        yield
    finally:
        flash.BlockDiffusion = BlockDiffusion


def light_system(bench, c, seed):
    """`bench.System` without optimizer and step: the parameters as the cell starts from them."""
    import jax

    from ray_tpu.models import sdar as program
    from ray_tpu.models.training import TrainState

    system = bench.System.__new__(bench.System)
    system.c, system.mesh, system.cfg = c, None, bench.model_config(c)
    params = jax.jit(lambda key: program.init_params(system.cfg, key))(jax.random.PRNGKey(seed))
    system.state = TrainState(params=params, opt_state=(), step=0)  # no compute copy: `check` differentiates at `params`
    if c.get("router_init_tiles", 1) > 1:
        system.state = bench.tile_routers(system.state, c["router_init_tiles"])
    return system


def read(bench, system, tokens, side):
    """`check`'s summary with `side` in the program's place."""
    if side == "system":
        return bench.check(system, tokens)
    if side == "below":
        return bench.check(system, tokens, program=bench.reference_program(system.c, "below"))
    with planted(side):  # `check` traces the program inside the call
        return bench.check(system, tokens, program=bench.system_program(system))


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument("--config", default="sdar-30b-a3b-chat-ep8")
    parser.add_argument("--sides", default="system,below")
    parser.add_argument("seeds", nargs="+", type=int)
    args = parser.parse_args()
    import jax.numpy as jnp
    import numpy as np

    from benchmark.harness.manifest import Manifest
    from benchmark.models import sdar as bench
    from ray_tpu._private.accelerators.jax_process import configure_compile_cache

    configure_compile_cache()  # `check` makes its programs anew each call: the references compile once
    c = Manifest().config(args.config)
    for seed in args.seeds:
        system = light_system(bench, c, seed % (1 << 31))
        tokens = jnp.asarray(np.random.default_rng(seed).integers(
            0, c["vocab_size"] - 1, (c["batch"]["global_rows"], c["batch"]["seq"] + 1), dtype=np.int32))
        for side in args.sides.split(","):
            out = read(bench, system, tokens, side)
            print("READING " + json.dumps({"seed": seed, "side": side, **{name: out[name] for name in READINGS}}),
                  flush=True)
        del system


if __name__ == "__main__":
    main()

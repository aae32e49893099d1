"""Headline benchmark: GPT-2 small training throughput/MFU THROUGH the framework.

Runs the workload twice on the local TPU chip:
  1. via ``JaxTrainer.fit()`` — a real 1-worker gang (worker actor, backend
     bring-up, session reporting): the number the framework is judged on;
  2. the identical bare-jax step loop in a clean subprocess: the native
     baseline, mirroring the reference's Ray-vs-native parity method
     (`doc/source/ray-air/benchmarks.rst:178-212` — framework overhead over
     native DDP must be within noise).

Runs on TPU chips only: a run that finds another device, or whose bare
baseline fails, is a failed run. Prints ONE JSON line {"metric", "value",
"unit", "vs_baseline", "device"} (plus diagnostic fields):
 - value: tokens/sec/chip for GPT-2 small (124M), batch 16 x seq 1024,
   measured THROUGH JaxTrainer.
 - vs_baseline: measured MFU / 0.40 — BASELINE.json north star is >=40% MFU.
 - overhead_pct: (bare - framework) / bare * 100, the parity diagnostic.
 - device: platform, device_kind and device count as jax reports them.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

B, S, WARMUP, ITERS, WINDOWS = 16, 1024, 5, 30, 2

# Peak dense bf16 FLOP/s of one chip, by jax `device_kind`. A device that is
# not here is an error, never a default.
PEAK_BF16_FLOPS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 per chip.
    "TPU v5 lite": 197e12,
}


def _timed_tokens_per_sec():
    """Build GPT-2 small on a DP mesh over all local devices, run the
    warmup+timed step loop, and return (tokens_per_sec_total, device) with
    device = {"platform", "kind", "count"} as jax reports it.

    This exact function body is the workload for BOTH the framework run
    (inside the Train worker) and the bare-jax subprocess, so the comparison
    isolates framework overhead from model/compile differences.
    """
    import time

    import jax
    import numpy as np

    from ray_tpu.models import (
        GPTConfig,
        create_train_state,
        default_optimizer,
        make_train_step,
        shard_batch,
    )
    from ray_tpu._private.accelerators import jax_process
    from ray_tpu.parallel import MeshSpec

    jax_process.configure_compile_cache()
    cfg = GPTConfig.gpt2_small()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        raise RuntimeError(
            f"bench.py measures TPU chips; jax came up on {device} "
            f"(JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})"
        )
    mesh = MeshSpec(data=len(devices)).build(devices)
    opt = default_optimizer(learning_rate=3e-4)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh)

    rng = np.random.default_rng(0)
    batch = shard_batch(
        {"tokens": rng.integers(0, cfg.vocab_size - 1, (B, S + 1)).astype(np.int32)},
        mesh,
    )
    for _ in range(WARMUP):
        state, m = step(state, batch)
    jax.block_until_ready(m)
    best_dt = None
    for _ in range(WINDOWS):
        t0 = time.time()
        for _ in range(ITERS):
            state, m = step(state, batch)
        jax.block_until_ready(m)
        dt = (time.time() - t0) / ITERS
        best_dt = dt if best_dt is None else min(best_dt, dt)
    return B * S / best_dt, device


def _train_loop(config):
    """The JaxTrainer per-worker loop: run the workload, report throughput."""
    from ray_tpu.air import session

    tps, device = _timed_tokens_per_sec()
    session.report({"tokens_per_sec": tps, "device": device})


def _framework_run():
    """tokens/s + device measured through JaxTrainer.fit(): one worker that
    owns every chip of this host, as the bare run does."""
    import ray_tpu
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    ray_tpu.init()
    try:
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        if not chips:
            raise RuntimeError("bench.py measures TPU chips; init() found none")
        trainer = JaxTrainer(
            _train_loop,
            scaling_config=ScalingConfig(
                num_workers=1, use_tpu=True, tpus_per_worker=chips
            ),
            run_config=RunConfig(name="bench_gpt2"),
        )
        result = trainer.fit()
        if result.error is not None:
            raise result.error
        return result.metrics["tokens_per_sec"], result.metrics["device"]
    finally:
        ray_tpu.shutdown()


def _bare_run():
    """The same workload in a clean subprocess (no framework on the path)."""
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--bare"],
        stdout=subprocess.PIPE,
        text=True,
        cwd=os.path.dirname(os.path.abspath(__file__)),
    )
    if proc.returncode != 0:
        raise RuntimeError(f"bare baseline subprocess failed rc={proc.returncode}")
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    return out["tokens_per_sec"], out["device"]


def main() -> None:
    from ray_tpu.models import GPTConfig, train_flops_per_token

    fw_tps, device = _framework_run()
    bare_tps, bare_device = _bare_run()
    if bare_device != device:
        raise RuntimeError(f"framework ran on {device}, bare run on {bare_device}")
    if device["kind"] not in PEAK_BF16_FLOPS:
        raise RuntimeError(
            f"no peak FLOP/s on record for device_kind {device['kind']!r}; "
            "add it to PEAK_BF16_FLOPS with its source"
        )
    n_dev = device["count"]

    cfg = GPTConfig.gpt2_small()
    mfu = train_flops_per_token(cfg, S) * fw_tps / (PEAK_BF16_FLOPS[device["kind"]] * n_dev)
    print(json.dumps({
        "metric": "gpt2_small_train_tokens_per_sec_per_chip_via_JaxTrainer",
        "value": round(fw_tps / n_dev, 1),
        "unit": "tokens/s/chip",
        "vs_baseline": round(mfu / 0.40, 3),
        "device": device,
        "bare_tokens_per_sec_per_chip": round(bare_tps / n_dev, 1),
        "overhead_pct": round((bare_tps - fw_tps) / bare_tps * 100, 2),
    }))


if __name__ == "__main__":
    if "--bare" in sys.argv:
        tps, device = _timed_tokens_per_sec()
        print(json.dumps({"tokens_per_sec": tps, "device": device}))
    else:
        main()

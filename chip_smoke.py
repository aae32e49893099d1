"""Does the system still start on the chip?

Drives the main path once, through the entry points a user calls:
`ray_tpu.init()` -> `JaxTrainer(loop, ScalingConfig(use_tpu=True)).fit()` ->
`create_train_state` / `make_train_step` on `GPTConfig.gpt2_small()` (124M,
bf16, vocab 50,304, batch 16 x seq 1024, remat "save_attn", attention
"auto"), with random weights from a seed. This process never initialises a
jax backend: every device is opened by a worker actor the scheduler granted
chips to, so a chip is never held by two processes.

Phases (any failure, any phase on a non-TPU device, no chip: exit != 0):

  cold     a one-chip trainer: device facts, the Pallas kernels against
           `xla_attention` at the training shape, the model's loss through the
           kernel against its XLA-attention form, the compiled step's HLO
           holding the Mosaic calls, a few reported steps with finite, falling
           loss, compile seconds apart from step seconds, peak HBM;
  release  `ray_tpu.shutdown()` leaves no worker behind;
  warm     a second `init()` + trainer in this same process: a fresh worker
           gets the chip back, repeats the losses, and its compiles hit the
           persistent cache;
  on a four-chip host also
  one_x_four   one worker x four chips, `mesh={"data": 4}`;
  four_x_one   four workers x one chip joined by `jax.distributed`;
  replicas     four independent one-chip actors holding different chips at once;
           each with four global devices, a shard of the batch and live memory
           on every device, and the one-chip run's losses.

Last line of stdout on success, and only then:
  {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}

`--rehearse-cpu` walks the same phases at a toy size on the CPU backend to
debug the control flow. It says platform=cpu and never prints the line above.
"""

from __future__ import annotations

import json
import os
import shutil
import signal
import sys
import tempfile
import threading
import time

STEPS = 8
BATCH, SEQ = 16, 1024
# (batch, heads, seq, head_dim) a chip sees in benchmark/configs: gpt2-medium
# (8 rows x 16 heads) and gpt2-xl-fsdp4 (16 rows over 4 chips x 25 heads).
BENCHMARK_ATTENTION_SHAPES = ((8, 16, 1024, 64), (4, 25, 1024, 64))
PHASE_TIMEOUT_S = 420.0


# --------------------------------------------------------------------------
# Worker side. Runs inside the train worker actor; everything is imported
# there, the parent never touches jax.
# --------------------------------------------------------------------------
def train_loop(config):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import multihost_utils

    from ray_tpu.air import session
    from ray_tpu.models import (
        GPTConfig, create_train_state, default_optimizer, loss_fn,
        make_train_step, shard_batch,
    )
    from ray_tpu.ops.flash_attention import (
        flash_attention, kernel_plan, select_backend, xla_attention,
    )

    rehearse = config["rehearse"]
    if rehearse:
        # Toy compiles finish under jax's 1 s caching threshold.
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    # jax's own account of compiling: seconds inside the backend compiler
    # (retrieval included on a hit) and persistent-cache hits and misses.
    compiles = {"seconds": 0.0, "count": 0, "hits": 0, "misses": 0}

    def on_duration(event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["seconds"] += seconds
            compiles["count"] += 1

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            compiles["hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            compiles["misses"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)

    def check(ok, what):
        if not ok:
            raise AssertionError(what)

    dev = jax.local_devices()[0]
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "local": jax.local_device_count(),
        "count": jax.device_count(),
        "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
        "cache_dir": jax.config.jax_compilation_cache_dir,
    }
    print(f"chip_smoke worker: platform={device['platform']} {device}", flush=True)
    check(rehearse or device["platform"] == "tpu", f"not on a TPU: {device}")
    if not rehearse:
        check(device["local"] == config["local_devices"]
              and device["count"] == config["global_devices"],
              f"wanted {config['local_devices']} local / "
              f"{config['global_devices']} global devices: {device}")

    cfg = GPTConfig.nano() if rehearse else GPTConfig.gpt2_small()
    batch_size, seq = (8, 64) if rehearse else (BATCH, SEQ)
    mesh = session.get_mesh()
    out = {"device": device, "mesh": {k: int(v) for k, v in mesh.shape.items() if v > 1}}

    # ---- the kernels against the reference, at the training shape and at the
    # per-chip attention shapes of both benchmark configurations
    qshape = (batch_size, cfg.n_head, seq, cfg.head_dim)
    out["attention_path"] = select_backend(qshape, dev.platform)
    if config["check_kernel"] and not rehearse:
        check(out["attention_path"] == "pallas",
              f"attention path for {qshape} is {out['attention_path']!r}, not the kernel")

        def grads_of(attn):
            def f(q, k, v, do):
                o = attn(q, k, v)
                return (o.astype(jnp.float32) * do.astype(jnp.float32)).sum(), o
            return jax.jit(jax.grad(f, argnums=(0, 1, 2), has_aux=True))

        kernel = grads_of(lambda q, k, v: flash_attention(q, k, v, causal=True, backend="pallas"))
        reference = grads_of(lambda q, k, v: xla_attention(q, k, v, causal=True))
        out["kernel_plan"], out["kernel_vs_xla_max_abs_err"] = {}, {}
        for shape in (qshape, *BENCHMARK_ATTENTION_SHAPES):
            label = "x".join(map(str, shape))
            out["kernel_plan"][label] = kernel_plan(shape, causal=True)._asdict()
            keys = jax.random.split(jax.random.PRNGKey(1), 4)
            q, k, v, do = (
                jax.random.normal(kk, shape, jnp.float32).astype(cfg.dtype) for kk in keys
            )
            (dq, dk, dv), o = kernel(q, k, v, do)
            (rq, rk, rv), ro = reference(q, k, v, do)
            errs = out["kernel_vs_xla_max_abs_err"][label] = {}
            for name, got, ref in (("o", o, ro), ("dq", dq, rq), ("dk", dk, rk), ("dv", dv, rv)):
                got, ref = np.asarray(got, np.float32), np.asarray(ref, np.float32)
                check(np.isfinite(got).all(), f"kernel {name} not finite at {shape}")
                err, scale = float(np.abs(got - ref).max()), float(np.abs(ref).max())
                errs[name] = [err, scale]
                # bf16 keeps 8 bits: two roundings of values up to `scale`.
                check(err <= 2.0 ** -6 * scale,
                      f"kernel {name} at {shape} off by {err} (max |ref| {scale})")

    # ---- the step
    opt = default_optimizer(learning_rate=3e-4)
    state = create_train_state(cfg, jax.random.PRNGKey(0), opt, mesh=mesh)
    step = make_train_step(cfg, opt, mesh=mesh)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, cfg.vocab_size - 1, (batch_size, seq + 1)).astype(np.int32)
    batch = shard_batch({"tokens": tokens}, mesh)  # same seeded global batch everywhere

    if config["check_kernel"]:
        # The model through the kernel against the model through XLA
        # attention: same weights, two rows of the batch.
        import dataclasses

        small = {"tokens": jnp.asarray(tokens[:2])}
        host_params = jax.device_get(state.params)
        ref_cfg = dataclasses.replace(cfg, attention="xla")
        got = float(jax.jit(lambda p, b: loss_fn(p, b, cfg))(host_params, small))
        ref = float(jax.jit(lambda p, b: loss_fn(p, b, ref_cfg))(host_params, small))
        out["loss_kernel_vs_xla_attention"] = [got, ref]
        check(np.isfinite(got) and abs(got - ref) <= 2e-2, f"loss {got} vs reference {ref}")

    before = dict(compiles)
    t0 = time.perf_counter()
    compiled = step.lower(state, batch).compile()
    out["step_compile_s"] = time.perf_counter() - t0
    out["step_compile_cache"] = {k: compiles[k] - before[k] for k in ("hits", "misses")}
    hlo = compiled.as_text()
    out["mosaic_calls_in_step"] = hlo.count("tpu_custom_call")
    mem = compiled.memory_analysis()
    out["step_memory_analysis_bytes"] = {
        "arguments": mem.argument_size_in_bytes, "temp": mem.temp_size_in_bytes,
    }
    check(rehearse or out["mosaic_calls_in_step"] >= 2,
          f"{out['mosaic_calls_in_step']} Mosaic calls in the compiled step: the kernel was bypassed")

    shards = batch["tokens"].addressable_shards
    check(len(shards) == jax.local_device_count()
          and len({s.device for s in shards}) == len(shards)
          and all(s.data.shape == (batch_size // jax.device_count(), seq + 1) for s in shards),
          f"batch not sharded one block per device: {[(s.device, s.data.shape) for s in shards]}")

    losses, step_s, compile_in_step = [], [], []
    for i in range(config["steps"]):
        c0 = compiles["seconds"]
        t0 = time.perf_counter()
        state, metrics = step(state, batch)
        jax.block_until_ready(metrics)  # synchronises on this machine (PERF.md)
        step_s.append(time.perf_counter() - t0)
        compile_in_step.append(compiles["seconds"] - c0)
        losses.append(float(metrics["loss"]))
        check(np.isfinite(losses[-1]), f"loss not finite at step {i}: {losses}")
        session.report({"step": i, "loss": losses[-1], "step_s": step_s[-1]})
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    # The jitted step compiles for the fresh state and again for the state it
    # returned (committed shardings); nothing may compile after that.
    check(not any(compile_in_step[2:]), f"compiled in steady state: {compile_in_step}")
    out.update(losses=losses, step_s=step_s, compile_in_step_s=compile_in_step,
               compile_s_total=compiles["seconds"], compiles=compiles["count"],
               cache_hits=compiles["hits"], cache_misses=compiles["misses"])

    # ---- every device: its block of the batch, live memory
    stats = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
    mine = []
    for d, s in zip(jax.local_devices(), shards):
        ms = d.memory_stats() or {}
        # MiB: the gather below is 32-bit (jax without x64).
        mine.append([d.id, s.data.shape[0]] + [ms.get(k, 0) >> 20 for k in stats])
    per_device = np.asarray(mine, np.int32)
    if jax.process_count() > 1:
        per_device = np.asarray(
            multihost_utils.process_allgather(per_device)).reshape(-1, per_device.shape[1])
    names = ("id", "batch_rows") + tuple(k.replace("bytes", "mib") for k in stats)
    out["per_device"] = [dict(zip(names, map(int, r))) for r in per_device]
    check(len({r["id"] for r in out["per_device"]}) == jax.device_count(), out["per_device"])
    check(rehearse or all(r["mib_in_use"] > 0 for r in out["per_device"]),
          f"a device holds nothing: {out['per_device']}")
    session.report({"step": config["steps"], "loss": losses[-1], "summary": out})


class Replica:
    """An independent one-chip process (what a Serve replica is to the
    scheduler): opens its chip, works on it, says which one it was."""

    def probe(self):
        import jax
        import jax.numpy as jnp

        x = jnp.ones((2048, 2048), jnp.bfloat16)
        y = float((x @ x).sum())
        dev = jax.local_devices()[0]
        return {"platform": dev.platform, "local": jax.local_device_count(),
                "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"),
                "ok": y == 2048.0 ** 3, "pid": os.getpid()}


# --------------------------------------------------------------------------
# Parent side.
# --------------------------------------------------------------------------
class Failed(Exception):
    pass


class Watchdog:
    """A phase that hangs (a worker waiting for a chip, a gang that never
    joins) must not sit on the chip until someone else's time limit."""

    def __init__(self):
        self._timer = None

    def arm(self, phase, seconds=PHASE_TIMEOUT_S):
        self.disarm()

        def fire():
            print(f"CHIP_SMOKE FAILED: phase {phase!r} still running after "
                  f"{seconds:.0f}s; killing every process this run started", flush=True)
            for pid in descendants():
                try:
                    os.kill(pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
            os._exit(1)

        self._timer = threading.Timer(seconds, fire)
        self._timer.daemon = True
        self._timer.start()

    def disarm(self):
        if self._timer is not None:
            self._timer.cancel()


def descendants():
    """Live processes this one started, children of children included
    (zombies have no command line and are left out)."""
    parent_of = {}
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as fh:
                ppid = int(fh.read().rsplit(")", 1)[1].split()[1])
            with open(f"/proc/{pid}/cmdline", "rb") as fh:
                if fh.read():
                    parent_of[int(pid)] = ppid
        except (OSError, ValueError, IndexError):
            continue
    found, frontier = [], {os.getpid()}
    while frontier:
        frontier = {p for p, pp in parent_of.items() if pp in frontier and p not in found}
        found.extend(frontier)
    return found


def run_trainer(name, rehearse, storage, *, num_workers=1, tpus_per_worker=None,
                mesh=None, check_kernel=False):
    from ray_tpu.air import RunConfig, ScalingConfig
    from ray_tpu.train.jax import JaxTrainer

    chips = tpus_per_worker or 1
    trainer = JaxTrainer(
        train_loop,
        train_loop_config={
            "rehearse": rehearse, "steps": STEPS, "check_kernel": check_kernel,
            "local_devices": chips, "global_devices": chips * num_workers,
        },
        scaling_config=ScalingConfig(
            num_workers=num_workers, use_tpu=not rehearse,
            tpus_per_worker=tpus_per_worker, mesh=mesh,
        ),
        run_config=RunConfig(name=f"chip_smoke_{name}", storage_path=storage),
    )
    t0 = time.time()
    result = trainer.fit()  # raises TrainingFailedError with the worker's reason
    metrics = result.metrics or {}
    if metrics.get("step") != STEPS or "summary" not in metrics:
        raise Failed(f"{name}: the worker did not report {STEPS} steps and a summary: {metrics}")
    s = metrics["summary"]
    steady = sorted(s["step_s"][2:])
    print(f"[{name}] platform={s['device']['platform']} kind={s['device']['kind']!r} "
          f"local={s['device']['local']} global={s['device']['count']} mesh={s['mesh']} "
          f"visible_chips={s['device']['visible_chips']} wall={time.time() - t0:.1f}s")
    print(f"[{name}] attention path {s['attention_path']!r}; Mosaic calls in the compiled "
          f"step: {s['mosaic_calls_in_step']}; kernel vs xla_attention [max abs err, max |ref|]: "
          f"{s.get('kernel_vs_xla_max_abs_err')}; loss kernel/xla-attention: "
          f"{s.get('loss_kernel_vs_xla_attention')}")
    for shape, plan in (s.get("kernel_plan") or {}).items():
        print(f"[{name}] kernel_plan {shape}: {plan}")
    print(f"[{name}] compile: step {s['step_compile_s']:.2f}s ({s['step_compile_cache']}), "
          f"all {s['compiles']} compiles {s['compile_s_total']:.2f}s, cache hits "
          f"{s['cache_hits']} misses {s['cache_misses']}, cache at {s['device']['cache_dir']}")
    print(f"[{name}] steps: wall {[round(x, 4) for x in s['step_s']]} of which compile "
          f"{[round(x, 2) for x in s['compile_in_step_s']]}; steady median "
          f"{steady[len(steady) // 2]:.4f}s (a smoke observation, not a benchmark)")
    print(f"[{name}] losses {[round(x, 4) for x in s['losses']]}")
    print(f"[{name}] per device {s['per_device']}", flush=True)
    return s


def same_losses(name, got, want):
    """bf16 activations, another reduction order: the loss before any update
    must agree closely, the few steps after it loosely."""
    diffs = [abs(a - b) for a, b in zip(got["losses"], want["losses"])]
    print(f"[{name}] |loss - one-chip loss| per step: {[round(d, 4) for d in diffs]}")
    if diffs[0] > 2e-2 or max(diffs) > 0.25:
        raise Failed(f"{name}: losses {got['losses']} differ from the one-chip run {want['losses']}")


def main(argv):
    rehearse = "--rehearse-cpu" in argv
    if [a for a in argv if a != "--rehearse-cpu"]:
        raise Failed(f"unknown arguments {argv}; usage: chip_smoke.py [--rehearse-cpu]")
    # A caller's time limit arrives as SIGTERM: leave through the finally
    # below, so that no worker outlives this process holding a chip.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
    try:
        import ray_tpu
        from ray_tpu._private.accelerators import tpu as tpu_accel
    except ImportError as e:
        raise Failed(f"the ray_tpu package is not importable from {os.getcwd()}: {e}")

    watchdog = Watchdog()
    storage = tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        # ------------------------------------------------------------ cold
        ray_tpu.init(num_tpus=0 if rehearse else None)
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        print(f"chip_smoke: {'REHEARSAL platform=cpu' if rehearse else 'chip run'}; "
              f"init() found {chips} TPU chip(s) ({tpu_accel.detection_report()})", flush=True)
        if not rehearse and not chips:
            raise Failed("no TPU chip on this host: " + tpu_accel.detection_report())
        watchdog.arm("cold")
        cold = run_trainer("cold", rehearse, storage, check_kernel=True)

        # --------------------------------------------------------- release
        watchdog.arm("release", 120)
        t0 = time.time()
        ray_tpu.shutdown()
        left = descendants()
        print(f"[release] shutdown() took {time.time() - t0:.2f}s; processes left: {left}", flush=True)
        if left:
            raise Failed(f"shutdown() returned with processes of this run alive: {left}")

        # ------------------------------------------------------------ warm
        watchdog.arm("warm")
        ray_tpu.init(num_tpus=0 if rehearse else None)
        warm = run_trainer("warm", rehearse, storage)
        same_losses("warm", warm, cold)
        print(f"[warm] step compile {warm['step_compile_s']:.2f}s against "
              f"{cold['step_compile_s']:.2f}s cold; every compile "
              f"{warm['compile_s_total']:.2f}s against {cold['compile_s_total']:.2f}s", flush=True)
        if warm["step_compile_cache"] != {"hits": 1, "misses": 0}:
            raise Failed(f"the second trainer's step compile missed the cache: {warm['step_compile_cache']}")
        # Where the cache directory outlives the machine the first trainer
        # hits too, and there is nothing to be faster than.
        if (not rehearse and cold["step_compile_cache"]["misses"]
                and warm["step_compile_s"] > 0.5 * cold["step_compile_s"]):
            raise Failed("the second trainer's compile hit the cache and was not faster than the first's")
        widest = warm

        # ------------------------------------------------- four-chip host
        if chips == 4 or rehearse:
            watchdog.arm("one_x_four")
            if rehearse:
                # Workers inherit this: one worker with four devices of its own.
                os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
            try:
                a = run_trainer("one_x_four", rehearse, storage, tpus_per_worker=4, mesh={"data": 4})
            finally:
                if rehearse:
                    del os.environ["XLA_FLAGS"]
            same_losses("one_x_four", a, cold)

            watchdog.arm("four_x_one")
            b = run_trainer("four_x_one", rehearse, storage, num_workers=4)
            same_losses("four_x_one", b, cold)
            widest = a

            watchdog.arm("replicas", 180)
            cls = ray_tpu.remote(Replica)
            replicas = [cls.options(num_tpus=None if rehearse else 1).remote() for _ in range(4)]
            probes = ray_tpu.get([r.probe.remote() for r in replicas], timeout=150)
            print(f"[replicas] {probes}", flush=True)
            held = sorted(p["visible_chips"] for p in probes if p["visible_chips"])
            if not all(p["ok"] and p["local"] == 1 for p in probes) or (
                not rehearse and (held != ["0", "1", "2", "3"]
                                  or any(p["platform"] != "tpu" for p in probes))
            ):
                raise Failed(f"four independent one-chip actors did not each hold their own chip: {probes}")
    finally:
        watchdog.arm("final shutdown", 120)
        try:
            ray_tpu.shutdown()
        finally:
            watchdog.disarm()
            shutil.rmtree(storage, ignore_errors=True)

    if rehearse:
        print("chip_smoke: REHEARSAL platform=cpu finished; this says nothing about the chip")
        return
    device = widest["device"]
    sys.stdout.flush()
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"], "count": device["count"]}}))


if __name__ == "__main__":
    try:
        main(sys.argv[1:])
    except BaseException as e:  # noqa: BLE001 — the one exit: say why, then fail
        if isinstance(e, SystemExit) and not e.code:
            raise
        print(f"CHIP_SMOKE FAILED: {type(e).__name__}: {e}", flush=True)
        sys.exit(1)

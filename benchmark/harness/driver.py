"""The parent of one run: one cell, one seed, one `JaxTrainer.fit()`, one
line. It never imports jax: a chip belongs to the worker that was granted it."""

from __future__ import annotations

import argparse
import importlib
import json
import os
import shutil
import signal
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

from benchmark.harness import bringup, procs
from benchmark.harness.manifest import Manifest

RUN_TIMEOUT_S = 1100.0  # a cold first run may take 1200 s; a hang must not
REHEARSAL_TIMEOUT_S = 240.0


class Failed(Exception):
    """No result: the run could not be made (no chip, a worker died)."""


def parse(argv: List[str]) -> argparse.Namespace:
    p = argparse.ArgumentParser(prog="benchmark/run.py")
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="nano configuration on the CPU backend: control flow only, "
                        "says platform=cpu, never a metric under a device's name")
    return p.parse_args(argv)


def run(argv: List[str], t_start: float, manifest: Manifest) -> Dict[str, Any]:
    args = parse(argv)
    cell = manifest.cell(args.workload)
    rehearse = args.rehearse_cpu
    config = manifest.config(cell["config"])
    # A rehearsal keeps the cell's layout and takes its sizes from the toy the
    # configuration names, where it names one.
    model_config = (manifest.config(config["rehearse_with"])
                    if rehearse and "rehearse_with" in config else config)
    mix = manifest.traffic(cell["traffic"])
    seconds = args.seconds if args.seconds is not None else float(manifest.data["run_seconds"])
    loop = importlib.import_module("benchmark.loops." + mix["loop"])
    layout = config["layout"]
    workers, per_worker = layout["num_workers"], layout["tpus_per_worker"]
    out_dir = os.path.join(manifest.dir, "out")
    os.makedirs(out_dir, exist_ok=True)

    # A caller's time limit arrives as SIGTERM: leave through the finally
    # below, so that no worker outlives this process holding a chip.
    signal.signal(signal.SIGTERM, lambda *_: sys.exit(143))
    if rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={per_worker}"
    try:
        import ray_tpu
        from ray_tpu._private.accelerators import tpu as tpu_accel
        from ray_tpu.air import RunConfig, ScalingConfig
        from ray_tpu.train.jax import JaxTrainer
    except ImportError as e:
        raise Failed(f"the ray_tpu package is not importable from {os.getcwd()}: {e}")

    watchdog = procs.Watchdog()
    sampler = procs.CpuSampler() if args.trace else None
    storage = tempfile.mkdtemp(prefix="benchmark_run_")
    summary: Optional[Dict[str, Any]] = None
    try:
        watchdog.arm("run", REHEARSAL_TIMEOUT_S if rehearse else RUN_TIMEOUT_S)
        ray_tpu.init(num_tpus=0 if rehearse else None)
        chips = int(ray_tpu.cluster_resources().get("TPU", 0))
        print(f"benchmark: {'REHEARSAL platform=cpu' if rehearse else 'chip run'} of "
              f"{cell['name']} seed {args.seed}, {seconds:g}s, trace {args.trace}; init() found "
              f"{chips} TPU chip(s) ({tpu_accel.detection_report()})", flush=True)
        if not rehearse and chips < cell["chips"]:
            raise Failed(f"{cell['name']} needs {cell['chips']} TPU chip(s), this host has "
                         f"{chips}: {tpu_accel.detection_report()}")
        ctx = {"cell": cell["name"], "chips": cell["chips"], "seed": args.seed,
               "seconds": seconds, "traffic": mix, "model_config": model_config}
        t = time.time()
        datasets = loop.prepare(ctx)
        prepare_s = time.time() - t
        trainer = JaxTrainer(
            loop.train_loop,
            train_loop_config={
                **ctx, "trace": bool(args.trace), "rehearse": rehearse, "out_dir": out_dir,
                "devices": {"local": per_worker, "global": per_worker * workers},
            },
            scaling_config=ScalingConfig(
                num_workers=workers, use_tpu=not rehearse,
                tpus_per_worker=None if rehearse else per_worker, mesh=layout["mesh"]),
            run_config=RunConfig(name=f"benchmark_{cell['name']}", storage_path=storage),
            datasets=datasets,
        )
        if sampler is not None:
            sampler.start()
        t_fit = time.time()
        result = trainer.fit()  # raises TrainingFailedError with the worker's reason
        summary = (result.metrics or {}).get("summary")
        if not summary or "window_s" not in summary:
            raise Failed(f"rank 0 sent no summary: {result.metrics}")
    finally:
        if sampler is not None:
            sampler.stop()
        watchdog.arm("shutdown", 120)
        try:
            ray_tpu.shutdown()
        finally:
            left = procs.descendants()
            procs.kill_descendants()
            watchdog.disarm()
            shutil.rmtree(storage, ignore_errors=True)
    if left:
        raise Failed(f"shutdown() returned with processes of this run alive: {left}")

    record = {
        "cell": cell, "seed": args.seed, "seconds": seconds, "trace": args.trace,
        "rehearse": rehearse, "config": model_config, "traffic": mix, "chips": cell["chips"],
        "parent": {"t_start_wall": t_start, "t_fit_wall": t_fit, "prepare_s": prepare_s},
        "summary": summary,
    }
    if sampler is not None:
        w0 = summary["window_wall_start"]
        record["parent"]["cpu_s_in_window"] = sampler.cpu_seconds_between(
            w0, w0 + summary["window_s"])
    return record


def set_up(run: Dict[str, Any]) -> Dict[str, float]:
    """The three numbers of a run's set-up. `whole_s`: process start to the
    first timed step, which is how long the chips were held before they
    trained. `chip_open_s`: the seconds the slowest rank spent in the
    program's `device_touch` span, the first `jax.local_devices()`, where
    libtpu opens the chip: one call that no change to this repository moves
    and that differs by 7-16 s between two runs of one tree. `setup_s`: the
    first less the second, everything a change can move and nothing it
    cannot. One name never holds two quantities: a chip run with no such span
    has no `setup_s`. A rehearsal takes out what there is: a lone CPU worker
    opens no span."""
    whole = run["summary"]["window_wall_start"] - run["parent"]["t_start_wall"]
    program = bringup.of(run)
    opened = program.chip_open_s if program is not None else None
    if opened is None:
        if not run["rehearse"]:
            raise Failed(f"no rank's `{bringup.CHIP_OPEN_SPAN}` span is in the report this process kept of the "
                         f"run's fit(): `setup_s` is process start -> first timed step ({whole:.2f}s) "
                         f"less that span, and stands for nothing else")
        opened = 0.0
    return {"whole_s": whole, "chip_open_s": opened, "setup_s": whole - opened}


def compared(s: Dict[str, Any], rehearse: bool) -> Dict[str, Any]:
    """Every number `correct` is decided from, beside its limit, as `[reading,
    limit]`: the reference check's (a limit named as its reading stands beside
    it, leaf by leaf where it is one a leaf; what is left of either side
    follows as it is), then the window's own."""
    check = dict(s["check"])
    limits = dict(check.pop("limits", {}))
    out: Dict[str, Any] = {}
    for name in [n for n in limits if n in check]:
        reading, limit = check.pop(name), limits.pop(name)
        if isinstance(reading, dict):
            per_leaf = limit if isinstance(limit, dict) else dict.fromkeys(reading, limit)
            out.update({f"{name}.{leaf}": [value, per_leaf.get(leaf)] for leaf, value in reading.items()})
        else:
            out[name] = [reading, limit]
    out["check"] = check
    if limits:
        out["limits"] = limits
    out["steps_completed_of_dispatched"] = [s["completed"], s["attempted"]]
    out["steps_outside_loss_band"] = [s["failed"], 0]
    out["compiles_in_window"] = [s["compiles_in_window"]["count"], 0]
    out["mosaic_calls_at_least"] = [s["compiled_step"]["mosaic_calls"], 0 if rehearse else 2]
    return out


def result_line(record: Dict[str, Any], manifest: Manifest) -> Dict[str, Any]:
    """The contract's last line from the run's record, its set-up read."""
    from benchmark.harness import xplane
    from benchmark.harness.peaks import peaks_for

    s, cell, rehearse = record["summary"], record["cell"], record["rehearse"]
    dev = s["device"]
    trace = None
    if s.get("trace_table"):
        with open(s["trace_table"]) as fh:
            trace = xplane.Trace(json.load(fh))
    run = dict(record, device_trace=trace, peaks=None if rehearse else peaks_for(dev["kind"]))
    chips = dev["count"]
    values: Dict[str, Any] = {}
    if record["trace"]:
        readers = manifest.layer_readers()
        for entry in manifest.metrics_for(cell["name"], "per_layer"):
            value = readers[entry["name"]].read(run)
            if value is not None:
                values[entry["name"]] = {"value": value, "unit": entry["unit"]}
    else:
        mine = {
            "tokens_per_s_per_chip": s["tokens_per_s"] / chips,
            "setup_s": record["setup"]["setup_s"],
        }
        for entry in manifest.metrics_for(cell["name"], "end_to_end"):
            values[entry["name"]] = {"value": mine[entry["name"]], "unit": entry["unit"]}
    correct = bool(
        s["check"].get("ok") and s["failed"] == 0 and s["completed"] > 0
        and s["completed"] == s["attempted"]
        and s["compiles_in_window"]["count"] == 0
        and (rehearse or s["compiled_step"]["mosaic_calls"] >= 2)
        and (not record["trace"] or trace is not None))
    peak = max([s["compiled_step"]["step_bytes"]]
               + [d["peak_bytes_in_use"] << 20 for d in s["per_device_mib"]])
    device = {"platform": dev["platform"], "kind": dev["kind"], "count": chips,
              "memory_peak_bytes": peak}
    line = {"correct": correct, "attempted": s["attempted"], "failed": s["failed"],
            "metrics": values, "device": device}
    if rehearse:
        # A CPU number never stands under a device metric's name.
        line["metrics"] = {"rehearsal." + k: v for k, v in values.items()}
    if trace is not None:
        pairs = [p for p in s["traced_busy_window_s"] if p[1] > 0]
        device["busy_s"] = sum(p[0] for p in pairs) / len(pairs)
        device["window_s"] = sum(p[1] for p in pairs) / len(pairs)
        line["breakdown"] = {
            "device_ops": [[n, sec] for n, sec in trace.top_ops(10)],
            "idle_gaps": [[n, sec] for n, sec in trace.idle_by_host_span()[:10]],
        }
    # Last: the record of a run that is not correct keeps the end of the line.
    line["compared"] = dict(compared(s, rehearse), trace_read_if_asked=[trace is not None, bool(record["trace"])])
    return line


def describe(record: Dict[str, Any]) -> None:
    """The lines before the last: what a reader of a log wants beside the numbers."""
    s = record["summary"]
    p = record["parent"]
    steps = s["completed"]
    print(f"[run] device {s['device']} mesh {s['mesh']}")
    print(f"[run] set-up: process start -> first timed step {record['setup']['whole_s']:.2f}s less the "
          f"chip's open {record['setup']['chip_open_s']:.2f}s = setup_s {record['setup']['setup_s']:.2f}s; "
          f"process start -> fit() {p['t_fit_wall'] - p['t_start_wall']:.2f}s "
          f"(of which traffic {p['prepare_s']:.2f}s), fit() -> loop entered "
          f"{s['t_loop_wall'] - p['t_fit_wall']:.2f}s, in the loop {json.dumps(s['setup_spans_s'])}; "
          f"compiles in set-up {json.dumps(s['compiles_setup'])}, in the window "
          f"{json.dumps(s['compiles_in_window'])}")
    print(f"[run] check {json.dumps(s['check'])}")
    print(f"[run] compiled step {json.dumps(s['compiled_step'])}; per device MiB {s['per_device_mib']}")
    if steps:
        gaps = [b - a for a, b in zip(s["completed_at_s"], s["completed_at_s"][1:])]
        gaps.sort()
        print(f"[run] window {s['window_s']:.3f}s, {s['attempted']} dispatched, {steps} completed, "
              f"{s['failed']} failed; tokens/s {s['tokens_per_s']:.1f} by the median step at each of "
              f"{len(s['step_medians_s'])} positions, {s['window_tokens_per_s']:.1f} over the whole window "
              f"(stalls {100 * (s['stall_share'] or 0):.2f}% of it); between completions median "
              f"{gaps[len(gaps) // 2] if gaps else float('nan'):.4f}s max "
              f"{gaps[-1] if gaps else float('nan'):.4f}s; loss first {s['losses'][0]:.4f} last "
              f"{s['losses'][-1]:.4f}")
    print(f"[run] host spans ms/step (median) {json.dumps(s['span_ms_per_step'])}; totals s "
          f"{json.dumps(s['span_total_s'])}", flush=True)


def main(argv: List[str], t_start: float) -> int:
    try:
        manifest = Manifest()
        record = run(argv, t_start, manifest)
        # What the readers are handed: the record, and beside it what is read
        # once for all of them (the report's `Bringup` first, traced or not).
        readings = dict(record)
        record["setup"] = readings["setup"] = set_up(readings)
        describe(record)
        line = result_line(readings, manifest)
        if record["rehearse"]:
            from benchmark.harness import rehearsal_names  # goes with three tests outside `paths`
            rehearsal_names.add_names_before_the_fold(line, manifest, record["cell"])
    except BaseException as e:  # noqa: BLE001 - the one exit: say why, then fail with no result
        if isinstance(e, SystemExit) and not e.code:
            raise
        print(f"BENCHMARK FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    out = os.path.join(manifest.dir, "out", f"{record['cell']['name']}.{record['seed']}.json")
    with open(out, "w") as fh:
        json.dump({**record, "line": line}, fh)
    sys.stdout.flush()
    print("compared, [reading, limit]: " + json.dumps(line["compared"]), file=sys.stderr, flush=True)
    print(json.dumps(line))
    return 0

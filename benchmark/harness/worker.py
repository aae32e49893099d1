"""What every loop file does around its loop, inside the train worker: find
the device, count compiles, build the system, place batches, check it against
the reference, look into the compiled step, run the profiler, and hand the
parent one summary. Only loop files import this module, inside the worker:
the parent never touches jax."""

from __future__ import annotations

import contextlib
import glob
import importlib
import json
import os
import shutil
import time
from typing import Any, Dict, List, Optional

import jax
import numpy as np

from benchmark.harness import xplane
from benchmark.harness.clock import WindowClock

WARMUP_STEPS = 3  # the step compiles for the fresh state and again for the state it returned


class Tracer:
    """The jax profiler for `TRACE_STEPS` steps, and the annotations that put
    the loop's spans into its trace."""

    def __init__(self, directory: str):
        self.directory = directory
        self.ran = False

    def start(self):
        shutil.rmtree(self.directory, ignore_errors=True)
        # No Python tracer: it slows the very host path whose gaps the trace
        # is there to show (with it on, the fed loop's idle share doubled).
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.directory, profiler_options=options)

    def stop(self):
        jax.profiler.stop_trace()
        self.ran = True

    def step_annotation(self, index: int):
        return jax.profiler.StepTraceAnnotation(xplane.STEP_ANNOTATION, step_num=index)

    def annotation(self, name: str):
        return jax.profiler.TraceAnnotation(name)

    def table(self) -> Optional[Dict]:
        paths = glob.glob(os.path.join(self.directory, "**", "*.xplane.pb"), recursive=True)
        return xplane.extract(paths[0]) if self.ran and paths else None


class WorkerRun:
    def __init__(self, config: Dict[str, Any]):
        from ray_tpu._private.accelerators import jax_process
        from ray_tpu.air import session

        self.t_loop = time.time()
        self.config = config
        self.model_config = config["model_config"]
        self.mix = config["traffic"]
        self.rehearse = config["rehearse"]
        self.setup_spans: Dict[str, float] = {}
        self.first_step_wall: Optional[float] = None
        if self.rehearse:
            # A rehearsal keeps out of the persistent cache: a CPU gang whose
            # ranks load cached executables hangs in its first collective.
            jax.config.update("jax_enable_compilation_cache", False)
        cache_dir = jax_process.configure_compile_cache()
        self.compiles = {"seconds": 0.0, "count": 0, "hits": 0, "misses": 0}
        self._listen()
        dev = jax.local_devices()[0]
        self.device = {
            "platform": dev.platform, "kind": dev.device_kind,
            "local": jax.local_device_count(), "count": jax.device_count(),
            "processes": jax.process_count(),
            "visible_chips": os.environ.get("TPU_VISIBLE_CHIPS"), "cache_dir": cache_dir,
        }
        self.rank = session.get_world_rank()
        self.log(f"platform={dev.platform} {self.device}")
        if not self.rehearse and dev.platform != "tpu":
            raise RuntimeError(f"not on a TPU: {self.device}")
        want = config["devices"]
        if (self.device["local"], self.device["count"]) != (want["local"], want["global"]):
            raise RuntimeError(f"wanted {want} devices, jax has {self.device}")
        self.mesh = session.get_mesh()
        self.session = session
        self.system = None
        self.checked: Dict[str, Any] = {}
        self.inspected: Dict[str, Any] = {}
        self.begin_vote, self.end_vote = self._make_vote()
        trace_dir = os.path.join(config["out_dir"], "trace",
                                 f"{config['cell']}.{config['seed']}.rank{self.rank}")
        self.tracer = Tracer(trace_dir) if config["trace"] else None

    def log(self, text: str) -> None:
        print(f"[bench worker {getattr(self, 'rank', '?')}] {text}", flush=True)

    def _listen(self) -> None:
        c = self.compiles

        def on_duration(event, seconds, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                c["seconds"] += seconds
                c["count"] += 1

        def on_event(event, **_):
            if event == "/jax/compilation_cache/cache_hits":
                c["hits"] += 1
            elif event == "/jax/compilation_cache/cache_misses":
                c["misses"] += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)

    def _make_vote(self):
        """(begin, end): begin(flag) queues one tiny jitted sum of the flag over
        the mesh and returns at once; end(handle) says whether it was set on
        any process. Alone, the flag itself."""
        from jax.sharding import NamedSharding, PartitionSpec as P

        if jax.process_count() == 1:
            return bool, bool
        sharded = NamedSharding(self.mesh, P(self.mesh.axis_names))
        total = jax.jit(lambda x: x.sum(), out_shardings=NamedSharding(self.mesh, P()))

        def begin(flag: bool):
            local = np.full((jax.local_device_count(),), int(flag), np.int32)
            return total(jax.make_array_from_process_local_data(sharded, local))

        return begin, lambda handle: int(handle) > 0

    @contextlib.contextmanager
    def setup(self, name: str):
        t = time.perf_counter()
        yield
        self.setup_spans[name] = self.setup_spans.get(name, 0.0) + time.perf_counter() - t

    # ---- the system
    def build_system(self):
        model = importlib.import_module("benchmark.models." + self.model_config["model"])
        with self.setup("state_init"):
            self.model = model
            self.system = model.build(self.model_config, self.mesh, self.config["seed"])
            jax.block_until_ready(self.system.state)
        return self.system

    @property
    def local_rows(self) -> int:
        return self.model_config["batch"]["global_rows"] // jax.process_count()

    @property
    def row_tokens(self) -> int:
        return self.model_config["batch"]["seq"] + 1

    def place(self, local_tokens: np.ndarray) -> Dict[str, Any]:
        """This process's rows onto the mesh, as a user does: `shard_batch`
        alone, `host_local_to_global` in a gang."""
        from ray_tpu.models import shard_batch
        from ray_tpu.parallel import batch_spec, host_local_to_global

        if jax.process_count() == 1:
            return shard_batch({"tokens": local_tokens}, self.mesh)
        return {"tokens": host_local_to_global(self.mesh, batch_spec(), local_tokens)}

    def check(self, local_tokens: np.ndarray) -> None:
        """The system against the reference on the first rows of the first
        batch: two rows on one device, one row per device in a gang (the batch
        axis has to divide)."""
        rows = 2 if jax.device_count() == 1 else jax.local_device_count()
        with self.setup("reference_check"):
            tokens = self.place(np.ascontiguousarray(local_tokens[:rows]))["tokens"]
            self.checked = self.model.check(self.system, tokens)
            path = self.system.attention_path(
                self.model_config["batch"]["global_rows"] // jax.device_count(),
                self.model_config["batch"]["seq"], self.device["platform"])
            self.checked["attention_path"] = path
            if not self.rehearse and path != "pallas":
                self.checked["ok"] = False
            # Each limit beside its reading, for the line's last key: the model's own where its
            # `check` gives them, else its module's `*_TOL` under the configuration's own.
            self.checked.setdefault("limits", {
                **{k: v for k, v in vars(self.model).items() if k.endswith("_TOL")},
                **self.model_config.get("check_tolerances", {})})
        self.log(f"check {json.dumps(self.checked)}")

    def inspect_step(self, batch) -> None:
        """The compiled step: Mosaic calls in its text, its memory by
        `memory_analysis()` (the runtime's `peak_bytes_in_use` leaves the
        program's temporaries out: PERF.md). `step_bytes` is XLA's own
        `peak_memory_in_bytes`, the arguments and the most of the temporaries
        that live at one time; arguments + `temp_size_in_bytes` count every
        temporary as if all lived at once and read more than a chip holds
        (PR 50). On the chip that peak is the one meaning of `step_bytes`: a
        compile that gives none fails the run. A CPU rehearsal, whose backend
        gives 0, keeps the sum, and its line says `rehearsal.`."""
        with self.setup("inspect_step"):
            compiled = self.system.step.lower(self.system.state, batch).compile()
            mem = compiled.memory_analysis()
            self.inspected = {
                "mosaic_calls": compiled.as_text().count("tpu_custom_call"),
                "argument_bytes": int(mem.argument_size_in_bytes),
                "temp_bytes": int(mem.temp_size_in_bytes),
                "output_bytes": int(mem.output_size_in_bytes),
                "alias_bytes": int(mem.alias_size_in_bytes),
                "peak_bytes": int(getattr(mem, "peak_memory_in_bytes", 0) or 0),
            }
            if not self.inspected["peak_bytes"] and not self.rehearse:
                raise RuntimeError("the compiled step's memory_analysis() gives no peak_memory_in_bytes: "
                                   "device.step_hbm_gib and memory_peak_bytes have no other meaning on the chip")
            self.inspected["step_bytes"] = self.inspected["peak_bytes"] or (
                self.inspected["argument_bytes"] + self.inspected["temp_bytes"]
                + self.inspected["output_bytes"] - self.inspected["alias_bytes"])
        self.log(f"compiled step {json.dumps(self.inspected)}")

    def warmup(self, one_step) -> None:
        """`WARMUP_STEPS` passes through the loop's whole path with this
        cell's shapes; `one_step()` returns what to wait for. Also warms the
        gang's vote, and notes when the first step was done."""
        with self.setup("warmup"):
            for i in range(WARMUP_STEPS):
                out = one_step()
                if i == 0:
                    jax.block_until_ready(out)
                    self.first_step_wall = time.time()
                    # The timed step's own first loss and gradient norm, beside the check's: where the step's
                    # rows are the check's (a gang's one row a chip), the same numbers by another program.
                    if isinstance(out, dict):
                        self.checked["first_step"] = {k: float(out[k]) for k in ("loss", "grad_norm") if k in out}
                self.end_vote(self.begin_vote(False))
            jax.block_until_ready(out)

    # ---- the window
    def clock(self) -> WindowClock:
        batch = self.model_config["batch"]
        self._compiles_before = dict(self.compiles)
        lo, hi = self.model_config["loss_band"]
        if self.mix.get("reuses_batch"):
            # A batch seen again and again is learnt by heart, after a spike
            # above the band in its first steps: only "finite" holds.
            lo, hi = 0.0, float("inf")
        return WindowClock(
            self.config["seconds"], batch["global_rows"] * batch["seq"], (lo, hi),
            begin_vote=self.begin_vote, end_vote=self.end_vote, tracer=self.tracer)

    def finish(self, clock: WindowClock, extra: Optional[Dict[str, Any]] = None) -> None:
        """After the window: memory on every device, the trace's table, one
        summary to the parent. Every rank reports, so that the gang's rounds
        stay in step."""
        from jax.experimental import multihost_utils

        in_window = {k: self.compiles[k] - self._compiles_before[k] for k in self.compiles}
        stats = ("bytes_in_use", "peak_bytes_in_use", "peak_bytes_reserved", "bytes_limit")
        mine = [[d.id] + [int((d.memory_stats() or {}).get(k, 0)) >> 20 for k in stats]
                for d in jax.local_devices()]
        traced = [0.0, 0.0]
        table = self.tracer.table() if self.tracer is not None else None
        if table is not None:
            trace = xplane.Trace(table)
            traced = [trace.busy_s, trace.window_s]
        per_device, traced_all = np.asarray(mine, np.int32), np.asarray([traced], np.float32)
        if jax.process_count() > 1:
            per_device = np.asarray(multihost_utils.process_allgather(per_device)).reshape(
                -1, per_device.shape[1])
            traced_all = np.asarray(multihost_utils.process_allgather(traced_all)).reshape(-1, 2)
        summary = {
            "device": self.device,
            "mesh": {k: int(v) for k, v in self.mesh.shape.items() if v > 1},
            "t_loop_wall": self.t_loop,
            "first_step_wall": self.first_step_wall,
            "window_wall_start": clock.wall0,
            "window_s": clock.window_s,
            "attempted": clock.attempted,
            "completed": clock.completed_steps,
            "failed": clock.failed,
            "tokens_per_s": clock.tokens_per_s,
            "window_tokens_per_s": clock.window_tokens_per_s,
            "step_medians_s": clock.step_medians,
            "stall_share": clock.stall_share(),
            "losses": clock.losses,
            "completed_at_s": clock.completed_at,
            "span_ms_per_step": {k: clock.span_ms_per_step(k) for k in clock.spans},
            "span_total_s": {k: sum(v) for k, v in clock.spans.items()},
            "setup_spans_s": self.setup_spans,
            "compiles_setup": self._compiles_before,
            "compiles_in_window": in_window,
            "check": self.checked,
            "compiled_step": self.inspected,
            "per_device_mib": [dict(zip(("id",) + stats, map(int, r))) for r in per_device],
            "traced_busy_window_s": [[float(a), float(b)] for a, b in traced_all],
            "trace_table": None,
        }
        summary.update(extra or {})
        if table is not None and self.rank == 0:
            path = os.path.join(self.config["out_dir"],
                                f"{self.config['cell']}.{self.config['seed']}.trace.json")
            with open(path, "w") as fh:
                json.dump(table, fh)
            summary["trace_table"] = path
        self.session.report({"summary": summary if self.rank == 0 else {"rank": self.rank}})

"""rt-lint (ray_tpu.devtools) test suite.

Two layers:
 - synthetic fixtures per pass (one known-bad + one known-good each), so the
   detectors themselves are pinned;
 - the live tree: `run_all` over the shipped package with the shipped
   allowlist must be clean — introducing an unhandled protocol tag, a
   blocking call on the loop thread, an undeclared config knob, etc. fails
   tier-1 right here.

Plus the runtime side of the annotations: RAY_TPU_DEBUG_INVARIANTS=1 turns
the decorators into asserts (checked in a subprocess, since the flag is read
at import), and off-mode decorators are identity (zero overhead).
"""

from __future__ import annotations

import os
import subprocess
import sys
import textwrap

import pytest

from ray_tpu.devtools import (
    lint, pass_affinity, pass_blocking, pass_config, pass_failpoints,
    pass_metrics, pass_protocol,
)
from ray_tpu.devtools.astutil import (
    Package, apply_allowlist, load_allowlist,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE_DIR = os.path.join(REPO_ROOT, "ray_tpu")


def make_pkg(**modules: str) -> Package:
    pkg = Package()
    for name, src in modules.items():
        pkg.add_module(name, name + ".py", textwrap.dedent(src))
    return pkg


# ---------------------------------------------------------------- protocol
FIXTURE_GRAMMAR = {
    "ping": {"arity": (2, 2), "readers": ("d",)},
    "batch": {"arity": (2, 2), "readers": ("d",)},
}
FIXTURE_DISPATCHERS = {"d": "fix:Conn.dispatch"}


def run_protocol(src: str):
    pkg = make_pkg(fix=src)
    return pass_protocol.run(
        pkg, grammar=FIXTURE_GRAMMAR, dispatchers=FIXTURE_DISPATCHERS,
        sender_modules=("fix",),
    )


def test_protocol_good_fixture_is_clean():
    violations = run_protocol(
        """
        class Conn:
            def dispatch(self, msg):
                kind = msg[0]
                if kind == "batch":
                    pass
                elif kind == "ping":
                    pass

            def emit(self):
                self.out.send(("ping", 1))
                self.out.send_async(("batch", [1, 2]))
        """
    )
    assert violations == []


def test_protocol_bad_fixture_flags_all_drift_kinds():
    violations = run_protocol(
        """
        class Conn:
            def dispatch(self, msg):
                kind = msg[0]
                if kind == "ping":     # handles ping but NOT batch
                    pass
                elif kind == "ghost":  # phantom: not in the grammar
                    pass

            def emit(self):
                self.out.send(("pong", 1))          # unknown tag
                self.out.send(("batch", [1], "x"))  # arity 3, grammar says 2
                self.out.send(("ping", 1))
        """
    )
    kinds = {v.key.split(":")[-1] for v in violations}
    assert "unknown" in kinds          # pong
    assert "arity" in kinds            # ("batch", ...) arity mismatch
    assert "phantom" in kinds          # ghost handled, not in grammar
    assert "unhandled" in kinds        # batch not handled by dispatcher
    # nothing ever sends a tag that isn't in the fixture, so no never-sent
    # beyond... batch IS sent. ping sent. -> no never-sent entries expected
    assert "never-sent" not in kinds


def test_protocol_never_sent_detected():
    violations = run_protocol(
        """
        class Conn:
            def dispatch(self, msg):
                kind = msg[0]
                if kind in ("ping", "batch"):
                    pass

            def emit(self):
                self.out.send(("ping", 1))   # batch handled but never sent
        """
    )
    assert any(v.key.endswith("tag=batch:never-sent") for v in violations)


def test_protocol_dynamic_tuple_registers_tag_without_arity_check():
    violations = run_protocol(
        """
        class Conn:
            def dispatch(self, msg):
                kind = msg[0]
                if kind in ("ping", "batch"):
                    pass

            def emit(self, payload):
                self.out.buffer(("ping",) + payload)  # arity unknown: ok
                self.out.send(("batch", [1]))
        """
    )
    assert violations == []


# ---------------------------------------------------------------- blocking
def run_blocking(src: str):
    pkg = make_pkg(fix=src)
    return pass_blocking.run(pkg, graph_modules=("fix",))


def test_blocking_bad_fixture_flags_reachable_sleep():
    violations = run_blocking(
        """
        import time

        def helper():
            time.sleep(1)

        class Scheduler:
            def _cmd_thing(self, payload):
                helper()
        """
    )
    assert len(violations) == 1
    assert "time.sleep" in violations[0].message
    assert "_cmd_thing" in violations[0].message  # root chain shown


def test_blocking_good_fixture_unreachable_and_guarded():
    violations = run_blocking(
        """
        import time

        def unreachable():
            time.sleep(1)  # nothing on the loop thread calls this

        class Scheduler:
            def _cmd_thing(self, payload):
                while self.conn.poll():
                    self.conn.recv_bytes()   # poll-guarded drain: fine
                self.fut.result(timeout=5)   # timed wait: fine

            def off_thread_helper(self):
                unreachable()
        """
    )
    assert violations == []


def test_blocking_nested_thread_target_not_attributed():
    violations = run_blocking(
        """
        import threading, time

        class Scheduler:
            def _cmd_thing(self, payload):
                def _worker():
                    time.sleep(1)  # runs on its own thread
                threading.Thread(target=_worker, daemon=True).start()
        """
    )
    assert violations == []


def test_blocking_untimed_waits_spelled_with_args_still_flagged():
    # acquire(blocking=True), acquire(True) and wait(None) are unbounded
    # waits dressed up with an argument — the bound check must not be fooled
    # (while acquire(blocking=False) is a try-lock and timeout=None is
    # explicit unboundedness).
    violations = run_blocking(
        """
        class Scheduler:
            def _cmd_a(self, payload):
                self._lock.acquire(blocking=True)

            def _cmd_b(self, payload):
                self._lock.acquire(True)

            def _cmd_c(self, payload):
                self.event.wait(None)

            def _cmd_d(self, payload):
                self.fut.result(timeout=None)

            def _cmd_ok(self, payload):
                self._lock.acquire(blocking=False)
                self._lock.acquire(True, 0.5)
                self.event.wait(1.0)
        """
    )
    flagged = {v.key.rsplit(":", 1)[0].rsplit(":", 1)[-1] for v in violations}
    assert flagged == {
        "Scheduler._cmd_a", "Scheduler._cmd_b", "Scheduler._cmd_c",
        "Scheduler._cmd_d",
    }, sorted(v.key for v in violations)


def test_blocking_loop_thread_only_annotation_is_a_root():
    violations = run_blocking(
        """
        import time
        from ray_tpu._private.concurrency import loop_thread_only

        class Other:
            @loop_thread_only
            def handler(self):
                time.sleep(0.1)
        """
    )
    assert len(violations) == 1 and "handler" in violations[0].message


# ---------------------------------------------------------------- affinity
def run_affinity(src: str):
    pkg = make_pkg(fix=src)
    return pass_affinity.run(pkg, modules={"fix"})


def test_affinity_bad_fixture_flags_call_and_unlocked_store():
    violations = run_affinity(
        """
        from ray_tpu._private.concurrency import any_thread, loop_thread_only

        class S:
            @loop_thread_only
            def on_loop(self):
                self.state = 1

            @any_thread
            def off_thread(self):
                self.state = 2      # off-thread mutation, no lock

            @any_thread
            def sneaky(self):
                self.on_loop()      # any -> loop call
        """
    )
    kinds = sorted(v.key for v in violations)
    assert any("calls=S.on_loop" in k for k in kinds)
    assert any("S.state:unlocked-shared" in k for k in kinds)


def test_affinity_good_fixture_locked_store_is_clean():
    violations = run_affinity(
        """
        from ray_tpu._private.concurrency import any_thread, loop_thread_only

        class S:
            @loop_thread_only
            def on_loop(self):
                with self._lock:
                    self.state = 1

            @any_thread
            def off_thread(self):
                with self._lock:
                    self.state = 2
        """
    )
    assert violations == []


def test_affinity_lock_guarded_counts_as_locked():
    violations = run_affinity(
        """
        from ray_tpu._private.concurrency import (
            any_thread, lock_guarded, loop_thread_only,
        )

        class S:
            @loop_thread_only
            def on_loop(self):
                with self._lock:
                    self.buf = []

            @any_thread
            @lock_guarded("_lock")
            def drain(self):
                self.buf = []
        """
    )
    assert violations == []


def test_affinity_closure_not_attributed_to_enclosing_function():
    # A closure defined inside a loop-only method runs when/where it is
    # CALLED (here: a thread target) — its unlocked store must not register
    # as a loop-thread store and pair up with the any-thread one.
    violations = run_affinity(
        """
        import threading

        from ray_tpu._private.concurrency import any_thread, loop_thread_only

        class S:
            @loop_thread_only
            def on_loop(self):
                def _bg():
                    self.state = 1   # runs on the bg thread, not the loop
                threading.Thread(target=_bg).start()

            @any_thread
            def off_thread(self):
                with self._lock:
                    self.state = 2
        """
    )
    assert violations == []


# ------------------------------------------------------------------ config
def run_config(src: str, fields, env_vars=frozenset(), **kw):
    pkg = make_pkg(fix=src)
    return pass_config.run(pkg, fields=set(fields), env_vars=set(env_vars), **kw)


def test_config_bad_fixture_flags_typo_dead_and_env():
    violations = run_config(
        """
        import os
        from ray_tpu._private.config import get_config

        def f():
            cfg = get_config()
            use(cfg.alpha)
            use(cfg.gamma)                       # undeclared (typo)
            use(os.environ.get("RAY_TPU_MYSTERY_KNOB"))  # unregistered env
        """,
        fields={"alpha", "beta"},  # beta is never read -> dead
    )
    keys = sorted(v.key for v in violations)
    assert any("cfg.gamma" in k for k in keys)
    assert any("dead.beta" in k for k in keys)
    assert any("env.RAY_TPU_MYSTERY_KNOB" in k for k in keys)
    assert not any("cfg.alpha" in k for k in keys)


def test_config_good_fixture_is_clean():
    violations = run_config(
        """
        import os
        from ray_tpu._private.config import get_config

        def f():
            cfg = get_config()
            use(cfg.alpha, cfg.beta)
            use(os.environ.get("RAY_TPU_alpha"))     # override form: fine
            use(os.environ.get("RAY_TPU_KNOWN"))     # registered: fine
        """,
        fields={"alpha", "beta"},
        env_vars={"RAY_TPU_KNOWN"},
    )
    assert violations == []


def test_config_rllib_style_config_objects_ignored():
    violations = run_config(
        """
        class Algo:
            def step(self):
                cfg = self.config       # rllib AlgorithmConfig, NOT runtime
                use(cfg.train_batch_size)
        """,
        fields={"alpha"},
        check_dead=False,
        config_modules=(),  # fixture module is not runtime-core
    )
    assert violations == []


# ----------------------------------------------------------------- metrics
def run_metrics(src: str, hot=False, doc="ray_tpu_documented_total"):
    pkg = make_pkg(fix=src)
    return pass_metrics.run(
        pkg, hot_modules=("fix",) if hot else (), doc_text=doc,
    )


def test_metrics_bad_names_flagged():
    violations = run_metrics(
        """
        from ray_tpu.util.metrics import Counter

        a = Counter("ray_tpu_documented_total", "fine")
        b = Counter("not_prefixed_total", "bad prefix")
        c = Counter("ray_tpu_not_in_doc_total", "undocumented")
        """
    )
    keys = sorted(v.key for v in violations)
    assert any("name.not_prefixed_total" in k for k in keys)
    assert any("undocumented.ray_tpu_not_in_doc_total" in k for k in keys)
    assert len(violations) == 2


def test_metrics_hot_module_import_and_calls_flagged():
    violations = run_metrics(
        """
        from ray_tpu.util.metrics import Counter

        def hot_path(m):
            m.inc(1)
        """,
        hot=True,
    )
    kinds = sorted(v.key for v in violations)
    assert any("hot-import" in k for k in kinds)
    assert any("hot-call" in k for k in kinds)


def test_metrics_plain_int_bumps_are_fine_in_hot_modules():
    violations = run_metrics(
        """
        _STATS = {"msgs": 0}

        def hot_path(n):
            _STATS["msgs"] += n
        """,
        hot=True,
    )
    assert violations == []


def _obs_pkg(rules: str, kinds: str, emitter: str = "") -> Package:
    """Fixture package carrying the two obs registries (alert pack + event
    kinds) plus an optional extra module with emit sites."""
    mods = {
        "fixpkg._private.timeseries": f"DEFAULT_ALERT_RULES = {rules}\n",
        "fixpkg._private.events": f"EVENT_KINDS = {kinds}\n",
    }
    if emitter:
        mods["fixpkg.emitter"] = emitter
    return make_pkg(**mods)


def test_metrics_alert_rules_and_event_kinds_cross_checked():
    """M4/M5: a rule whose metric or name is missing from the doc fails, as
    does an EVENT_KINDS entry the doc doesn't list."""
    pkg = _obs_pkg(
        rules="""[
            {"name": "good_rule", "metric": "ray_tpu_documented_total"},
            {"name": "stale_rule", "metric": "ray_tpu_ghost_total"},
        ]""",
        kinds='("documented_kind", "ghost_kind")',
    )
    doc = ("| `good_rule` | ray_tpu_documented_total |\n"
           "| `documented_kind` | head |\n")
    violations = pass_metrics.run(pkg, hot_modules=(), doc_text=doc)
    keys = sorted(v.key for v in violations)
    assert any("alert-rule.stale_rule" in k for k in keys)
    assert any("alert-metric.ray_tpu_ghost_total" in k for k in keys)
    assert any("event-kind.ghost_kind" in k for k in keys)
    assert not any("good_rule" in k for k in keys)
    assert not any("documented_kind" in k for k in keys)
    assert len(violations) == 3


def test_metrics_unregistered_emit_kind_flagged():
    """M5: an emit site using a kind that EVENT_KINDS doesn't register fails
    even if the doc happens to mention the string."""
    pkg = _obs_pkg(
        rules="[]",
        kinds='("registered_kind",)',
        emitter="""
            from fixpkg._private.events import emit_event

            def seams(self):
                emit_event("registered_kind", "fine")
                emit_event("rogue_kind", "not in the registry")
                self._emit_event("rogue_method_kind", "also checked")
            """,
    )
    doc = "| `registered_kind` | `rogue_kind` | `rogue_method_kind` |"
    violations = pass_metrics.run(pkg, hot_modules=(), doc_text=doc)
    keys = sorted(v.key for v in violations)
    assert any("event-unregistered.rogue_kind" in k for k in keys)
    assert any("event-unregistered.rogue_method_kind" in k for k in keys)
    assert len(violations) == 2


def test_metrics_live_alert_pack_parses_as_literal():
    """The real DEFAULT_ALERT_RULES must stay a pure literal (the lint
    contract) and reference only documented metrics — parse it exactly the
    way the pass does and cross-check the live COMPONENTS.md."""
    import ast as _ast

    src = open(os.path.join(PACKAGE_DIR, "_private", "timeseries.py")).read()
    rules = None
    for node in _ast.walk(_ast.parse(src)):
        if isinstance(node, _ast.Assign) and any(
            isinstance(t, _ast.Name) and t.id == "DEFAULT_ALERT_RULES"
            for t in node.targets
        ):
            rules = _ast.literal_eval(node.value)
    assert rules, "DEFAULT_ALERT_RULES must be a module-level pure literal"
    doc = open(os.path.join(REPO_ROOT, "COMPONENTS.md")).read()
    from ray_tpu._private.events import EVENT_KINDS

    for rule in rules:
        assert rule["name"] in doc
        assert rule["metric"] in doc
    for kind in EVENT_KINDS:
        assert kind in doc


# -------------------------------------------------------------- failpoints
def run_failpoints(src: str, doc="`conn.send` | `sched.cmd.<method>` |"):
    pkg = make_pkg(fix=src)
    return pass_failpoints.run(pkg, doc_text=doc)


def test_failpoints_documented_names_are_clean():
    violations = run_failpoints(
        """
        from ray_tpu._private import failpoints

        def hook(method):
            failpoints.fire("conn.send")
            failpoints.fire("sched.cmd." + method)   # documented prefix
            failpoints.fire(method)                  # dynamic: skipped
        """
    )
    assert violations == []


def test_failpoints_undocumented_and_bad_names_flagged():
    violations = run_failpoints(
        """
        from ray_tpu._private import failpoints

        def hook():
            failpoints.fire("not.in.the.table")
            failpoints.maybe_crash("Bad-Name")
        """
    )
    keys = sorted(v.key for v in violations)
    assert any("undocumented.not.in.the.table" in k for k in keys)
    assert any("name.Bad-Name" in k for k in keys)
    assert len(violations) == 2


# --------------------------------------------------------------- ownership
def test_ownership_head_table_access_flagged():
    from ray_tpu.devtools import pass_ownership

    pkg = make_pkg(**{
        "ray_tpu._private.worker": """
            def bad(ctx):
                return ctx.scheduler.tasks[b"k"]

            def also_bad(sched):
                sched.object_table.pop(b"k", None)

            def fine(ctx):
                return ctx.scheduler.call("get_metas", None)
            """,
    })
    violations = pass_ownership.run(pkg)
    keys = sorted(v.key for v in violations)
    assert any("head_table.tasks" in k for k in keys)
    assert any("head_table.object_table" in k for k in keys)
    assert len(violations) == 2


def test_ownership_scheduler_module_itself_exempt():
    from ray_tpu.devtools import pass_ownership

    pkg = make_pkg(**{
        "ray_tpu._private.scheduler": """
            class Scheduler:
                def seal(self, key):
                    return self.object_table.get(key)
            """,
    })
    assert pass_ownership.run(pkg) == []


# --------------------------------------------------------------- allowlist
def test_allowlist_requires_justification_and_rejects_stale(tmp_path):
    f = tmp_path / "allow.txt"
    f.write_text(
        "# comment\n"
        "some:key:with -- a real justification\n"
        "bare:key:without:justification\n"
    )
    entries, errors = load_allowlist(str(f))
    assert len(entries) == 1 and entries[0].key == "some:key:with"
    assert len(errors) == 1 and "justification" in errors[0]
    # No violation matches the entry -> it is stale/unused.
    remaining, unused = apply_allowlist([], entries)
    assert remaining == [] and len(unused) == 1


# --------------------------------------------------------------- live tree
def test_live_tree_is_clean_under_shipped_allowlist():
    violations, errors = lint.run_all(
        PACKAGE_DIR, allowlist_path=lint.DEFAULT_ALLOWLIST,
    )
    msg = "\n".join(v.render() for v in violations) + "\n".join(errors)
    assert not violations and not errors, f"rt-lint regressions:\n{msg}"


def test_cli_exits_zero_on_live_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.lint", PACKAGE_DIR, "-q"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_grammar_is_a_pure_literal():
    # The linter reads MESSAGE_GRAMMAR with ast.literal_eval from source;
    # a refactor to computed values would silently disable the pass.
    import ast as _ast

    from ray_tpu.devtools.astutil import load_package

    pkg = load_package(PACKAGE_DIR, package_name="ray_tpu")
    grammar, dispatchers = pass_protocol._grammar_from_source(pkg)
    assert isinstance(grammar, dict) and len(grammar) >= 20
    assert isinstance(dispatchers, dict) and len(dispatchers) >= 6
    for tag, spec in grammar.items():
        lo, hi = spec["arity"]
        assert 1 <= lo <= hi, tag


# ============================================================== rt-verify
# The system-level passes (ray_tpu.devtools.verify): session machine,
# lock-order cycles, native C checks, stale binaries. Same two-layer
# structure as rt-lint: pinned fixtures + the live tree must verify clean.

FIX_SESSION_GRAMMAR = {
    "ping": {"dir": "worker->head", "arity": (2, 2), "readers": ("d",)},
    "pong": {"dir": "head->worker", "arity": (2, 2), "readers": ("w",)},
    "reply": {"dir": "worker->head", "arity": (2, 2), "readers": ("d",)},
}
FIX_SESSION_SPEC = {
    "module_roles": {"fix.py": ("worker",)},
    "pairs": {"ping": {"reply": "pong", "token_elem": 1}},
    "streams": {},
}


def run_session(src: str, spec=None, grammar=None):
    from ray_tpu.devtools.verify import pass_session

    pkg = make_pkg(fix=src)
    return pass_session.run(
        pkg, grammar=grammar or FIX_SESSION_GRAMMAR,
        spec=spec or FIX_SESSION_SPEC, sender_modules=("fix",),
    )


def test_session_good_fixture_is_clean():
    violations = run_session(
        """
        class Conn:
            def emit(self):
                self.out.send(("ping", 1))
                self.out.send(("reply", 2))
        """
    )
    assert violations == []


def test_session_role_violation_flagged():
    # fix.py speaks "worker"; "pong" is head->worker, so sending it here is
    # a role violation — the dir field is enforced, not documentation.
    violations = run_session(
        """
        class Conn:
            def emit(self):
                self.out.send(("pong", 1))
        """
    )
    assert len(violations) == 1
    assert "role" in violations[0].key and "pong" in violations[0].message


def test_session_unmapped_module_flagged():
    violations = run_session(
        """
        class Conn:
            def emit(self):
                self.out.send(("ping", 1))
        """,
        spec={"module_roles": {}, "pairs": {}, "streams": {}},
    )
    assert any("module-unmapped" in v.key for v in violations)


def test_session_spec_coherence_checks():
    # Pair naming an unknown tag + reply that does not reverse direction.
    violations = run_session(
        """
        class Conn:
            def emit(self):
                self.out.send(("ping", 1))
                self.out.send(("reply", 2))
        """,
        spec={
            "module_roles": {"fix.py": ("worker",)},
            "pairs": {
                "ping": {"reply": "ghost", "token_elem": 1},
                "reply": {"reply": "ping", "token_elem": 1},  # w->h -> w->h
            },
            "streams": {},
        },
    )
    keys = sorted(v.key for v in violations)
    assert any("spec-unknown" in k for k in keys)
    assert any("direction" in k for k in keys)


def test_session_stream_coverage():
    grammar = dict(FIX_SESSION_GRAMMAR)
    grammar["xfer_begin"] = {"dir": "any", "arity": (2, 2), "readers": ("d",)}
    grammar["xfer_stray"] = {"dir": "any", "arity": (2, 2), "readers": ("d",)}
    violations = run_session(
        """
        class Conn:
            def emit(self):
                self.out.send(("ping", 1))
                self.out.send(("reply", 2))
        """,
        grammar=grammar,
        spec={
            "module_roles": {"fix.py": ("worker",)},
            "pairs": {},
            "streams": {"xfer": {"open": "xfer_begin", "data": (),
                                 "close": (), "key_elem": 1}},
        },
    )
    assert any("stream-coverage" in v.key and "xfer_stray" in v.message
               for v in violations)


def test_lockorder_cycle_and_self_cycle_detected():
    from ray_tpu.devtools.verify import pass_lockorder

    pkg = make_pkg(fix="""
        import threading

        class A:
            def __init__(self, b: "B"):
                self._lock = threading.Lock()
                self.b = b
            def one(self):
                with self._lock:
                    self.b.poke()
        class B:
            def __init__(self, a: "A"):
                self._lock = threading.Lock()
                self.a = a
            def poke(self):
                with self._lock:
                    pass
            def two(self):
                with self._lock:
                    self.a.one()
        class C:
            def __init__(self):
                self._lock = threading.Lock()
            def outer(self):
                with self._lock:
                    self.helper()
            def helper(self):
                with self._lock:
                    pass
        """)
    violations = pass_lockorder.run(pkg)
    keys = sorted(v.key for v in violations)
    assert any("cycle=A._lock>B._lock" in k for k in keys), keys
    assert any("self-cycle=C._lock" in k for k in keys), keys


def test_lockorder_clean_fixture_and_nested_def_excluded():
    from ray_tpu.devtools.verify import pass_lockorder

    pkg = make_pkg(fix="""
        import threading

        class A:
            def __init__(self, b: "B"):
                self._lock = threading.Lock()
                self.b = b
            def one(self):
                with self._lock:
                    pass
                self.b.poke()          # outside the lock: no edge
            def deferred(self):
                with self._lock:
                    def cb():
                        self.b.poke()  # runs later, elsewhere: no edge
                    register(cb)
        class B:
            def __init__(self):
                self._lock = threading.Lock()
            def poke(self):
                with self._lock:
                    pass
            def two(self):
                with self._lock:
                    pass
        """)
    assert pass_lockorder.run(pkg) == []


def test_lockorder_guard_decorator_counts_as_held():
    from ray_tpu.devtools.verify import pass_lockorder

    pkg = make_pkg(fix="""
        import threading
        from ray_tpu._private.concurrency import lock_guarded

        class A:
            def __init__(self, b: "B"):
                self._lock = threading.Lock()
                self.b = b
            @lock_guarded("_lock")
            def flush_locked(self):
                self.b.poke()
        class B:
            def __init__(self, a: "A"):
                self._lock = threading.Lock()
                self.a = a
            def poke(self):
                with self._lock:
                    pass
            def two(self):
                with self._lock:
                    self.a.flush_locked()
        """)
    violations = pass_lockorder.run(pkg)
    assert any("cycle" in v.key for v in violations)


NATIVE_BAD_FIXTURE = r"""
static PyObject *leaky(void) {
    PyObject *a = PyList_New(2);
    if (!a) return NULL;
    if (bad_thing()) {
        return NULL;   /* leaks a */
    }
    return a;
}
static int unchecked_alloc(void) {
    char *m = (char *)PyMem_Malloc(64);
    m[0] = 'x';
    return 0;
}
static void unchecked_copy(char *dst, const char *src, unsigned n) {
    memcpy(dst, src, n);
}
"""

NATIVE_GOOD_FIXTURE = r"""
static PyObject *clean(void) {
    PyObject *a = PyList_New(2);
    if (!a) return NULL;
    if (bad_thing()) {
        Py_DECREF(a);
        return NULL;
    }
    return a;
}
static int checked_alloc(void) {
    char *m = (char *)PyMem_Malloc(64);
    if (!m) return -1;
    m[0] = 'x';
    return 0;
}
static void checked_copy(char *dst, const char *src, unsigned n) {
    if (n > 64) return;
    memcpy(dst, src, n);
}
"""


def test_native_pass_bad_fixture_flags_all_kinds():
    from ray_tpu.devtools.verify import pass_native

    violations = pass_native.run(sources={"fix.c": NATIVE_BAD_FIXTURE})
    keys = sorted(v.key for v in violations)
    assert any("leak=a" in k for k in keys), keys
    assert any("alloc=m:unchecked" in k for k in keys), keys
    assert any("len=n:memcpy" in k for k in keys), keys


def test_native_pass_good_fixture_is_clean():
    from ray_tpu.devtools.verify import pass_native

    assert pass_native.run(sources={"fix.c": NATIVE_GOOD_FIXTURE}) == []


def test_stale_binary_guard(tmp_path):
    from ray_tpu.devtools.verify import stale

    src = tmp_path / "wire_native.c"
    so = tmp_path / "wire_native.so"
    src.write_bytes(b"int x;\n")
    import hashlib

    good = hashlib.sha256(b"int x;\n").hexdigest()
    # Matching stamp: clean.
    so.write_bytes(b"\x7fELF" + b"RAY_TPU_WIRE_SRC_SHA256=" + good.encode() + b"\x00")
    assert stale.run(native_dir=str(tmp_path)) == []
    # Source drifts: violation.
    src.write_bytes(b"int y;\n")
    violations = stale.run(native_dir=str(tmp_path))
    assert len(violations) == 1 and "drift" in violations[0].key
    # Unstamped binary: violation.
    so.write_bytes(b"\x7fELF no stamp\x00")
    violations = stale.run(native_dir=str(tmp_path))
    assert len(violations) == 1 and "unstamped" in violations[0].key
    # Missing binary: not a violation (built on demand).
    so.unlink()
    assert stale.run(native_dir=str(tmp_path)) == []


def test_built_binaries_match_their_sources():
    # The live stale check: whatever .so this checkout has built (none are
    # in git; a fresh clone builds them on first use) embeds the sha256 of
    # the exact source next to it.
    from ray_tpu.devtools.verify import stale

    violations = stale.run()
    assert violations == [], "\n".join(v.render() for v in violations)


def test_verify_live_tree_is_clean_under_shipped_allowlist():
    from ray_tpu.devtools import verify

    violations, errors = verify.run_all(
        PACKAGE_DIR, allowlist_path=verify.DEFAULT_ALLOWLIST,
    )
    msg = "\n".join(v.render() for v in violations) + "\n".join(errors)
    assert not violations and not errors, f"rt-verify regressions:\n{msg}"


def test_verify_cli_exits_zero_on_live_tree():
    proc = subprocess.run(
        [sys.executable, "-m", "ray_tpu.devtools.verify", PACKAGE_DIR, "-q"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=180,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


def test_session_spec_is_a_pure_literal():
    # Like MESSAGE_GRAMMAR: the session spec must stay literal_eval-able or
    # the static pass silently loses its input.
    from ray_tpu.devtools.astutil import load_package
    from ray_tpu.devtools.verify import pass_session

    pkg = load_package(PACKAGE_DIR, package_name="ray_tpu")
    spec = pass_session._literal_from_source(pkg, ("SESSION_SPEC",)).get(
        "SESSION_SPEC")
    assert isinstance(spec, dict)
    assert spec["pairs"] and spec["streams"] and spec["module_roles"]


def test_parsed_ast_cache_shared_across_passes():
    # Satellite: one parse per file per process. Two loads of the live tree
    # return the IDENTICAL Package object (stat-signature validated).
    from ray_tpu.devtools.astutil import load_package

    p1 = load_package(PACKAGE_DIR, package_name="ray_tpu")
    p2 = load_package(PACKAGE_DIR, package_name="ray_tpu")
    assert p1 is p2


# ------------------------------------------------------------ runtime guards
_GUARD_SNIPPET = """
import threading
from ray_tpu._private import concurrency

assert concurrency.DEBUG_INVARIANTS

class Obj:
    def __init__(self):
        self._loop_tid = threading.get_ident() + 12345  # "another" thread
        self._lock = threading.Lock()

    @concurrency.loop_thread_only
    def loop_fn(self):
        return 1

    @concurrency.lock_guarded("_lock")
    def locked_fn(self):
        return 2

o = Obj()
try:
    o.loop_fn()
    raise SystemExit("loop_thread_only guard did not fire")
except AssertionError:
    pass
try:
    o.locked_fn()
    raise SystemExit("lock_guarded guard did not fire")
except AssertionError:
    pass
with o._lock:
    assert o.locked_fn() == 2
o._loop_tid = threading.get_ident()
assert o.loop_fn() == 1
o._loop_tid = None          # loop not started yet: guard skips
assert o.loop_fn() == 1

# BatchedSender's internals honor the lock contract under the guard.
from ray_tpu._private.batching import BatchedSender
from ray_tpu._private.config import Config

frames = []
bs = BatchedSender(frames.append, cfg=Config(), start_timer=False)
bs.send_async(("cmd", "x", 1))
bs.flush()
bs.send(("req", 0, "y", 2))
assert len(frames) >= 2
try:
    bs._flush_locked()
    raise SystemExit("BatchedSender._flush_locked ran without the lock")
except AssertionError:
    pass
print("GUARDS-OK")
"""


def test_debug_invariants_guards_fire_in_subprocess():
    env = dict(os.environ, RAY_TPU_DEBUG_INVARIANTS="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", _GUARD_SNIPPET], env=env, cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=120,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "GUARDS-OK" in proc.stdout


def test_debug_invariants_off_mode_is_identity():
    # Off (the default here): decorators hand back the same function object —
    # literally zero call overhead, which is what bench_core's invariants
    # probe asserts end to end.
    from ray_tpu._private import concurrency

    if concurrency.DEBUG_INVARIANTS:
        pytest.skip("suite running with RAY_TPU_DEBUG_INVARIANTS=1")

    def fn(self):
        return 7

    assert concurrency.loop_thread_only(fn) is fn
    assert concurrency.any_thread(fn) is fn
    assert concurrency.lock_guarded("_lock")(fn) is fn


def test_cluster_runs_clean_under_debug_invariants():
    # End-to-end: a real (small) cluster with the runtime guards armed —
    # tasks, an actor, a put/get — must not trip a single assert.
    snippet = (
        "import ray_tpu\n"
        "ray_tpu.init(num_cpus=2)\n"
        "@ray_tpu.remote\n"
        "def f(x):\n"
        "    return x + 1\n"
        "assert ray_tpu.get([f.remote(i) for i in range(40)]) == list(range(1, 41))\n"
        "@ray_tpu.remote\n"
        "class A:\n"
        "    def inc(self, v):\n"
        "        return v + 1\n"
        "a = A.remote()\n"
        "assert ray_tpu.get(a.inc.remote(41)) == 42\n"
        "r = ray_tpu.put(b'x' * 4096)\n"
        "assert ray_tpu.get(r) == b'x' * 4096\n"
        "ray_tpu.shutdown()\n"
        "print('INVARIANT-CLUSTER-OK')\n"
    )
    env = dict(os.environ, RAY_TPU_DEBUG_INVARIANTS="1", JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, "-c", snippet], env=env, cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=300,
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "INVARIANT-CLUSTER-OK" in proc.stdout

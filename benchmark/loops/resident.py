"""The loop of a `resident` mix: one seeded batch placed on the device in
set-up and reused. The loop dispatches a step and waits for the loss of the
step `lag` before it, so the device's queue never empties and never runs far
ahead, and every step has a completion time. `session.report` every
`report_every` steps. The framework's per-step path is idle."""

from __future__ import annotations

import collections


def prepare(ctx):
    """Parent side, before `fit()`: nothing to feed."""
    return None


def train_loop(config):
    import jax

    from benchmark.harness import traffic
    from benchmark.harness.worker import WorkerRun

    run = WorkerRun(config)
    mix, session = run.mix, run.session
    system = run.build_system()
    with run.setup("data"):
        tokens = traffic.resident_batch(
            run.model_config["vocab_size"], run.model_config["batch"]["global_rows"],
            run.row_tokens, config["seed"])
        local = tokens[run.rank * run.local_rows:(run.rank + 1) * run.local_rows]
        batch = run.place(local)
    run.check(local)
    run.inspect_step(batch)
    state, step = system.state, system.step

    def one_step():
        nonlocal state
        state, metrics = step(state, batch)
        return metrics

    run.warmup(one_step)

    clock = run.clock()
    pending = collections.deque()

    def drain():
        while pending:
            clock.completed(float(pending.popleft()["loss"]))

    clock.start()
    while not clock.expired():
        with clock.step(drain):
            with clock.span("dispatch"):
                state, metrics = step(state, batch)
            clock.dispatched()
            pending.append(metrics)
            if len(pending) > mix["lag"]:
                with clock.span("sync"):
                    loss = float(pending.popleft()["loss"])
                clock.completed(loss)
            if clock.attempted % mix["report_every"] == 0:
                with clock.span("report"):
                    session.report({"step": clock.attempted, "loss": clock.losses[-1]})
    with clock.span("sync"):
        drain()
    jax.block_until_ready(state)
    clock.stop()
    clock.close_tracer()
    run.finish(clock)

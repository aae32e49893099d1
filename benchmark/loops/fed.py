"""The loop of a `fed` mix: the loop `JaxTrainer`'s docstring gives, with the
input pipeline running. Documents of heavy-tailed length from `--seed` are a
`ray_tpu.data` dataset; a `map_batches` stage packs them into rows during the
window; `streaming_split` deals the blocks to the workers;
`get_dataset_shard("train").iter_batches` -> `shard_batch` (or
`host_local_to_global` in a gang) -> `step` -> `float(loss)` ->
`session.report`, every step."""

from __future__ import annotations

import functools

WARMUP_ALLOWANCE_S = 5.0  # rows the warm-up and the check eat, in seconds of supply


def prepare(ctx):
    """Parent side, before `fit()`: the dataset, `supply_factor` times what the
    configuration is predicted to eat in the window. No jax here."""
    import ray_tpu.data

    from benchmark.harness import traffic

    mix, model = ctx["traffic"], ctx["model_config"]
    row_tokens = model["batch"]["seq"] + 1
    total = int(mix["supply_factor"] * model["predicted_tokens_per_s_per_chip"]
                * ctx["chips"] * (ctx["seconds"] + WARMUP_ALLOWANCE_S))
    eot_id = model["vocab_size"] - 1
    blocks = traffic.make_document_blocks(
        mix["documents"], ctx["seed"], total, mix["block_rows"], row_tokens, eot_id)
    pack = functools.partial(traffic.pack_documents, row_tokens=row_tokens, eot_id=eot_id)
    return {"train": ray_tpu.data.from_arrow(blocks).map_batches(pack, batch_format="pyarrow")}


def train_loop(config):
    import jax

    from benchmark.harness.worker import WorkerRun

    run = WorkerRun(config)
    session = run.session
    system = run.build_system()
    with run.setup("data"):
        batches = iter(session.get_dataset_shard("train").iter_batches(
            batch_size=run.local_rows, drop_last=True))
        first = next(batches)["tokens"]
    run.check(first)
    run.inspect_step(run.place(first))
    state, step = system.state, system.step
    warm = iter([first])

    def one_step():
        nonlocal state
        tokens = next(warm, None)
        if tokens is None:
            tokens = next(batches)["tokens"]
        state, metrics = step(state, run.place(tokens))
        session.report({"loss": float(metrics["loss"]), "warmup": True})
        return metrics

    run.warmup(one_step)

    clock = run.clock()
    clock.start()
    while not clock.expired():
        with clock.step():
            with clock.span("data_wait"):
                rows = next(batches, None)
            if rows is None:
                # No rank can leave a gang's loop alone: no result.
                raise RuntimeError("the dataset ran dry inside the window: raise "
                                   "predicted_tokens_per_s_per_chip in the configuration")
            with clock.span("h2d"):
                batch = run.place(rows["tokens"])
            with clock.span("dispatch"):
                state, metrics = step(state, batch)
            clock.dispatched()
            with clock.span("sync"):
                loss = float(metrics["loss"])
            clock.completed(loss)
            with clock.span("report"):
                session.report({"loss": loss})
    jax.block_until_ready(state)
    clock.stop()
    clock.close_tracer()
    run.finish(clock)

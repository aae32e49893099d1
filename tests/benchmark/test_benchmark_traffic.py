"""The document generator and the packer of the `fed` mix, and where the mix's geometry puts the
block pulls of each fed cell on one chip."""

import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.clock import TRACE_STEPS  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402
from benchmark.loops.fed import WARMUP_ALLOWANCE_S  # noqa: E402

SPEC = Manifest().traffic("fed")["documents"]
EOT = 50256


def rows_of(seed, total=400_000):
    blocks = traffic.make_document_blocks(SPEC, seed, total, 64, 1025, EOT)
    return blocks, [traffic.pack_documents(b, row_tokens=1025, eot_id=EOT)["tokens"] for b in blocks]


def test_same_seed_same_rows_another_seed_other_rows():
    _, a = rows_of(7)
    _, b = rows_of(7)
    _, c = rows_of(8)
    assert len(a) == len(b) and all((x == y).all() for x, y in zip(a, b))
    assert not (a[0][: len(c[0])] == c[0][: len(a[0])]).all()


def test_rows_are_1025_int32_ids_of_the_vocabulary_and_enough_of_them():
    blocks, rows = rows_of(1)
    assert all(r.dtype == np.int32 and r.ndim == 2 and r.shape[1] == 1025 for r in rows)
    assert all(r.shape[0] >= 64 for r in rows)
    assert sum(r.size for r in rows) >= 400_000
    assert all(0 <= r.min() and r.max() <= EOT for r in rows)


def test_packer_equals_the_plain_loop_and_ends_every_document():
    blocks, rows = rows_of(3, total=70_000)
    flat = []
    for doc in blocks[0].column("tokens").to_pylist():
        flat += doc + [EOT]
    n = len(flat) // 1025
    assert (np.asarray(flat[: n * 1025], np.int32).reshape(n, 1025) == rows[0]).all()
    assert all(EOT not in doc for doc in blocks[0].column("tokens").to_pylist())


def test_document_lengths_are_the_stated_lognormal():
    lengths = traffic.document_lengths(SPEC, 200_000, np.random.default_rng(0))
    assert lengths.min() >= 8 and lengths.max() == 8192
    assert abs(np.median(lengths) - 400) < 8
    inside = lengths[(lengths > 8) & (lengths < 8192)]
    assert abs(np.log(inside).std() - 1.2) < 0.05
    assert 780 < lengths.mean() < 840  # 400 exp(0.72) = 822 before the cap


def test_resident_batch_is_seeded():
    a = traffic.resident_batch(50257, 8, 1025, 4)
    assert a.shape == (8, 1025) and a.dtype == np.int32 and a.max() < 50257
    assert (a == traffic.resident_batch(50257, 8, 1025, 4)).all()
    assert not (a == traffic.resident_batch(50257, 8, 1025, 5)).all()


# ------------------------------------------------------------------ where the block pulls fall
WALK_SEEDS = range(2147480100, 2147480112)
ONE_CHIP_FED = ("gpt2-medium.fed", "olmoe-1b-7b-l1.fed4k", "lfm2-24b-a2b-ep8-l5.fed4k",
                "glm-4.7-flash-ep8-l5.fed4k", "keye-vl-2.0-30b-a3b-ep8.fed16k", "sdar-30b-a3b-chat-ep8.fed8k")


def pulls_by_step(m, cell, seed):
    """The blocks `loops/fed.py prepare` makes for a run of `cell`, packed, and dealt out as
    `DataIterator.iter_batches(batch_size=rows a step, drop_last=True)` deals them: the rows left of
    the blocks before carry over, and a step whose `next(batches)` finds fewer than it needs pulls
    blocks until it has them. Returns the packed blocks' row counts and the pulls in each step."""
    w = m.cell(cell)
    mix, model = m.traffic(w["traffic"]), m.config(w["config"])
    row_tokens, eot_id = model["batch"]["seq"] + 1, model["vocab_size"] - 1
    total = int(mix["supply_factor"] * model["predicted_tokens_per_s_per_chip"] * w["chips"]
                * (m.data["run_seconds"] + WARMUP_ALLOWANCE_S))
    blocks = traffic.make_document_blocks(mix["documents"], seed, total, mix["block_rows"], row_tokens, eot_id)
    rows = [len(traffic.pack_documents(b, row_tokens=row_tokens, eot_id=eot_id)["tokens"]) for b in blocks]
    a_step, left, carry, pulls = model["batch"]["global_rows"], iter(rows), 0, []
    while True:
        pulled = 0
        while carry < a_step:
            block = next(left, None)
            if block is None:
                return rows, pulls
            carry, pulled = carry + block, pulled + 1
        carry -= a_step
        pulls.append(pulled)


@pytest.mark.parametrize("cell", ONE_CHIP_FED)
def test_the_pulls_reading_lists_a_cell_just_where_every_window_of_traced_steps_holds_a_pull(cell):
    """`data.fetch_block_ms` reads the pulls inside the `TRACE_STEPS` traced steps and nothing where
    none fell there, and a traced line that lacks a listed metric is refused. A packed block holds the
    document that crosses its end, so a row or several more than `block_rows`, the carried rows add up
    and every so often a pull comes a step late: where a block lasts about eight steps some window of
    8 holds none (`gpt2-medium.fed` and OLMoE's cell were listed until PR 50, and read by luck); where
    it lasts two (LFM2's cell: 8 rows a step of blocks of 16 or 17) every window holds three or more.
    The walk is over the whole supply of twelve seeds, so over every place the traced steps can fall."""
    m = Manifest()
    listed = cell in next(e for e in m.data["per_layer"] if e["name"] == "data.fetch_block_ms")["workloads"]
    windows = empty = 0
    fewest = TRACE_STEPS
    for seed in WALK_SEEDS:
        rows, pulls = pulls_by_step(m, cell, seed)
        assert min(rows) >= m.traffic(m.cell(cell)["traffic"])["block_rows"] and len(pulls) > 4 * TRACE_STEPS
        for first in range(len(pulls) - TRACE_STEPS + 1):
            inside = sum(pulls[first:first + TRACE_STEPS])
            windows, empty, fewest = windows + 1, empty + (inside == 0), min(fewest, inside)
    assert listed == (empty == 0), f"{cell}: {empty} of {windows} windows of {TRACE_STEPS} steps hold no pull"
    assert listed == (cell == "lfm2-24b-a2b-ep8-l5.fed4k") and (not listed or fewest >= 3)

"""The document generator and the packer of the `fed` mix."""

import os
import sys

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, REPO)

from benchmark.harness import traffic  # noqa: E402
from benchmark.harness.manifest import Manifest  # noqa: E402

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

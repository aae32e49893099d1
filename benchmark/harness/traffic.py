"""The one traffic generator. A mix is a data file, `benchmark/traffic/<mix>.json`;
this file turns its `documents` parameters and `--seed` into token documents,
and packs documents into training rows. No jax: the parent and the dataset's
map tasks import it.

Documents: lengths log-normal (`median_tokens`, `sigma`), clipped to
[`min_tokens`, `max_tokens`]; token ids uniform over [0, `eot_id`), where
`eot_id` is the configuration's last published id (GPT-2: 50,256). The packer
joins documents with `eot_id` after each and cuts the stream into rows of
`row_tokens`; what is left of a block after its last whole row is dropped.
There is no attention mask at document boundaries: the model has none, and
GPT-2 was trained that way.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List

import numpy as np


def document_lengths(spec: Dict[str, Any], n: int, rng: np.random.Generator) -> np.ndarray:
    raw = rng.lognormal(mean=math.log(spec["median_tokens"]), sigma=spec["sigma"], size=n)
    return np.clip(np.rint(raw), spec["min_tokens"], spec["max_tokens"]).astype(np.int64)


def mean_document_tokens(spec: Dict[str, Any]) -> float:
    """Mean of the unclipped log-normal plus the end-of-text id: an upper
    estimate of tokens per document, used only to size the supply."""
    return spec["median_tokens"] * math.exp(spec["sigma"] ** 2 / 2) + 1


def make_document_blocks(spec: Dict[str, Any], seed: int, total_tokens: int,
                         block_rows: int, row_tokens: int, eot_id: int) -> List[Any]:
    """Arrow tables with one `tokens: list<int32>` row per document, each
    holding about `block_rows` rows' worth of tokens, at least `total_tokens`
    in all. Same seed, same blocks."""
    import pyarrow as pa

    rng = np.random.default_rng(seed)
    per_block = block_rows * row_tokens
    tables = []
    made = 0
    while made < total_tokens:
        # Draw lengths until the block holds its rows (the end-of-text id
        # after each document counts).
        guess = max(8, int(1.2 * per_block / mean_document_tokens(spec)))
        lengths = document_lengths(spec, guess, rng)
        while int(lengths.sum()) + len(lengths) < per_block:
            lengths = np.concatenate([lengths, document_lengths(spec, guess, rng)])
        keep = int(np.searchsorted(np.cumsum(lengths + 1), per_block)) + 1
        lengths = lengths[:keep]
        values = rng.integers(0, eot_id, int(lengths.sum()), dtype=np.int32)
        offsets = np.concatenate([[0], np.cumsum(lengths)]).astype(np.int32)
        tables.append(pa.table({"tokens": pa.ListArray.from_arrays(offsets, values)}))
        made += int(lengths.sum()) + len(lengths)
    return tables


def pack_documents(batch, *, row_tokens: int, eot_id: int) -> Dict[str, np.ndarray]:
    """A `map_batches(batch_format="pyarrow")` stage: documents in, rows of
    `row_tokens` token ids out."""
    column = batch.column("tokens").combine_chunks()
    values = column.values.to_numpy(zero_copy_only=False)
    offsets = column.offsets.to_numpy(zero_copy_only=False).astype(np.int64)
    lengths = np.diff(offsets)
    values = values[offsets[0]:offsets[-1]]
    stream = np.full(len(values) + len(lengths), eot_id, np.int32)
    # Document i's tokens land after the i end-of-text ids before it.
    stream[np.arange(len(values)) + np.repeat(np.arange(len(lengths)), lengths)] = values
    rows = len(stream) // row_tokens
    return {"tokens": stream[: rows * row_tokens].reshape(rows, row_tokens)}


def resident_batch(vocab_ids: int, rows: int, row_tokens: int, seed: int) -> np.ndarray:
    """The one seeded batch of a `resident` mix: uniform ids over [0, vocab_ids)."""
    return np.random.default_rng(seed).integers(
        0, vocab_ids, (rows, row_tokens), dtype=np.int32)

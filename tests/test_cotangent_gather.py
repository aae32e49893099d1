"""The cotangent's gather of a held prefix through `ops/sum_rows.py gather_rows` (`models/moe.py _rows_by`, PR 72)
against XLA's gather of the same rows: the layer of `tests/test_moe.py _share_at_kernel_shapes`, a file of its own so
that its two interpret-mode runs are no part of that file's time."""

import numpy as np
import pytest

from test_moe import WEIGHTS, _share_at_kernel_shapes


@pytest.mark.parametrize("what", range(6), ids=("out", "x") + WEIGHTS)
def test_the_gradients_through_the_cotangents_kernel_are_xlas_bit_for_bit_and_no_unowned_row_is_read(what):
    """PR 72: a cotangent that has no room in VMEM beside the layer's tokens is gathered by `gather_rows`. Its rows
    are the cotangent's rows times 1.0; behind the owned ones (and the zeros to the next row tile) it writes nothing,
    here NaN, where XLA's gather puts some token's row: the grouped matmuls' backward rule under `short=True` and
    `_held_rows`' selects read neither, so every gradient is the one through XLA's gather, to the bit."""
    (got, aux), (want, xla) = _share_at_kernel_shapes(True, True), _share_at_kernel_shapes(True)
    assert aux["gathered_by_the_kernel"] == [(512, 128)] and xla["gathered_by_the_kernel"] == []
    assert bool(aux["compact"]) and np.abs(want[what]).max() > 1e-3 and np.isfinite(got[what]).all()
    np.testing.assert_array_equal(got[what], want[what])

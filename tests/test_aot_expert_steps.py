"""The expert cells' whole train steps, compiled ahead of time for a `v5e:2x2`.

`tests/aot_v5e.py` compiles each cell's step in a process of its own when a
test first reads it; what it reports is pinned here: the OLMoE step is the
pinned program (same instruction count, same `memory_analysis()`, the same
Mosaic calls), and the expert layer moves its `tokens * k` sorted rows only to
permute them or, in a kernel, to sum them, and its `tokens * k` scalars through
sorts and compares alone: no gather or scatter of single elements under
`router`, `dispatch` or `combine`, in the OLMoE step and in both branches of
every layer of the LFM2 step. (Until PR 46 these tests stood in
`tests/test_model_scopes.py`, behind one fixture that compiled five steps.)
"""

import pytest

import aot_v5e
from benchmark.harness.program_trace import phase

LFM2 = "lfm2-24b-a2b-ep8-l5"
# This tree's program (ahead-of-time compile for v5e:2x2 on this installation): instructions
# of the compiled text and `memory_analysis()`. A PR that means to change neither sees it here.
PARENT = {
    # Pinned at PR 33. PR 32 (the router's weight inside SwiGLU's fusion) left 5,324 instructions /
    # 3,740,107,776 B; since PR 33 `gmm_fwd` / `gmm_dlhs` copy their groups' matrices themselves and take
    # two more scalar arrays for it (`grouped_matmul._matrix_slots`: a cumsum, a reverse cummin and
    # what XLA makes of them, for each of the six calls): + 85 instructions, + 387,072 B (0.0004 GiB)
    # of temporaries, 5,409 / 3,740,494,848 B. Pinned again at PR 34: the two `rows[inverse]` gathers
    # and the two sums of a token's 8 rows are two calls of the `sum_rows` kernel; what it walks
    # (`ops/sum_rows.py sorted_runs`, a dozen small operations on the routing, once a step) and the
    # two `(k, tokens)` transposes of `inverse` are + 195 instructions and + 781,312 B (0.0007 GiB)
    # of temporaries: the 268 MB token-order copy is gone, but the step's peak is not where it was.
    # Pinned again at PR 38: the pairs' scalars ride two sorts and a compare (`element_moves` below);
    # two gathers, a scatter and a scatter-add of 65,536 elements and what fed them are gone:
    # - 25 instructions, - 487,936 B of temporaries.
    # Pinned again at PR 64, on purpose: the three matrices' bf16 copies are arguments (+ 805,306,368 B, and the
    # same of outputs aliased to them) and no temporary any more (- 805,403,136 B), the three casts gone from
    # `experts` and one more output in each of the three AdamW fusions: - 9 instructions; XLA's peak is the
    # parent's to the byte (11,236,138,496).
    # Pinned again at PR 68, on purpose: the loss's gather, its gradient's scatter and what fed them are a compare and a
    # sum inside fusions that were there: - 86 instructions, temporaries and peak to the byte.
    "olmoe-1b-7b-l1": {"instructions": 5484, "argument": 8312743936, "temp": 2935385088,
                       "output": 8312712192, "alias": 8312710144},
}
# What the cell's step hands to Mosaic: the tile schedule its two flash kernels run under
# (head_dim 128 at 4,096), and the expert layer's kernels
# beside them: the grouped matmuls (three products, each forward and for both gradients) and,
# since PR 34, `sum_rows` (`combine` forward, and backward as the gradient of `dispatch`).
KERNELS = {
    "olmoe-1b-7b-l1": {"tiles": "tiles_36of64",
                       "moe": {"gmm_fwd": 3, "gmm_dlhs": 3, "gmm_drhs": 3, "sum_rows": 2}},
}
# The expert cells, and the gathers of whole rows by `order // k` that each one's step keeps under
# `dispatch` / `combine` (tokens into expert order, the results' gradient likewise). OLMoE: one layer,
# one form. LFM2: four layers, each with the whole-length form (131,072 rows) and the prefix form
# (32,768) behind `lax.cond`; in a form, the forward pass's gather and, in the backward `cond`, the
# forward pass again and the gradient's: (1 + 2) x 2 forms x 4 layers, until PR 40. Since then the
# prefix form's three a layer are calls of the `gather_rows` kernel (`PREFIX_KERNELS`) and the
# whole-length form's stay XLA's: 3 x 4. Its program is pinned by nothing else here (57 s of compile:
# `PARENT`'s three take 120).
ROW_GATHERS = {"olmoe-1b-7b-l1": 2, "lfm2-24b-a2b-ep8-l5": 12}
# The row movers of the LFM2 step's prefix form, `jit(_prefix_or_whole)/cond/branch_1_fun`, by (phase,
# the scope of `moe_mlp` they stand in, under `jvp(sorted_form)`: the forward pass made again inside
# the backward `cond`): four layers of each. `moe.dispatch_ms` reads both kernels through these scopes.
PREFIX_KERNELS = {
    "gather_rows": [("backward", "combine", False), ("backward", "dispatch", True), ("forward", "dispatch", False)],
    "sum_rows": [("backward", "dispatch", False), ("forward", "combine", False)],
}


@pytest.fixture(scope="module")
def aot():
    return aot_v5e.steps(*PARENT, LFM2)


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_the_v5e_program_is_the_pinned_one_and_needs_no_more_memory(aot, cell):
    aot_v5e.is_the_pinned_program(aot(cell), cell, PARENT[cell], PARENT[cell]["temp"])


@pytest.mark.parametrize("cell", sorted(PARENT))
def test_one_kernel_under_flash_fwd_one_under_flash_bwd_and_all_phases(aot, cell):
    aot_v5e.has_one_flash_kernel_a_pass_and_all_phases(aot(cell), KERNELS[cell])


@pytest.mark.parametrize("what, count", [
    ("gather", 2), ("reduce_sum", 0), ("pallas_call", 2), ("anything else", 0),
    ("backward scatter-add", 0)])
def test_the_sorted_rows_are_only_permuted_and_summed(aot, what, count):
    """The 65,536 x 2,048 sorted rows of the OLMoE cell cross memory under
    `dispatch` / `combine` in two gathers (tokens into expert order, and the
    gradient of the results likewise) and two calls of the `sum_rows` kernel
    (the results' sums per token, and the gradient of the tokens), and in
    nothing else. Until PR 34 the sums were a gather by `inverse` that wrote
    the 65,536 rows in token order and a `reduce_sum` over a token's 8 that
    read them again: four gathers, two `reduce_sum`. The router's weight is
    applied where SwiGLU's output is written (`models/moe.py`), so no pass
    exists for the weighting, and the weight's gradient goes back to
    `(tokens, k)` as the payload of a sort (until PR 38 by a gather), not by a
    scatter-add of 65,536 updates. Before
    PR 32: a `convert_element_type` pass forward, `reduce_sum` three times,
    one `scatter-add`."""
    moved, scatter_adds = (aot("olmoe-1b-7b-l1")[key]
                           for key in ("sorted_rows_moved", "backward_scatter_adds"))
    got = {"anything else": [n for kind, names in moved.items()
                             if kind not in ("gather", "reduce_sum", "pallas_call") for n in names],
           "backward scatter-add": scatter_adds}.get(what, moved.get(what, []))
    assert len(got) == count, (what, moved, scatter_adds)


def test_the_lfm2_step_makes_nothing_again_that_it_kept_before(aot):
    """XLA keeps most of what `save_attn` says to make again in this step (PR
    36), and what tips it back is not the step's memory: three small index
    tables among what the expert layer keeps for its backward pass made it
    recompute three layers' routers, sorts and short convolutions, 16 ms of
    440 on the chip (1,427 instructions under `rematted_computation` for the
    142 that the dense layer's and the attention layer's recomputation hold;
    PERF.md section 6, PR 40). A PR that changes what a layer keeps sees it here.
    Pinned again at PR 62, on purpose: the short convolutions' `conv_mix` keeps
    `bcu` alone, so the 15 instructions that made its chain again are gone (127)."""
    assert aot(LFM2)["recomputed"] <= 127


# What this reader finds outside `optimizer` in the parent's step (PR 63's tree, the same compile): OLMoE's three
# forward casts; LFM2's twelve matrices cast forward and again backward in both forms of a layer.
PARENTS_CASTS = {"olmoe-1b-7b-l1": 3, LFM2: 48}


@pytest.mark.parametrize("cell", sorted(PARENTS_CASTS))
def test_no_pass_rounds_an_expert_matrix_outside_the_optimizer(aot, cell):
    aot_v5e.rounds_the_experts_matrices_in_the_optimizer_alone(aot(cell), PARENTS_CASTS[cell])


# The LFM2 step's XLA peak before PR 62 (`memory_analysis().peak_memory_in_bytes`, this installation): the
# float32 z = b u and the taps' sum were residuals of `conv_mix`, a 268 MB array each a conv layer.
LFM2_PEAK_AT_PR61 = 13_512_126_464


def test_the_short_convolutions_mix_is_one_pass_forward_and_one_kernel_backward(aot):
    """PR 62. Under `conv_mix` (what `conv.mix_ms` and `conv.mix_roofline` read) the step holds four calls of
    `gated_conv_bwd`, one a conv layer, all in the backward pass and none under a remat; forward a layer's chain is
    one fusion that reads `bcu` and writes y in bf16, so nothing of a layer's size is written in float32 in any
    phase (until PR 62: the gate `b u` and the taps' sum forward, four of the latter cloned by XLA's own
    rematerialization, and four more in the gradient). XLA clones no `multiply_add_fusion` and one product
    fewer (`remat_products` 7 until then), and the peak is under the parent's.
    Pinned again at PR 64, on purpose: the experts' compute copy is 603,979,776 B more of arguments in a step that
    XLA unrolls (no stacked gradient to pay it back), its peak rises by 101,188,096 B (13,479,096,832, still under
    PR 61's) and the rematerialization clones a fourth short convolution's in-projection (`fusion.588.remat`,
    `bf16[8,4096,6144]`) beside the three it cloned: `remat_products` 7 and eight clones (PERF.md section 6, PR 64)."""
    got = aot(LFM2)
    calls = [n.split("/") for n in got["mosaic_scopes"] if n.split("/")[-2] == "gated_conv_bwd"]
    assert len(calls) == 4
    for parts in calls:
        assert parts[-4:-2] == ["tile_512", "rows_4096"] and "conv_mix" in parts and "short_conv" in parts, parts
        assert phase("/".join(parts)) == "backward" and "rematted_computation" not in parts
    assert got["conv_mix_f32"] == {}
    assert not [n for n in got["remat_clones"] if n.startswith("multiply_add_fusion")], got["remat_clones"]
    assert got["remat_products"] <= 7 and len(got["remat_clones"]) <= 8
    assert got["peak"] is not None and got["peak"] <= LFM2_PEAK_AT_PR61 - (30 << 20)


@pytest.mark.parametrize("kernel", sorted(PREFIX_KERNELS))
def test_the_prefix_form_moves_its_rows_through_the_two_kernels(aot, kernel):
    """Where a layer holds some of the experts, the branch that runs over the
    held prefix gathers its tokens by `gather_rows` (forward, forward again in
    the backward `cond`, and as `combine`'s transpose) and sums by `sum_rows`
    (`combine`, and `dispatch`'s transpose): no XLA gather of rows is left in
    it (`ROW_GATHERS` counts the whole-length branch's three a layer), and the
    whole-length branch calls no `gather_rows`."""
    where, elsewhere = aot_v5e.prefix_form_calls(aot(LFM2), kernel)
    assert where == sorted(PREFIX_KERNELS[kernel] * 4), where
    assert elsewhere == {"gather_rows": 0, "sum_rows": 8}[kernel]


@pytest.mark.parametrize("cell", sorted(ROW_GATHERS))
@pytest.mark.parametrize("what", ("scalars", "rows"))
def test_no_scalar_of_the_pairs_is_gathered_or_scattered(aot, cell, what):
    """Under `router`, `dispatch` and `combine` no instruction of the step
    gathers or scatters single elements: the `tokens * k` weights, `inverse`,
    the sorted ids and the router's picked scores go through sorts and through
    compares against an iota of E, and `counts` is a sum of those compares
    (`models/moe.py`, PR 38). Until then the OLMoE step held two `f32[65536]`
    gathers, the scatter that built `inverse` and the scatter-add of `counts`;
    the LFM2 step, a layer, the `take_along_axis` gather and the same four in
    both branches. The gathers of whole rows keep their count."""
    got = aot(cell)["element_moves"]
    assert len(got[what]) == {"scalars": 0, "rows": ROW_GATHERS[cell]}[what], got

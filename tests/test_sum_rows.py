"""The `sum_rows` kernel of `ops/sum_rows.py` in interpret mode against its XLA
form (a gather by `inverse`, a float32 sum of each token's k rows): every k and
routing, the pieces `sorted_runs` hands it, what it does with rows that are not
a token's own, the rows its copies move, and `moe_mlp`'s gradients through it."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.models import moe
from ray_tpu.ops import sum_rows as sr

TOKENS, WIDTH, EXPERTS = 3 * sr.BLOCK, 128, 8


def _routing(name, k, tokens=TOKENS, seed=0):
    """(tokens, k) int32: each token's experts (the kernel does not ask that they differ)."""
    rng = np.random.default_rng(seed)
    if name == "even":
        experts = rng.integers(0, EXPERTS, (tokens, k))
    elif name == "skewed":  # half of all pairs on expert 2
        experts = np.where(rng.random((tokens, k)) < 0.5, 2, rng.integers(0, EXPERTS, (tokens, k)))
    elif name == "an_expert_with_no_pair":  # 3 and the last get none; 0 gets one pair
        experts = rng.choice([1, 2, 4, 5, 6], (tokens, k))
        experts[tokens // 2, 0] = 0
    elif name == "every_pair_on_one_expert":
        experts = np.full((tokens, k), 5)
    return experts.astype(np.int32)


def _order(experts):
    """(order, inverse) of `models/moe.py expert_order`, which sorts the pairs' weights along."""
    return moe.expert_order(experts, jnp.zeros(experts.shape, jnp.float32))[1:3]


def _operands(routing, k, dtype, tokens=TOKENS, width=WIDTH):
    experts = jnp.asarray(_routing(routing, k, tokens))
    _, inverse = _order(experts)
    rows = jax.random.normal(jax.random.PRNGKey(k), (tokens * k, width), jnp.float32).astype(dtype)
    return rows, inverse, experts


ROUTINGS = ("even", "skewed", "an_expert_with_no_pair", "every_pair_on_one_expert")


@pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32), ids=("bf16", "f32"))
@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("k", (1, 2, 8))
def test_the_kernel_sums_what_the_xla_form_sums(k, routing, dtype):
    rows, inverse, experts = _operands(routing, k, dtype)
    got = sr.sum_rows(rows, inverse, sr.sorted_runs(experts, EXPERTS), k, backend="pallas", interpret=True)
    want = sr.xla_sum_rows(rows, inverse, k)
    assert got.dtype == dtype and got.shape == (TOKENS, WIDTH)
    # The same k float32 terms, added in another order: the last bit of the rounded sum may differ.
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               rtol=2 ** -7 if dtype == jnp.bfloat16 else 1e-6, atol=1e-6)
    if k == 1:  # a permutation: nothing is added
        np.testing.assert_array_equal(np.asarray(got, np.float32), np.asarray(want, np.float32))


@pytest.mark.parametrize("routing", ROUTINGS)
@pytest.mark.parametrize("k", (1, 2, 8))
def test_the_pieces_cover_each_blocks_rows_once_and_rows_read_counts_them(k, routing):
    experts = _routing(routing, k)
    runs = sr.sorted_runs(jnp.asarray(experts), EXPERTS)
    count, tile, lo, hi = (np.asarray(a) for a in runs[:4])
    _, inverse = _order(jnp.asarray(experts))
    inverse = np.asarray(inverse).reshape(TOKENS // sr.BLOCK, sr.BLOCK * k)
    per_block = tile.shape[0] // count.shape[0]
    brute_force = 0
    for b, n in enumerate(count):
        at = slice(b * per_block, (b + 1) * per_block)
        assert not tile[at][n:].any() and not lo[at][n:].any() and not hi[at][n:].any()
        owned = np.concatenate([t * sr.PIECE + np.arange(l, h)
                                for t, l, h in zip(tile[at][:n], lo[at][:n], hi[at][:n])])
        assert sorted(owned) == sorted(inverse[b])  # every row of the block, none twice
        by_expert = experts[b * sr.BLOCK:(b + 1) * sr.BLOCK].reshape(-1)
        brute_force += sum(len(set(inverse[b][by_expert == e] // sr.PIECE)) for e in range(EXPERTS))
    assert sr.rows_read(experts, sr.PIECE) == brute_force * sr.PIECE == count.sum() * sr.PIECE
    assert TOKENS * k <= brute_force * sr.PIECE <= TOKENS * k + 2 * sr.PIECE * count.shape[0] * EXPERTS
    # At the largest chunk the kernel reads whole chunks: a block's last one is filled up from tile 0.
    # At a smaller one (k = 1: a block's 128 rows) it copies the block's own pieces and no other.
    chunk = sr.chunk_rows(WIDTH, 2, k, TOKENS * k, TOKENS * k)
    assert chunk == (256 if k == 1 else 512)
    assert sr.rows_read(experts, chunk) == sum(-(-n * sr.PIECE // chunk) * chunk if k > 1 else n * sr.PIECE for n in count)


@pytest.mark.parametrize("poisoned", ("rows_of_other_blocks", "the_stage_before_the_call"))
@pytest.mark.parametrize("k", (2, 8))
def test_what_is_not_a_tokens_own_adds_nothing(k, poisoned):
    """A copy moves whole `PIECE`-row tiles, so a block's stage holds rows of
    other blocks; and what a stage held before must not matter either. Neither
    may reach a sum, not even as `0 x NaN`."""
    rows, inverse, experts = _operands("even", k, jnp.bfloat16)
    want = np.asarray(sr.xla_sum_rows(rows, inverse, k), np.float32)
    runs = sr.sorted_runs(experts, EXPERTS)
    if poisoned == "rows_of_other_blocks":
        own = np.zeros(TOKENS * k, bool)
        own[np.asarray(inverse)[sr.BLOCK * k:2 * sr.BLOCK * k]] = True  # the second block's rows
        rows = jnp.where(own[:, None], rows, jnp.nan)
        got = sr.sum_rows(rows, inverse, runs, k, backend="pallas", interpret=True)
        got, want = (np.asarray(a, np.float32)[sr.BLOCK:2 * sr.BLOCK] for a in (got, want))
    else:  # TPU interpret mode: scratch memory starts as NaN
        got = sr.sum_rows(rows, inverse, runs, k, backend="pallas",
                          interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
        got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    np.testing.assert_allclose(got, want, rtol=2 ** -7)


@pytest.mark.parametrize("tokens, width", [(sr.BLOCK + 8, 128), (sr.BLOCK, 64), (sr.BLOCK, 192)], ids=(
    "tokens_not_in_whole_blocks", "a_width_under_128", "a_width_of_no_whole_lane_tiles"))
def test_shapes_that_do_not_tile_take_the_xla_form(tokens, width):
    rows, inverse, experts = _operands("even", 2, jnp.bfloat16, tokens, width)
    runs = sr.sorted_runs(experts, EXPERTS)
    assert (runs is None) == (tokens % sr.BLOCK != 0)
    got = sr.sum_rows(rows, inverse, runs, 2)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(sr.xla_sum_rows(rows, inverse, 2), np.float32))
    with pytest.raises(ValueError, match="does not tile"):
        sr.sum_rows(rows, inverse, runs, 2, backend="pallas")


@functools.lru_cache(maxsize=None)
def _moe_gradients(k, through_the_kernel):
    """`moe_mlp`'s output and gradients (input and every weight) with `combine`
    and `dispatch`'s gradient through the kernel, or through the XLA form."""
    d, f = 128, 64
    keys = jax.random.split(jax.random.PRNGKey(k), 6)
    x = jax.random.normal(keys[0], (2, sr.BLOCK, d))
    cotangent = jax.random.normal(keys[1], x.shape)
    weights = (jax.random.normal(keys[2], (d, EXPERTS)), jax.random.normal(keys[3], (EXPERTS, d, f)) / d ** 0.5,
               jax.random.normal(keys[4], (EXPERTS, d, f)) / d ** 0.5,
               jax.random.normal(keys[5], (EXPERTS, f, d)) / f ** 0.5)
    backend = dict(backend="pallas", interpret=True) if through_the_kernel else dict(backend="xla")
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "sum_rows", functools.partial(sr.sum_rows, **backend))
        out, vjp = jax.vjp(lambda x, *w: moe.moe_mlp(x, *w, k=k)[0], x, *weights)
        return [np.asarray(a) for a in (out, *vjp(cotangent))]


@pytest.mark.parametrize("what", range(6), ids=("out", "x", "router_w", "w_gate", "w_up", "w_down"))
@pytest.mark.parametrize("k", (1, 8))
def test_moe_mlp_and_its_gradients_are_the_same_through_the_kernel(k, what):
    got, want = _moe_gradients(k, True)[what], _moe_gradients(k, False)[what]
    assert np.abs(want).max() > 1e-3
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# ------------------------------------------- choices that are nobody's (experts held elsewhere)
@pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32), ids=("bf16", "f32"))
@pytest.mark.parametrize("k", (2, 4))
def test_rows_of_experts_held_elsewhere_are_never_read_and_a_block_may_own_none(k, dtype):
    """An expert layer that holds 2 of 8 experts gives every other choice the
    id 2 (`models/moe.py`): those pairs sort behind the held groups and have no
    run. Their rows hold NaN here, and no sum sees one. The second block of
    tokens owns no row at all: it still gets a chunk (of nobody's rows), so that
    the block before it has something to start and it has something to wait for."""
    held = 2
    rng = np.random.default_rng(k)
    experts = rng.integers(0, 8, (TOKENS, k))
    experts[sr.BLOCK:2 * sr.BLOCK] = rng.integers(held, 8, (sr.BLOCK, k))  # none of the held ones
    local = jnp.asarray(np.where(experts < held, experts, held).astype(np.int32))
    order, inverse = _order(local)
    n_held = int((np.asarray(local) < held).sum())
    rows = jax.random.normal(jax.random.PRNGKey(0), (TOKENS * k, WIDTH), jnp.float32).astype(dtype)
    rows = jnp.where((jnp.arange(TOKENS * k) < n_held)[:, None], rows, jnp.nan)
    runs = sr.sorted_runs(local, held, True)
    count = np.asarray(runs.count)
    assert count[1] == 1 and not np.asarray(runs.hi).reshape(3, -1)[1].any()  # a piece that owns no row
    assert count.min() >= 1 and sr.sorted_runs(local, held).count[1] == 0
    got = sr.sum_rows(rows, inverse, runs, k, backend="pallas", interpret=True)
    mine = (np.asarray(local) < held)[..., None]
    by_token = np.asarray(rows, np.float32)[np.asarray(inverse)].reshape(TOKENS, k, WIDTH)
    want = np.where(mine, by_token, 0.0).sum(axis=1)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    assert not np.asarray(got, np.float32)[sr.BLOCK:2 * sr.BLOCK].any()
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               rtol=2 ** -7 if dtype == jnp.bfloat16 else 1e-6, atol=1e-6)


@pytest.mark.parametrize("form", ("kernel", "xla"))
@pytest.mark.parametrize("k", (2, 4))
def test_a_prefix_that_holds_every_held_row_sums_to_what_all_the_rows_do(k, form):
    """The held pairs sort first, so a layer may hand over the first rows of
    the sorted form alone, with `inverse` whole (`models/moe.py`: the prefix
    form). The kernel is pointed only at held rows and reads what the runs
    say; the XLA form gathers by every position, and one past the array's end
    adds nothing."""
    held = 2
    experts = np.random.default_rng(k).integers(0, 8, (TOKENS, k))
    local = jnp.asarray(np.where(experts < held, experts, held).astype(np.int32))
    _, inverse = _order(local)
    n_held = int((np.asarray(local) < held).sum())
    prefix = -(-n_held // sr.BLOCK) * sr.BLOCK
    assert n_held < prefix < TOKENS * k
    rows = jax.random.normal(jax.random.PRNGKey(1), (TOKENS * k, WIDTH), jnp.float32)
    rows = jnp.where((jnp.arange(TOKENS * k) < n_held)[:, None], rows, 0.0)  # as `moe_mlp` masks them
    runs = sr.sorted_runs(local, held, True)
    backend = dict(backend="pallas", interpret=True) if form == "kernel" else dict(backend="xla")
    got = sr.sum_rows(rows[:prefix], inverse, runs, k, **backend)
    want = sr.sum_rows(rows, inverse, runs, k, **backend)
    assert got.shape == (TOKENS, WIDTH) and np.abs(np.asarray(want)).max() > 1
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    # The gradient of the XLA form gives a position past the prefix nothing to scatter.
    if form == "xla":
        g = jax.grad(lambda r: sr.xla_sum_rows(r, inverse, k).sum())(rows[:prefix])
        np.testing.assert_array_equal(np.asarray(g), np.ones((prefix, WIDTH), np.float32))


# ------------------------------------------- gather_rows: the prefix form's tokens in expert order
G_EXPERTS, G_HELD, G_TOKENS = 24, 3, 4 * sr.BLOCK  # an eighth of the experts held, as the cells hold


def _held_routing(name, k, seed=0):
    """(tokens, k) int32 over `G_EXPERTS` experts, of which the first `G_HELD` are held."""
    rng = np.random.default_rng(seed)
    experts = np.argsort(rng.random((G_TOKENS, G_EXPERTS)), axis=1)[:, :k]  # k distinct a token
    if name == "one_held_expert_takes_twice_its_share":
        more = (rng.random(G_TOKENS) < 0.5 * k / G_EXPERTS / (1 - k / G_EXPERTS)) & ~(experts == 0).any(axis=1)
        experts[more, 0] = 0
    elif name == "a_block_of_tokens_with_no_held_pair":
        experts[sr.BLOCK:2 * sr.BLOCK] = rng.integers(G_HELD, G_EXPERTS, (sr.BLOCK, k))
    elif name == "runs_that_end_on_a_tile_edge":  # a block's runs are 8 and 16 rows: every tile is one run's
        experts = rng.integers(G_HELD, G_EXPERTS, (G_TOKENS, k))
        for b in range(G_TOKENS // sr.BLOCK):
            experts[b * sr.BLOCK:b * sr.BLOCK + 8, 0] = 0
            experts[b * sr.BLOCK + 8:b * sr.BLOCK + 24, 0] = 2
    elif name == "a_held_expert_with_no_row":  # the middle one: its neighbours' groups touch
        experts = np.where(experts == 1, G_EXPERTS - 1, experts)
    elif name == "groups_shorter_than_a_tile":  # three groups inside the first tile
        experts = rng.integers(G_HELD, G_EXPERTS, (G_TOKENS, k))
        experts[[3, 200, 300], 0] = 0
        experts[[5, 450], 1] = 1
        experts[[7, 130, 131, 480], 0] = 2
    return experts.astype(np.int32)


HELD_ROUTINGS = ("even", "one_held_expert_takes_twice_its_share", "a_block_of_tokens_with_no_held_pair",
                 "runs_that_end_on_a_tile_edge", "a_held_expert_with_no_row", "groups_shorter_than_a_tile")


def _prefix(routing, k, seed=0):
    """(local ids, order of the prefix, inverse, runs, owned rows) as `moe_mlp` makes them."""
    experts = _held_routing(routing, k, seed)
    local = jnp.asarray(np.where(experts < G_HELD, experts, G_HELD).astype(np.int32))
    order, inverse = _order(local)
    n = moe.held_row_bound(G_TOKENS * k, G_HELD, G_EXPERTS)
    owned = int((np.asarray(local) < G_HELD).sum())
    assert owned <= n < G_TOKENS * k
    return local, order[:n], inverse, sr.sorted_runs(local, G_HELD, True), owned


@pytest.mark.parametrize("dtype", (jnp.bfloat16, jnp.float32), ids=("bf16", "f32"))
@pytest.mark.parametrize("routing", HELD_ROUTINGS)
@pytest.mark.parametrize("k", (4, 8))
def test_gather_rows_writes_every_owned_row_and_zeros_to_the_next_row_tile(k, routing, dtype):
    """Every owned row is its token's row, bit for bit (one row times 1.0, the
    rest times exact zeros); behind them zeros up to a multiple of `ZEROED`,
    so that a kernel that loads a row tile across the last group's end
    multiplies nothing that is not a number. The stage starts as NaN."""
    _, order, inverse, runs, owned = _prefix(routing, k)
    x = jax.random.normal(jax.random.PRNGKey(k), (G_TOKENS, WIDTH), jnp.float32).astype(dtype)
    got = sr.gather_rows(x, order, inverse, runs, k, backend="pallas",
                         interpret=pltpu.InterpretParams(uninitialized_memory="nan"))
    assert got.dtype == dtype and got.shape == (order.shape[0], WIDTH)
    got, want = np.asarray(got, np.float32), np.asarray(x[order // k], np.float32)
    np.testing.assert_array_equal(got[:owned], want[:owned])
    assert not got[owned:min(-(-owned // sr.ZEROED) * sr.ZEROED, order.shape[0])].any()


def test_gather_rows_off_the_tpu_and_untiled_is_the_xla_gather():
    _, order, inverse, runs, _ = _prefix("even", 4)
    x = jax.random.normal(jax.random.PRNGKey(0), (G_TOKENS, WIDTH), jnp.float32)
    np.testing.assert_array_equal(np.asarray(sr.gather_rows(x, order, inverse, runs, 4)), np.asarray(x[order // 4]))
    np.testing.assert_array_equal(np.asarray(sr.gather_rows(x[:, :64], order, inverse, runs, 4)),
                                  np.asarray(x[:, :64][order // 4]))
    with pytest.raises(ValueError, match="does not tile"):
        sr.gather_rows(x[:, :64], order, inverse, runs, 4, backend="pallas")


@pytest.mark.parametrize("which", ("gather", "sum"))
@pytest.mark.parametrize("k", (4, 8))
def test_the_pair_of_kernels_are_each_others_transposes_as_jax_derives_them(k, which):
    """`_gather_rows`' gradient is `_sum_rows` and the other way round
    (`models/moe.py`), both through the kernels here: against the transposes
    jax derives for the plain gather and the plain gather-and-sum, over the
    owned rows (a cotangent is zeros behind them, as `moe_mlp`'s masks make it)."""
    _, order, inverse, runs, owned = _prefix("even", k)
    n = order.shape[0]
    is_owned = (jnp.arange(n) < owned)[:, None]
    keys = jax.random.split(jax.random.PRNGKey(k), 3)
    x = jax.random.normal(keys[0], (G_TOKENS, WIDTH), jnp.float32)
    rows = jnp.where(is_owned, jax.random.normal(keys[1], (n, WIDTH), jnp.float32), 0)
    kernels = dict(backend="pallas", interpret=True)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "sum_rows", functools.partial(sr.sum_rows, **kernels))
        patch.setattr(moe, "gather_rows", functools.partial(sr.gather_rows, **kernels))
        if which == "gather":
            got = jax.vjp(lambda x: moe._gather_rows(x, order, inverse, runs, k), x)[1](rows)[0]
            want = jax.vjp(lambda x: x[order // k], x)[1](rows)[0]
        else:
            g = jax.random.normal(keys[2], (G_TOKENS, WIDTH), jnp.float32)
            got = jax.vjp(lambda r: moe._sum_rows(r, order, inverse, runs, k), rows)[1](g)[0]
            want = jax.vjp(lambda r: sr.xla_sum_rows(r, inverse, k), rows)[1](g)[0]
            got, want = got[:owned], want[:owned]
    assert np.abs(np.asarray(want)).max() > 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("cell, k, rows, pairs, chunk", [
    ("olmoe-1b-7b-l1", 8, 65536, 65536, 512), ("lfm2-24b-a2b-ep8-l5", 4, 32768, 131072, 256),
    ("glm-4.7-flash-ep8-l5", 4, 8192, 32768, 256), ("lfm2-24b-a2b-ep8-l5, every pair", 4, 131072, 131072, 512)])
def test_a_chunk_is_sized_by_the_share_of_the_pairs_the_rows_hold(cell, k, rows, pairs, chunk):
    assert sr.chunk_rows(2048, 2, k, rows, pairs) == chunk
    assert sr.chunk_rows(2048, 4, k, rows, pairs) == min(chunk, 256)  # float32: 512 rows do not fit a slot
    assert sr.chunk_rows(1 << 20, 2, k, rows, pairs) is None


def test_the_rows_moved_for_a_layer_that_holds_an_eighth_of_the_experts():
    """At the cells' geometry (4 of 64 experts a token, 8 held) a block of
    tokens owns 64 rows in 8 runs: `sum_rows` at 256-row chunks reads the
    tiles of its runs, under half of the 512 rows a block it read before, and
    `gather_rows` writes every tile once."""
    experts = np.argsort(np.random.default_rng(0).random((16 * sr.BLOCK, 64)), axis=1)[:, :4]
    owned = int((experts < 8).sum())
    at_512, at_256, tiles = (sr.rows_read(experts, c, 8) for c in (512, 256, sr.PIECE))
    assert owned < at_256 == tiles < 2.2 * owned and at_512 == 16 * 512 > 3.5 * owned
    runs = sum(len(np.unique(b[b < 8])) for b in experts.reshape(16, -1))
    assert owned <= sr.rows_written(experts, 8) <= owned + sr.PIECE * runs + sr.ZEROED
    assert sr.rows_written(experts, 8) - (-(-owned // sr.ZEROED) * sr.ZEROED - -(-owned // sr.PIECE) * sr.PIECE) <= tiles
    # Every pair owned: what `rows_read` counted before it learnt of a prefix.
    assert sr.rows_read(experts % 8) == sr.rows_read(experts % 8, 512, 8)


@pytest.mark.parametrize("tokens, rows, kernel", [
    (32768, 32768, True), (8192, 8192, False), (16384, 16384, False), (32768, 131072, False), (8192, 65536, False)],
    ids=("lfm2", "glm-4.7-flash", "half of lfm2", "lfm2, every pair", "olmoe"))
def test_which_gather_runs_is_read_off_the_shapes(tokens, rows, kernel):
    """`moe._rows_by`: the kernel for a prefix of the sort out of a source of
    128 MiB or more, XLA's gather for everything else."""
    called = []
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "gather_rows", lambda x, order, *_: called.append(order.shape) or x[order // 4])
        k = 8 if rows == 65536 else 4
        out = jax.eval_shape(lambda x, order, inverse: moe._rows_by(x, order, inverse, None, k),
                             *(jax.ShapeDtypeStruct(*s) for s in (((tokens, 2048), jnp.bfloat16), ((rows,), jnp.int32),
                                                                 ((tokens * k,), jnp.int32))))
    assert out.shape == (rows, 2048) and bool(called) == kernel


# ------------------------------------------- the cotangent's gather: beside the tokens, half of VMEM is the bound
def _kernels_by_scope(jaxpr, above=""):
    """(a `pallas_call`'s name, the named scopes it stands under), for every one in `jaxpr` and whatever it calls."""
    for eqn in jaxpr.eqns:
        here = f"{above}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            yield eqn.params["name"], here
        for value in eqn.params.values():
            for sub in value if isinstance(value, (tuple, list)) else [value]:
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _kernels_by_scope(sub, here)


@pytest.mark.parametrize("tokens, k, d, f, n_experts, held, scopes", [
    (16384, 6, 2560, 768, 64, 16, ["transpose(jvp(combine))"]), (16384, 8, 2048, 1024, 128, 8, []),
    (16384, 8, 2048, 768, 128, 16, []), (8192, 4, 2048, 1536, 64, 8, []),
    (32768, 4, 2048, 1536, 64, 8, ["jvp(dispatch)", "transpose(jvp(combine))"])],
    ids=("smallthinker", "trinity-mini", "sdar", "glm-4.7-flash", "lfm2"))
def test_a_cotangent_over_half_of_vmem_is_gathered_by_the_kernel_and_the_tokens_are_not(
        tokens, k, d, f, n_experts, held, scopes):
    """`moe._rows_by` under `_sorted_form` and its backward pass at the cells' shapes, traced from abstract
    operands (nothing is computed): SmallThinker's 80 MiB of tokens are XLA's gather forward (they fit VMEM) and
    the cotangent's, which has no room beside them, the kernel's; at 64 MiB and under both are XLA's (no shape says
    where XLA leaves SDAR's cotangent and where Trinity-Mini's: PERF.md section 7); LFM2's 128 MiB both the kernel's."""
    sd, pairs = jax.ShapeDtypeStruct, tokens * k
    n = moe.held_row_bound(pairs, held, n_experts)
    assert n < pairs
    operands = (sd((tokens, d), jnp.bfloat16), sd((pairs,), jnp.float32), sd((held, d, f), jnp.bfloat16),
                sd((held, d, f), jnp.bfloat16), sd((held, f, d), jnp.bfloat16))
    plan = (sd((held,), jnp.int32), *(sd((pairs,), jnp.int32),) * 3,
            jax.eval_shape(lambda experts: sr.sorted_runs(experts, held, True), sd((tokens, k), jnp.int32)))

    def both_passes(operands, plan, g):
        return jax.vjp(lambda *o: moe._sorted_form(n, k, True, jax.nn.silu, *o, *plan)[0], *operands)[1](g)

    kernels = list(_kernels_by_scope(jax.make_jaxpr(both_passes)(operands, plan, operands[0]).jaxpr))
    assert sorted(where.strip("/").split("/")[0] for name, where in kernels if name == "gather_rows") == scopes
    assert [name for name, _ in kernels].count("sum_rows") == 2  # `combine` forward, `dispatch`'s gradient

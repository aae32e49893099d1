"""Compile for the v5e without holding one.

libtpu is installed, so `jax.experimental.topologies.get_topology_desc` hands
out the devices of a `v5e:2x2` host under JAX_PLATFORMS=cpu and a jitted
function can be lowered and compiled for them. This is how the chip path is
checked on every PR from a sandbox with no chip: the Mosaic kernels must
compile, and the flagship step must lower on more than one device (XLA cannot
partition a Mosaic call; before the kernel ran inside a shard_map the
four-device step died here with "Mosaic kernels cannot be automatically
partitioned").

The cases run in one subprocess (`python tests/test_aot_v5e.py <cases>`): libtpu
start-up is kept out of the pytest process and its 8-device CPU backend.
"""

import json
import os
import re
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# memory_stats()["bytes_limit"] of one v5e chip (chip run, PR 21).
V5E_HBM_BYTES = 16_909_336_064
B, S = 16, 1024  # the flagship cell: gpt2_small, batch 16 x seq 1024
LONG_HEAD_64 = "kernel:8x32x4096x64"  # the lfm2 cell's attention layer: 8 rows, 32 heads of 4096 x 64
WIDE_HEAD_256 = "kernel:2x20x4096x256"  # the glm-4.7-flash cell's: 2 rows, 20 heads of 4096 x 256
LONGER_HEADS = ("kernel:2x32x8192x64", "kernel:1x16x8192x128")  # what failed in `flash_bwd` until PR 39
# Between the largest head the backward program holds whole (4096 x 128) and the largest the forward
# program holds (4096 x 256), and 2048 x 256, whose default tile (1024) failed in both kernels.
# The expert layers of the lfm2 and glm-4.7-flash cells: 8 x 4,096 and 2 x 4,096 tokens, 4 of 64 experts a
# token, 8 held: a prefix of 32,768 and of 8,192 sorted rows of 2,048.
ROW_MOVERS = ("row_movers:32768", "row_movers:8192")
# The Keye cell's attention (PR 42): one row, 32 query heads on 4 key/value heads of 16,384 x 128, a
# packed selection; and the two kernels of `ops/lightning_indexer.py` at its indexer's 16 heads of 64.
SELECTED_16K = "selected:1x32x4x16384x128"
# Heads above 2 MiB with as many key/value heads: the pair-streamed forward takes four of them a program (PR 44);
# and a llama-like 8 on 2 heads of 2,048 x 64, which streams pairs for its shared key/value heads alone.
STREAMED_HEADS = ("kernel:1x8x16384x128", "kernel:1x8x8192x256")
GROUPED_2K = "selected:2x8x2x2048x64"
INDEXER_16K = "indexer:1x32x4x16384x128x16x64x2048"
BETWEEN_HEADS = ("kernel:2x4x3072x256", "kernel:2x4x3584x256", "kernel:2x4x6144x128",
                 "kernel:2x4x7168x128", "kernel:2x4x2048x256")


def _kernel_case(topo, shape=(B, 12, S, 64)):
    """Forward + fused backward kernel at GPT-2 shapes (or `shape`), one device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import flash_attention, kernel_plan

    x = jax.ShapeDtypeStruct(
        shape, jnp.bfloat16,
        sharding=jax.sharding.SingleDeviceSharding(topo.devices[0]),
    )
    loss = lambda q, k, v: flash_attention(q, k, v, backend="pallas").astype(jnp.float32).sum()
    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(x, x, x).compile()
    return {"mosaic_calls": compiled.as_text().count("tpu_custom_call"),
            "plan": list(kernel_plan(shape))}


def _selected_case(topo, batch, heads, kv_heads, seq, d):
    """Both flash kernels a (Q tile, K tile) pair a program: grouped heads, K and V streamed, a `keep`."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.ops.flash_attention import KEEP_SPAN, _fwd_pairs_plan, flash_attention, kernel_plan

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    q, k = (jax.ShapeDtypeStruct((batch, h, seq, d), jnp.bfloat16, sharding=one) for h in (heads, kv_heads))
    keep = jax.ShapeDtypeStruct((batch, seq, -(-seq // KEEP_SPAN) * 128), jnp.int32, sharding=one)
    loss = lambda q, k, v, keep: flash_attention(q, k, v, backend="pallas", keep=keep).astype(jnp.float32).sum()
    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k, keep).compile().as_text()
    dense = jax.jit(jax.grad(lambda q, k, v: loss(q, k, v, None), argnums=(0, 1, 2))).lower(q, k, k).compile()
    return {"mosaic_calls": text.count("tpu_custom_call"), "mosaic_calls_without_keep": dense.as_text().count(
                "tpu_custom_call"),
            "plan": list(kernel_plan(q.shape, kv_heads=kv_heads, keep=True)),
            "forward": list(_fwd_pairs_plan(heads // kv_heads, heads, d, 2, kernel_plan(q.shape, kv_heads=kv_heads, keep=True))),
            "forward_scopes": sorted(set(re.findall(r"/(group_\d+)/flash_fwd/", text))),
            "kernels": sorted(set(re.findall(r"(flash_fwd|flash_bwd)[.\d]* = ", text)))}


def _indexer_case(topo, batch, heads, kv_heads, seq, d, index_heads, index_d, topk):
    """`select` and `index_loss` (with the gradient it keeps) at a cell's shapes."""
    import importlib

    import jax
    import jax.numpy as jnp

    li = importlib.import_module("ray_tpu.ops.lightning_indexer")
    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    sd = lambda shape, dtype=jnp.bfloat16: jax.ShapeDtypeStruct(shape, dtype, sharding=one)
    q_i, k_i, w = sd((batch, index_heads, seq, index_d)), sd((batch, seq, index_d)), sd((batch, seq, index_heads), jnp.float32)
    select = jax.jit(lambda q_i, k_i, w: li.select(q_i, k_i, w, topk, backend="pallas")).lower(q_i, k_i, w).compile()
    keep, lse_i = (sd(x.shape, x.dtype) for x in jax.eval_shape(lambda: li.select(q_i, k_i, w, topk, backend="xla")))
    loss = lambda q_i, k_i, w, q, k, lse, keep, lse_i: li.index_loss(q, k, lse, keep, q_i, k_i, w, lse_i, backend="pallas")
    index_loss = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q_i, k_i, w, sd((batch, heads, seq, d)), sd((batch, kv_heads, seq, d)), sd((batch, heads, seq), jnp.float32),
        keep, lse_i).compile()
    named = lambda compiled: sorted(set(re.findall(r"(select|index_loss)[.\d]* = ", compiled.as_text())))
    return {"select": named(select), "index_loss": named(index_loss), "keep": list(keep.shape),
            "index_loss_mosaic_calls": index_loss.as_text().count("tpu_custom_call")}


def _row_movers_case(topo, tokens, k=4, width=2048, n_experts=64, held=8):
    """`gather_rows` and `sum_rows` over the prefix of a layer that holds `held`
    of `n_experts` experts, at a cell's shapes, one device."""
    import jax
    import jax.numpy as jnp

    from ray_tpu.models import moe
    from ray_tpu.ops import sum_rows as sr

    one = jax.sharding.SingleDeviceSharding(topo.devices[0])
    n = moe.held_row_bound(tokens * k, held, n_experts)

    def both(x, experts, rows):
        _, order, inverse, _ = moe.expert_order(experts, jnp.zeros(experts.shape, jnp.float32))
        runs = sr.sorted_runs(experts, held, True)
        return (sr.gather_rows(x, order[:n], inverse, runs, k, backend="pallas"),
                sr.sum_rows(rows, inverse, runs, k, backend="pallas"))

    shapes = [jax.ShapeDtypeStruct(shape, dtype, sharding=one) for shape, dtype in (
        ((tokens, width), jnp.bfloat16), ((tokens, k), jnp.int32), ((n, width), jnp.bfloat16))]
    text = jax.jit(both).lower(*shapes).compile().as_text()
    return {"rows": n, "chunk_rows": [sr.chunk_rows(width, 2, k, n, tokens * k, times) for times in (2, 1)],
            "kernels": sorted(re.findall(r"(gather_rows|sum_rows)[.\d]* = ", text))}


def _lowered_step(topo, axes, cfg, rows, seq):
    """`make_train_step` for `cfg` over `axes`, lowered from abstract inputs laid
    out as `create_train_state` / `shard_batch` lay out real ones."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.models import default_optimizer, make_train_step
    from ray_tpu.models.training import TrainState, model_for, param_shardings
    from ray_tpu.parallel import MeshSpec, ShardingRules, batch_spec

    spec = MeshSpec(**axes)
    mesh = spec.build(topo.devices[: spec.num_devices])
    opt = default_optimizer(learning_rate=3e-4)
    shapes = jax.eval_shape(lambda: model_for(cfg).init_params(cfg, jax.random.PRNGKey(0)))
    shardings = param_shardings(cfg, mesh, ShardingRules())
    replicated = NamedSharding(mesh, P())
    by_shape = dict(zip(
        (s.shape for s in jax.tree.leaves(shapes)), jax.tree.leaves(shardings)))

    def abstract(s, sharding):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    state = TrainState(
        params=jax.tree.map(abstract, shapes, shardings),
        # Adam moments are laid out like their parameter; counters replicate.
        opt_state=jax.tree.map(
            lambda s: abstract(s, by_shape.get(s.shape, replicated)),
            jax.eval_shape(opt.init, shapes)),
        step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
    )
    batch = {"tokens": jax.ShapeDtypeStruct(
        (rows, seq + 1), jnp.int32, sharding=NamedSharding(mesh, batch_spec()))}
    return make_train_step(cfg, opt, mesh=mesh).lower(state, batch)


def _step_case(topo, axes, compile_it):
    """gpt2_small's step at the flagship cell's batch."""
    from ray_tpu.models import GPTConfig

    lowered = _lowered_step(topo, axes, GPTConfig.gpt2_small(), B, S)
    out = {"mosaic_calls": lowered.as_text().count("tpu_custom_call")}
    if compile_it:
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        out["mosaic_calls_compiled"] = compiled.as_text().count("tpu_custom_call")
        out["device_bytes"] = (
            mem.argument_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes + mem.temp_size_in_bytes
        )
    return out


CALLED = re.compile(r"(?:calls|to_apply|body|condition|true_computation|false_computation)=%?([\w.\-]+)"
                    r"|branch_computations=\{([^}]*)\}")


def wide_results_by_branch(text, wide):
    """Of a compiled program's text: for every `conditional`, how many results
    that match `wide` (a shape, `[4096,256]`) each of its branches holds, in
    the branch's computation and whatever that calls; and how many the program
    holds outside every branch."""
    computations, name = {}, None
    for line in text.splitlines():
        if line.endswith("{") and " = " not in line:
            name = line.split()[1 if line.startswith("ENTRY") else 0].lstrip("%")
            computations[name] = []
        elif name is not None and " = " in line:
            computations[name].append(line)

    def called(lines):
        for line in lines:
            for one, many in CALLED.findall(line):
                yield from [one] if one else (n.strip().lstrip("%") for n in many.split(","))

    def closure(root):
        seen, todo = set(), [root]
        while todo:
            n = todo.pop()
            if n not in seen and n in computations:
                seen.add(n)
                todo += called(computations[n])
        return seen

    def count(names):
        return sum(bool(re.search(wide, line.split(" = ")[1].split("(")[0]))
                   for n in names for line in computations[n])

    branches = [closure(b) for lines in computations.values() for line in lines
                if " conditional(" in line for b in called([line])]
    return [count(b) for b in branches], count(set(computations) - set().union(*branches))


def _held_experts_case(topo):
    """An LFM2 step whose one expert layer holds 2 of 16 experts at shapes that
    tile: 2 x 1,024 tokens of 256, two experts a token (4,096 pairs, a bound
    of 1,024 rows), experts of 128. Where are the arrays as long as all pairs?"""
    from ray_tpu.models import LFM2Config
    from ray_tpu.models.lfm2 import CONV

    cfg = LFM2Config(vocab_size=512, layer_types=(CONV, CONV), n_dense_layers=1, n_head=4, n_kv_head=2,
                     d_model=256, d_ff=512, d_expert=128, n_experts=16, experts_per_token=2,
                     n_experts_held=2, first_expert_held=4, max_seq_len=1024)
    text = _lowered_step(topo, {"data": 1}, cfg, 2, 1024).compile().as_text()
    by_branch, outside = wide_results_by_branch(text, r"\[4096,(256|128)\]")
    kernels = sorted(set(re.findall(r"(gmm_\w+?|sum_rows|gather_rows)[.\d]* = ", text)))
    return {"wide_by_branch": by_branch, "wide_outside": outside, "kernels": kernels}


_MESHES = {"d1": {"data": 1}, "d4": {"data": 4}, "d2t2": {"data": 2, "tensor": 2}}


def _main(cases):
    sys.path.insert(0, REPO)
    from jax.experimental import topologies

    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    results = {"device_kind": topo.devices[0].device_kind}
    for case in cases:
        if case == "kernel":
            results[case] = _kernel_case(topo)
        elif case == "held_experts":
            results[case] = _held_experts_case(topo)
        elif case.startswith("row_movers:"):
            results[case] = _row_movers_case(topo, int(case[len("row_movers:"):]))
        elif case.startswith("selected:"):
            results[case] = _selected_case(topo, *(int(n) for n in case[len("selected:"):].split("x")))
        elif case.startswith("indexer:"):
            results[case] = _indexer_case(topo, *(int(n) for n in case[len("indexer:"):].split("x")))
        elif case.startswith("kernel:"):
            results[case] = _kernel_case(topo, tuple(int(n) for n in case[len("kernel:"):].split("x")))
        else:
            verb, mesh = case.split(":")
            results[case] = _step_case(topo, _MESHES[mesh], verb == "compile")
    print("AOT_RESULT " + json.dumps(results))


def _run(cases):
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), *cases],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=600,
    )
    lines = [l for l in proc.stdout.splitlines() if l.startswith("AOT_RESULT ")]
    assert proc.returncode == 0 and lines, proc.stdout[-2000:] + proc.stderr[-4000:]
    return json.loads(lines[-1][len("AOT_RESULT "):])


@pytest.fixture(scope="module")
def aot():
    return _run(["kernel", LONG_HEAD_64, WIDE_HEAD_256, *LONGER_HEADS, *BETWEEN_HEADS, "held_experts",
                 *ROW_MOVERS, SELECTED_16K, INDEXER_16K, *STREAMED_HEADS, GROUPED_2K, "lower:d4", "lower:d2t2"])


def test_topology_is_the_v5e(aot):
    assert aot["device_kind"] == "TPU v5 lite"


def test_flash_kernels_compile_for_v5e_at_gpt2_shapes(aot):
    assert aot["kernel"]["mosaic_calls"] == 2  # forward, fused backward


def test_flash_kernels_compile_for_v5e_at_a_head_of_4096_by_64(aot):
    """`(8, 32, 4096, 64)` bf16: 64 lanes pad to 128 in VMEM, so the head takes
    what a 4096 x 128 one does and must run the same form, 512-tiles with the
    backward pass's whole-head operands single-buffered. Counted by
    `seq * head_dim` it kept the 1024-tile double-buffered form, and `flash_bwd`
    failed here with RESOURCE_EXHAUSTED in VMEM at every tile size (PR 35)."""
    assert aot[LONG_HEAD_64]["mosaic_calls"] == 2  # forward, fused backward
    assert aot[LONG_HEAD_64]["plan"] == [512, 512, 36, 8, 64, False]


@pytest.mark.parametrize("case", [WIDE_HEAD_256, *LONGER_HEADS, *BETWEEN_HEADS])
def test_flash_kernels_compile_for_v5e_at_heads_above_that_vmem(aot, case):
    """`(2, 20, 4096, 256)` bf16, GLM-4.7-Flash's latent attention: a head takes
    2 MiB of VMEM, and the loop form's backward program (q, do and two (seq, 1)
    statistics whole, dq's output block twice, an f32 dq scratch: 18 MiB before
    a tile) failed with RESOURCE_EXHAUSTED. For every head above 1 MiB the
    backward program is one (Q tile, K tile) pair with every operand streamed,
    still one fused Mosaic call; `(2, 32, 8192, 64)` and `(1, 16, 8192, 128)`,
    PERF.md's long-context failures, take the same VMEM and compile the same
    way, as does every head between (the same model at 3,072 or 3,584
    positions; 128-wide heads of 6,144 and 7,168), and all of them walk
    512-tiles: 1024-tiles at 256 wide failed in both kernels."""
    assert aot[case]["mosaic_calls"] == 2  # forward, fused backward
    seq = int(case.split("x")[2])
    n = seq // 512
    assert aot[case]["plan"] == [512, 512, n * (n + 1) // 2, n, n * n, False]


def test_both_flash_kernels_stream_pairs_under_a_selection_at_16384_by_128(aot):
    """`(1, 32 on 4, 16384, 128)` bf16 with a packed `keep`: a head is 4 MiB, which no forward program
    holds, so `flash_fwd` runs in `flash_bwd`'s pair-streamed form, each one Mosaic call under the 16
    MiB default, at pairs of 512 x 1024 (1024-row Q tiles and 2048-key K tiles fail in `flash_bwd`: its
    f32 dq scratch alone is 8 MiB here); key/value heads unrepeated; and the same two programs without a selection."""
    got = aot[SELECTED_16K]
    assert got["mosaic_calls"] == got["mosaic_calls_without_keep"] == 2
    assert got["plan"] == [512, 1024, 272, 32, 512, False] and got["kernels"] == ["flash_bwd", "flash_fwd"]
    # The forward's program is a pair of a key/value head's whole group, at the plan's own Q tile (PR 44: 10.3 MiB by
    # the smallest limit that compiles, under the default 16 with none asked for), and its scope says so.
    assert got["forward"] == [8, 512] and got["forward_scopes"] == ["group_8"]


@pytest.mark.parametrize("case, plan, forward", [
    (STREAMED_HEADS[0], [512, 1024, 272, 32, 512, False], [4, 512]),
    (STREAMED_HEADS[1], [512, 512, 136, 16, 256, False], [4, 512])])
def test_the_streamed_forward_takes_four_equal_heads_a_program(aot, case, plan, forward):
    """`(1, 8, 16384, 128)` and `(1, 8, 8192, 256)` bf16, as many key/value heads as heads and no selection: the
    same forward program with a group of one, four heads and their four key/value heads a program (one head a
    program was 12 % slower on the chip than the form it replaced, four are 17 % faster: PERF.md section 6, PR 44)."""
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    assert aot[case]["mosaic_calls"] == 2 and aot[case]["plan"] == plan
    _, heads, seq, d = (int(n) for n in case[len("kernel:"):].split("x"))
    assert list(fa._fwd_pairs_plan(1, heads, d, 2, fa.kernel_plan((1, heads, seq, d)))) == forward


def test_grouped_heads_of_2048_by_64_stream_pairs_with_and_without_a_selection(aot):
    """`(2, 8 on 2, 2048, 64)` bf16, what `models/llama.py` calls with `n_kv_head < n_head`: two groups of four and
    their two key/value heads a program at a head of 64 lanes (`o^T` is (64, tile_q) there)."""
    got = aot[GROUPED_2K]
    assert got["mosaic_calls"] == got["mosaic_calls_without_keep"] == 2
    assert got["plan"] == [512, 1024, 6, 4, 8, False] and got["forward"] == [8, 512]
    assert got["forward_scopes"] == ["group_8"]


def test_the_selection_and_the_indexer_loss_compile_for_v5e_at_the_cells_shapes(aot):
    """`select` (64 query rows' scores against 16,384 keys in VMEM, the threshold, the packed words)
    and `index_loss` (grid (1, 2080 pairs of 256 x 256), a program a whole pair: the 32 heads' products on
    their 4 key/value heads, the 16 index scores made once and kept, 10.75 MiB of VMEM by the smallest
    limit that compiles, under the default with none asked for; the gradients leave the same call, so its
    backward pass is no kernel) at the Keye cell's indexer of 16 heads of 64, top-2,048."""
    got = aot[INDEXER_16K]
    assert got["select"] == ["select"] and got["index_loss"] == ["index_loss"]
    assert got["keep"] == [1, 16384, 512] and got["index_loss_mosaic_calls"] == 1


def test_the_plans_of_the_other_cells_shapes_are_what_they_were():
    from ray_tpu.ops.flash_attention import kernel_plan

    assert kernel_plan((8, 16, 1024, 64)) == (512, 512, 3, 2, 4, True)  # gpt2-medium
    assert kernel_plan((4, 25, 1024, 64)) == (512, 512, 3, 2, 4, True)  # gpt2-xl-fsdp4, a chip
    assert kernel_plan((2, 16, 4096, 128)) == (512, 512, 36, 8, 64, False)  # olmoe-1b-7b-l1
    assert kernel_plan((16, 32, 2048, 64)) == (512, 512, 10, 4, 16, True)  # shorter heads of 64: untouched
    assert kernel_plan((8, 32, 4096, 64)) == (512, 512, 36, 8, 64, False)  # lfm2-24b-a2b-ep8-l5
    # ... and the form of the backward program behind the plan: pairs streamed above 1 MiB a head.
    import importlib

    fa = importlib.import_module("ray_tpu.ops.flash_attention")
    streamed = lambda b, h, s, d: fa._streamed_head(s, d, 2)  # noqa: E731
    assert not any(streamed(*shape) for shape in (
        (8, 16, 1024, 64), (4, 25, 1024, 64), (2, 16, 4096, 128), (8, 32, 4096, 64), (16, 32, 2048, 64)))
    assert all(streamed(*shape) for shape in (
        (2, 20, 4096, 256), (2, 32, 8192, 64), (1, 16, 8192, 128), (2, 20, 2560, 256), (2, 20, 3072, 256),
        (2, 16, 4608, 128), (2, 16, 7680, 128), (2, 4, 2048, 256)))


def test_a_share_of_the_experts_holds_no_array_as_long_as_every_pair_where_the_prefix_runs(aot):
    """`moe_mlp` with fewer experts than the router scores runs its sorted form
    over the prefix of the sort that the held pairs fill, or over every pair
    where they do not fit it: a `conditional` in the forward pass and one in
    the backward pass (the recomputation's has no reader and is gone). The
    `[pairs, D]` and `[pairs, F]` arrays are all inside the whole-length
    branches: the branch that runs when the router is anywhere near even
    holds none, forward or backward, and neither does the program around
    them. Both branches call the kernels."""
    got = aot["held_experts"]
    assert len(got["wide_by_branch"]) == 4 and got["wide_outside"] == 0, got
    prefix, whole = sorted(got["wide_by_branch"])[:2], sorted(got["wide_by_branch"])[2:]
    assert prefix == [0, 0] and min(whole) > 0, got
    # 2,048 tokens of 256: a source that XLA's gather serves (`moe._rows_by`), so no `gather_rows`.
    assert got["kernels"] == ["gmm_dlhs", "gmm_drhs", "gmm_fwd", "sum_rows"]


@pytest.mark.parametrize("case, rows", zip(ROW_MOVERS, (32768, 8192)))
def test_the_row_movers_of_the_prefix_form_compile_for_v5e_at_the_cells_shapes(aot, case, rows):
    """`gather_rows` (tokens into expert order, written a block of tokens at a
    time, at 128-row chunks) and `sum_rows` at the 256-row chunk that a layer
    holding an eighth of the experts gets (`chunk_rows`; 512 where every pair
    is held): one Mosaic call each, at the prefix lengths the LFM2 and
    GLM-4.7-Flash steps run (the second's gather stays XLA's in the step,
    `moe._rows_by`: the kernel compiles there all the same)."""
    assert aot[case] == {"rows": rows, "chunk_rows": [256, 128], "kernels": ["gather_rows", "sum_rows"]}


@pytest.mark.parametrize("mesh", ["d4", "d2t2"])
def test_gpt2_small_step_lowers_on_four_chips_with_the_kernel(aot, mesh):
    assert aot[f"lower:{mesh}"]["mosaic_calls"] == 2


@pytest.mark.slow
def test_gpt2_small_step_compiles_and_fits_hbm_on_one_and_four_chips():
    out = _run(["compile:d1", "compile:d4", "compile:d2t2"])
    for case, r in out.items():
        if case == "device_kind":
            continue
        assert r["mosaic_calls_compiled"] == 2, (case, r)
        assert 0 < r["device_bytes"] < V5E_HBM_BYTES, (case, r)


if __name__ == "__main__":
    _main(sys.argv[1:])

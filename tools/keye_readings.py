"""The two readings the limits of `benchmark/models/keye_vl2.py check` lie between, on the chip at the
published widths: the system's distance from the f32 reference, and the distance of the reference itself computed
in bf16 (parameters, router, norms, indexer scores and logits; the cross entropy of those logits in f32), one JSON
line a seed (PERF.md section 6, PR 42): loss, gradient norm, the indexer's loss, the share of flipped expert
choices, and the share of the f32 reference's selected (query, key) pairs on which the other's selection differs.

    chiprun --chips 1 --timeout 1800 -- python3 tools/keye_readings.py 3141592653 2718281828
"""
import json, sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np, optax
from benchmark.harness.manifest import Manifest
from benchmark.models import keye_vl2 as bench
from ray_tpu.models import keye_vl2 as program

c = Manifest().config("keye-vl-2.0-30b-a3b-ep8")
cfg = bench.model_config(c)
k = c["num_experts_per_tok"]

def of_system(params, tokens):
    loss, grads = jax.value_and_grad(lambda p: program.loss_fn(p, {"tokens": tokens}, cfg))(params)
    walked = program.layer_stats(params, tokens, cfg)
    return (loss, optax.global_norm(grads), walked["moe"]["experts"], walked["selection"]["keep"],
            walked["selection"]["index_loss"].sum())

def of_reference(dtype, return_keep):
    def f(params, tokens, keep):
        (loss, aux), grads = jax.value_and_grad(lambda p: bench.reference_loss(
            p, tokens, c, dtype, system_keep=keep, return_keep=return_keep), has_aux=True)(params)
        return loss, optax.global_norm(grads), aux
    return jax.jit(f)

def flipped(chosen, experts):  # is each of `experts` (layers, tokens, k) one of `chosen` (layers, tokens, E)?
    return float(1.0 - jnp.take_along_axis(chosen, experts, axis=-1).mean())

sys_fn, ref32, ref16 = jax.jit(of_system), of_reference(None, False), of_reference(jnp.bfloat16, True)
for seed in map(int, sys.argv[1:]):
    params = jax.jit(lambda key: program.init_params(cfg, key))(jax.random.PRNGKey(seed))
    rows = c["batch"]["global_rows"]
    tokens = jnp.asarray(np.random.default_rng(seed).integers(0, c["vocab_size"] - 1, (rows, c["batch"]["seq"] + 1), dtype=np.int32))
    s_loss, s_norm, experts, keep, s_index = sys_fn(params, tokens)
    r_loss, r_norm, r = ref32(params, tokens, keep)
    b_loss, b_norm, b = ref16(params, tokens, keep)
    _, _, r_against_b = ref32(params, tokens, b["keep"])  # the f32 reference again, counting against the bf16 one's selection
    selected = float(r["selected_pairs"].sum())
    rel = lambda a, ref: abs(float(a) - float(ref)) / abs(float(ref))
    out = {"seed": seed, "loss_f32": float(r_loss), "index_loss_f32": float(r["index_loss"]),
           "system_loss_err": abs(float(s_loss) - float(r_loss)), "system_grad_norm_rel_err": rel(s_norm, r_norm),
           "system_index_loss_rel_err": rel(s_index, r["index_loss"]), "system_flipped": flipped(r["chosen"], experts),
           "system_selection_differs": float(r["selection_differs"].sum()) / selected,
           "bf16_loss_err": abs(float(b_loss) - float(r_loss)), "bf16_grad_norm_rel_err": rel(b_norm, r_norm),
           "bf16_index_loss_rel_err": rel(b["index_loss"], r["index_loss"]),
           "bf16_flipped": flipped(r["chosen"], jax.lax.top_k(b["chosen"].astype(jnp.float32), k)[1]),
           "bf16_selection_differs": float(r_against_b["selection_differs"].sum()) / selected}
    print("READING " + json.dumps(out), flush=True)
    del params

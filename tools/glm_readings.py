"""The two readings the limits of `benchmark/models/glm4_moe_lite.py check` lie between, on the chip at the
published widths: the system's distance from the f32 reference, and the distance of the reference itself computed
in bf16 (parameters, router, norms and logits; the cross entropy of those logits in f32), one JSON line a seed
(PERF.md section 6, PR 39).

    chiprun --chips 1 --timeout 1800 -- python3 tools/glm_readings.py 3141592653 2718281828
"""
import json, sys
sys.path.insert(0, ".")
import jax, jax.numpy as jnp, numpy as np, optax
from benchmark.harness.manifest import Manifest
from benchmark.models import glm4_moe_lite as bench
from ray_tpu.models import glm4_moe_lite as program

c = Manifest().config("glm-4.7-flash-ep8-l5")
cfg = bench.model_config(c)

def of_system(params, tokens):
    loss, grads = jax.value_and_grad(lambda p: program.loss_fn(p, {"tokens": tokens}, cfg))(params)
    return loss, optax.global_norm(grads), program.routing_stats(params, tokens, cfg)["experts"]

def of_reference(dtype):
    def f(params, tokens, experts):
        (loss, chosen), grads = jax.value_and_grad(lambda p: bench.reference_loss(p, tokens, c, dtype), has_aux=True)(params)
        same = jnp.take_along_axis(chosen, experts, axis=-1)
        return loss, optax.global_norm(grads), 1.0 - same.mean(), chosen
    return jax.jit(f)

sys_fn, ref32, ref16 = jax.jit(of_system), of_reference(None), of_reference(jnp.bfloat16)
for seed in map(int, sys.argv[1:]):
    params = jax.jit(lambda k: program.init_params(cfg, k))(jax.random.PRNGKey(seed))
    tokens = jnp.asarray(np.random.default_rng(seed).integers(0, c["vocab_size"] - 1, (2, c["batch"]["seq"] + 1), dtype=np.int32))
    s_loss, s_norm, experts = sys_fn(params, tokens)
    r_loss, r_norm, flipped, chosen = ref32(params, tokens, experts)
    # the bf16 reference's own choices against the f32 reference's: its top-k as (layers, tokens, k)
    b_loss, b_norm, b_flipped_vs_system, b_chosen = ref16(params, tokens, experts)
    k = c["num_experts_per_tok"]
    b_experts = jax.lax.top_k(b_chosen.astype(jnp.float32), k)[1]
    b_flipped = 1.0 - jnp.take_along_axis(chosen, b_experts, axis=-1).mean()
    out = {"seed": seed, "loss_f32": float(r_loss), "system_loss_err": abs(float(s_loss) - float(r_loss)),
           "system_grad_norm_rel_err": abs(float(s_norm) - float(r_norm)) / float(r_norm), "system_flipped": float(flipped),
           "bf16_loss": float(b_loss), "bf16_loss_err": abs(float(b_loss) - float(r_loss)),
           "bf16_grad_norm_rel_err": abs(float(b_norm) - float(r_norm)) / float(r_norm), "bf16_flipped": float(b_flipped)}
    print("READING " + json.dumps(out), flush=True)
    del params

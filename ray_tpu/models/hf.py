"""HuggingFace checkpoint import: GPT-2 weights -> the zoo's pytree layout.

The reference's GPT-2 workloads fine-tune HF checkpoints through Ray Train
(`release/air_tests/air_benchmarks/` HF-Transformers benchmarks; BASELINE
config #4). This module is that on-ramp for the TPU build: load a
`transformers` GPT-2 (any size), convert to `models/gpt.py`'s stacked-layer
pytree, and continue training/fine-tuning under any mesh the zoo supports.

Conversion notes:
 - HF Conv1D stores weights (in, out) — already our einsum orientation.
 - c_attn packs q|k|v along the output dim, heads inside each: (d, 3d) is
   `qkv_w`'s own form, as (d, d) of c_proj is `out_w`'s; only the bias is
   viewed per head, (3d,) -> (3, nh, hd).
 - per-layer tensors stack on a leading `layers` dim (scan-over-layers).
 - the vocab pads up to a multiple of 128 (MXU tiling); padded embedding
   rows are zero and their logits sit at 0 — harmless for fine-tuning (they
   never appear as targets), slice `[:, :, :hf_vocab]` for exact HF logits.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

import numpy as np

from ray_tpu.models.gpt import GPTConfig


def _pad_vocab(n: int, multiple: int = 128) -> int:
    return (n + multiple - 1) // multiple * multiple


def config_from_hf(hf_config, **overrides) -> GPTConfig:
    """GPTConfig matching a transformers GPT2Config (vocab padded for MXU).

    Raises on HF options this forward pass does not implement (non-gelu
    activations, non-default layer-norm eps) rather than silently diverging
    from the parity promise."""
    act = getattr(hf_config, "activation_function", "gelu_new")
    # gpt.py computes jax.nn.gelu's tanh approximation; HF "gelu" is the
    # exact erf variant and would silently diverge from parity.
    if act not in ("gelu_new", "gelu_pytorch_tanh"):
        raise ValueError(f"unsupported activation_function {act!r} (tanh-gelu only)")
    eps = float(getattr(hf_config, "layer_norm_epsilon", 1e-5))
    if abs(eps - 1e-5) > 1e-9:
        raise ValueError(f"layer_norm_epsilon {eps} != 1e-5 (models/gpt.py hardcodes 1e-5)")
    kw = dict(
        vocab_size=_pad_vocab(hf_config.vocab_size),
        n_layer=hf_config.n_layer,
        n_head=hf_config.n_head,
        d_model=hf_config.n_embd,
        d_ff=getattr(hf_config, "n_inner", None) or 0,  # 0 -> 4*d_model
        max_seq_len=hf_config.n_positions,
    )
    kw.update(overrides)
    return GPTConfig(**kw)


def load_hf_gpt2(model, **config_overrides) -> Tuple[GPTConfig, Dict[str, Any]]:
    """Convert a transformers GPT2LMHeadModel (or name) to (GPTConfig, params).

    Accepts a model instance or a checkpoint name for `from_pretrained`
    (instance is the offline-friendly path)."""
    if isinstance(model, str):
        from transformers import GPT2LMHeadModel

        model = GPT2LMHeadModel.from_pretrained(model)
    hf_cfg = model.config
    config = config_from_hf(hf_cfg, **config_overrides)
    sd = {k: v.detach().cpu().numpy() for k, v in model.state_dict().items()}
    L, d = config.n_layer, config.d_model
    nh, hd, F = config.n_head, config.head_dim, config.ff_dim
    V_hf = hf_cfg.vocab_size
    pd = np.dtype(config.param_dtype)

    wte = np.zeros((config.vocab_size, d), pd)
    wte[:V_hf] = sd["transformer.wte.weight"]

    def stack(fmt, reshape=None):
        arrs = [sd[fmt.format(i)] for i in range(L)]
        out = np.stack([a.reshape(reshape) if reshape else a for a in arrs])
        return np.ascontiguousarray(out, pd)

    blocks = {
        "ln1_scale": stack("transformer.h.{}.ln_1.weight"),
        "ln1_bias": stack("transformer.h.{}.ln_1.bias"),
        "qkv_w": stack("transformer.h.{}.attn.c_attn.weight"),
        "qkv_b": stack("transformer.h.{}.attn.c_attn.bias", (3, nh, hd)),
        "out_w": stack("transformer.h.{}.attn.c_proj.weight"),
        "out_b": stack("transformer.h.{}.attn.c_proj.bias"),
        "ln2_scale": stack("transformer.h.{}.ln_2.weight"),
        "ln2_bias": stack("transformer.h.{}.ln_2.bias"),
        "fc_w": stack("transformer.h.{}.mlp.c_fc.weight"),
        "fc_b": stack("transformer.h.{}.mlp.c_fc.bias"),
        "proj_w": stack("transformer.h.{}.mlp.c_proj.weight"),
        "proj_b": stack("transformer.h.{}.mlp.c_proj.bias"),
    }
    params = {
        "wte": wte,
        "wpe": np.ascontiguousarray(sd["transformer.wpe.weight"], pd),
        "blocks": blocks,
        "lnf_scale": np.ascontiguousarray(sd["transformer.ln_f.weight"], pd),
        "lnf_bias": np.ascontiguousarray(sd["transformer.ln_f.bias"], pd),
    }
    return config, params

"""GPT-2 for the benchmark: the system under test built through ray_tpu's
public API, a plain float32 reference written from the paper, the comparison
that decides `correct`, and the arithmetic of FLOPs and bytes.

A configuration file (`benchmark/configs/<name>.json`) with `"model": "gpt2"`
is served by this module. Keys read: `n_layer`, `n_head`, `n_embd`,
`n_positions`, `vocab_size` (as published), `padded_vocab_size`, `dtype`,
`param_dtype`, `remat_policy`, `attention`, `learning_rate`.
"""

from __future__ import annotations

from typing import Any, Dict, Tuple

# ------------------------------------------------------------------ arithmetic
# No jax below this line until `build`: the parent and the tests use these.


def matmul_params(c: Dict[str, Any]) -> int:
    """Parameters that are an operand of a matrix multiplication: per layer
    qkv (3 d^2), attention output (d^2) and the two MLP matrices (2 * 4 d^2),
    plus the tied vocabulary head at its padded size. Position embeddings,
    biases and LayerNorm parameters multiply nothing and are left out (the
    program's `train_flops_per_token` counts them: < 1 % more)."""
    d, layers = c["n_embd"], c["n_layer"]
    return layers * 12 * d * d + c["padded_vocab_size"] * d


def train_flops_per_token(c: Dict[str, Any], seq: int) -> float:
    """FLOPs the forward and backward passes require per token: 6 per matmul
    parameter, plus attention's two matrix products forward and four backward
    over the full square of `seq` positions (12 * layers * d * seq: the PaLM
    convention for MFU; a causal model needs half of that term, so this MFU
    flatters attention by at most that half). Recomputation is not counted."""
    return 6.0 * matmul_params(c) + 12.0 * c["n_layer"] * c["n_embd"] * seq


def flash_flops_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """FLOPs the causal attention of one train step requires on the device
    that holds `rows` sequences: per (row, head) two products forward (QK^T,
    PV) and four backward (dV, dP, dQ, dK), each 2 * seq^2 * head_dim, over
    the causal half of the square. The kernel's recomputation of QK^T in its
    backward pass is not counted."""
    head_dim = c["n_embd"] // c["n_head"]
    per_head = 6 * 2 * seq * seq * head_dim / 2
    return per_head * rows * c["n_head"] * c["n_layer"]


def flash_bytes_per_step(c: Dict[str, Any], rows: int, seq: int) -> float:
    """Bytes the two kernels must move per step: forward reads q, k, v and
    writes o (bf16) and the row statistics (f32); backward reads q, k, v, do,
    the statistics and delta, and writes dq, dk, dv."""
    head_dim = c["n_embd"] // c["n_head"]
    act = seq * head_dim * 2
    stat = seq * 4
    per_head = (4 * act + stat) + (7 * act + 2 * stat)
    return per_head * rows * c["n_head"] * c["n_layer"]


# ---------------------------------------------------------------------- system
def gpt_config(c: Dict[str, Any]):
    import jax.numpy as jnp

    from ray_tpu.models import GPTConfig

    return GPTConfig(
        vocab_size=c["padded_vocab_size"], n_layer=c["n_layer"], n_head=c["n_head"],
        d_model=c["n_embd"], max_seq_len=c["n_positions"],
        dtype=jnp.dtype(c["dtype"]), param_dtype=jnp.dtype(c["param_dtype"]),
        remat_policy=c["remat_policy"], attention=c["attention"],
    )


class System:
    """cfg, optimizer, state and jitted step, made as a user makes them."""

    def __init__(self, c: Dict[str, Any], mesh, seed: int):
        import jax

        from ray_tpu.models import create_train_state, default_optimizer, make_train_step

        self.c = c
        self.mesh = mesh
        self.cfg = gpt_config(c)
        self.optimizer = default_optimizer(learning_rate=c["learning_rate"])
        self.state = create_train_state(self.cfg, jax.random.PRNGKey(seed), self.optimizer, mesh=mesh)
        if mesh is not None and mesh.size > 1:
            self._shard_moments()
        self.step = make_train_step(self.cfg, self.optimizer, mesh=mesh)

    def _shard_moments(self):
        """`create_train_state` says the AdamW moments inherit the parameters'
        shardings; they do not (PERF.md, PR 22): `jit(optimizer.init)` puts
        them whole on every process's chip, 12.4 GB for gpt2-xl. Until the
        program lays them out, the benchmark does: the moments are zeros, so
        they are made again, each laid out like the parameter of its shape."""
        import jax
        from jax.sharding import NamedSharding, PartitionSpec

        params = self.state.params
        by_shape = {p.shape: p.sharding for p in jax.tree.leaves(params)}
        replicated = NamedSharding(self.mesh, PartitionSpec())
        shardings = jax.tree.map(lambda s: by_shape.get(s.shape, replicated),
                                 jax.eval_shape(self.optimizer.init, params))
        self.state.opt_state = None  # free the whole copies before the sharded ones exist
        self.state.opt_state = jax.jit(self.optimizer.init, out_shardings=shardings)(params)

    def param_shardings(self):
        """The parameters' shardings as they are laid out, or None on one device."""
        import jax

        if self.mesh is None or self.mesh.size == 1:
            return None
        return jax.tree.map(lambda p: p.sharding, self.state.params)

    def attention_path(self, rows_per_device: int, seq: int, platform: str) -> str:
        from ray_tpu.ops.flash_attention import select_backend

        return select_backend((rows_per_device, self.cfg.n_head, seq, self.cfg.head_dim), platform)


def build(c: Dict[str, Any], mesh, seed: int) -> System:
    return System(c, mesh, seed)


# ------------------------------------------------------------------- reference
def reference_loss(params, tokens, n_head: int):
    """GPT-2 (Radford et al. 2019) in float32 `jax.numpy`: learned position
    embeddings, pre-LayerNorm blocks (eps 1e-5), softmax attention under a
    causal mask, GELU (tanh form, GPT-2's `gelu_new`), tied output head, mean
    cross entropy of the next token. No kernel, no bf16.

    Takes the parameter tree the system trains (layers stacked on a leading
    axis, q/k/v and heads as separate axes) and reads it as the published
    shapes. Two departures from a line-by-line transcription, neither changes
    the arithmetic: the layers run in a `lax.scan`, and each layer is
    recomputed in the backward pass (`jax.checkpoint`), so that 48 layers of
    1024 x 1024 float32 attention matrices are never held at once beside the
    training state.
    """
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    inputs, targets = tokens[:, :-1], tokens[:, 1:]
    seq = inputs.shape[1]
    wte = params["wte"].astype(f32)
    d = wte.shape[1]
    head_dim = d // n_head
    x = wte[inputs] + params["wpe"].astype(f32)[:seq][None]
    mask = jnp.tril(jnp.ones((seq, seq), bool))

    def layer_norm(x, scale, bias):
        mean = x.mean(-1, keepdims=True)
        var = ((x - mean) ** 2).mean(-1, keepdims=True)
        return (x - mean) / jnp.sqrt(var + 1e-5) * scale + bias

    def gelu_new(x):
        return 0.5 * x * (1.0 + jnp.tanh(jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))

    @jax.checkpoint
    def block(x, layer):
        layer = jax.tree.map(lambda p: p.astype(f32), layer)
        h = layer_norm(x, layer["ln1_scale"], layer["ln1_bias"])
        c_attn = layer["qkv_w"].reshape(d, 3 * d)  # columns: q heads, k heads, v heads
        qkv = h @ c_attn + layer["qkv_b"].reshape(3 * d)
        q, k, v = (t.reshape(t.shape[0], seq, n_head, head_dim).transpose(0, 2, 1, 3)
                   for t in jnp.split(qkv, 3, axis=-1))
        scores = q @ k.transpose(0, 1, 3, 2) / jnp.sqrt(f32(head_dim))
        scores = jnp.where(mask, scores, -jnp.inf)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        attn = attn.transpose(0, 2, 1, 3).reshape(x.shape[0], seq, d)
        x = x + attn @ layer["out_w"].reshape(d, d) + layer["out_b"]
        h = layer_norm(x, layer["ln2_scale"], layer["ln2_bias"])
        h = gelu_new(h @ layer["fc_w"] + layer["fc_b"])
        return x + h @ layer["proj_w"] + layer["proj_b"], None

    with jax.default_matmul_precision("highest"):
        x, _ = jax.lax.scan(block, x, params["blocks"])
        x = layer_norm(x, params["lnf_scale"].astype(f32), params["lnf_bias"].astype(f32))
        logits = x @ wte.T
        logp = jax.nn.log_softmax(logits, axis=-1)
        return -jnp.take_along_axis(logp, targets[..., None], axis=-1).mean()


# Tolerances of the agreement between the system (bf16 activations, the
# Pallas kernel, f32 logits) and the reference (f32 throughout), at seeded
# initial weights. Measured on the chip (PR 22): loss off by 2e-5..2e-4 in both
# configurations, gradient norm by 1e-4..8e-4 (medium) and 2.2e-3..2.3e-3 (xl,
# 48 layers of bf16 rounding). The bounds are about five times that, and far
# under what a lower precision costs: logits or
# softmax statistics in bf16 move the loss by > 1e-2 (8 bits of mantissa on
# values near 11). The forward pass casts every matrix to bf16 by design, so
# no comparison of losses can see parameters kept in bf16: the parameters'
# and the optimizer moments' dtype is checked by name instead.
LOSS_ABS_TOL = 1e-3
GRAD_NORM_REL_TOL = 1e-2


def both_losses_and_norms(system: System):
    """(params, tokens) -> system loss, its gradient's norm, reference loss,
    its gradient's norm: one program, four scalars out."""
    import jax
    import optax

    from ray_tpu.models import loss_fn

    cfg, mesh, n_head = system.cfg, system.mesh, system.c["n_head"]
    shardings = system.param_shardings()

    def norm(grads):
        # Laid out like the parameters: left alone, XLA all-reduces whole
        # float32 gradients onto every chip (13.1 GB of temporaries for
        # gpt2-xl on fsdp=4 against 6.8 GB so: ahead-of-time compiles, PR 22).
        if shardings is not None:
            grads = jax.tree.map(jax.lax.with_sharding_constraint, grads, shardings)
        return optax.global_norm(grads)

    def both(params, tokens):
        sys_loss, sys_grads = jax.value_and_grad(
            lambda p: loss_fn(p, {"tokens": tokens}, cfg, mesh=mesh))(params)
        ref_loss, ref_grads = jax.value_and_grad(
            lambda p: reference_loss(p, tokens, n_head))(params)
        return sys_loss, norm(sys_grads), ref_loss, norm(ref_grads)

    return both


def check(system: System, tokens, *, loss_tol: float = LOSS_ABS_TOL,
          grad_tol: float = GRAD_NORM_REL_TOL) -> Dict[str, Any]:
    """Loss and global gradient norm of the system's `loss_fn` (through
    whatever attention path it selects) against the reference's, on `tokens`
    (a jax array, already placed under the system's mesh), with the run's own
    parameters. Nothing is gathered to the host but four scalars."""
    import jax
    import jax.numpy as jnp

    got = [float(x) for x in jax.jit(both_losses_and_norms(system))(system.state.params, tokens)]
    sys_loss, sys_norm, ref_loss, ref_norm = got
    want_dtype = jnp.dtype(system.c["param_dtype"])
    leaves = jax.tree.leaves(system.state.params) + [
        x for x in jax.tree.leaves(system.state.opt_state) if getattr(x, "ndim", 0) > 0]
    wrong_dtype = sorted({str(x.dtype) for x in leaves if x.dtype != want_dtype})
    out = {
        "loss_system": sys_loss, "loss_reference": ref_loss,
        "grad_norm_system": sys_norm, "grad_norm_reference": ref_norm,
        "loss_abs_err": abs(sys_loss - ref_loss),
        "grad_norm_rel_err": abs(sys_norm - ref_norm) / max(ref_norm, 1e-30),
        "state_dtypes_other_than_stated": wrong_dtype,
    }
    out["ok"] = bool(
        all(map(_finite, got)) and out["loss_abs_err"] <= loss_tol
        and out["grad_norm_rel_err"] <= grad_tol and not wrong_dtype)
    return out


def _finite(x: float) -> bool:
    return x == x and abs(x) != float("inf")

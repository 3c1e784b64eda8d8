"""Plain reference of Qwen3-MoE (Qwen3-30B-A3B): the dense GQA decoder of
``dense_gqa`` with a per-head RMSNorm on queries and keys, and in every layer
a sparse mixture of experts in place of the MLP: a softmax router over all
experts, the top ``num_experts_per_tok`` kept and (``norm_topk_prob``) scaled
to sum to one, each expert a SwiGLU of width ``moe_intermediate_size``.

float32, highest precision, no kernels, no grouped matmul: every expert is
applied to every token and weighted by its gate, which is zero where the
router did not choose it.  The experts are walked in a scan, so one expert's
weights are upcast at a time.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from cellbench.reference.dense_gqa import attention, decoder, f32, rms_norm


def gates(h, router, cfg):
    """[T, E]: each token's weight on each expert, zero off its top-k."""
    probs = jax.nn.softmax(h @ f32(router), axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", False):
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, topi].set(topv)


def experts(h, lp, cfg):
    g = gates(h, lp["router"], cfg)

    def one(acc, e):
        w_gate, w_up, w_down, ge = e
        y = (jax.nn.silu(h @ f32(w_gate)) * (h @ f32(w_up))) @ f32(w_down)
        return acc + ge[:, None] * y, None

    out, _ = jax.lax.scan(
        one, jnp.zeros_like(h),
        (lp["w_gate"], lp["w_up"], lp["w_down"], g.T))
    return out


def make_forward(cfg: dict):
    eps = cfg["rms_norm_eps"]

    def layer(x, lp):
        x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg, qk_norm=True)
        return x + experts(rms_norm(x, lp["mlp_norm"], eps), lp, cfg)

    return lambda params, tokens, at: decoder(params, tokens, at, cfg, layer)

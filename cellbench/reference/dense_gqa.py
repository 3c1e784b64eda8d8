"""Plain reference of a dense Llama-style decoder with grouped-query
attention (Mistral-7B): RMSNorm, rotary embedding (rotate-half, as the
published checkpoints use it), GQA, SwiGLU.  ``jax.numpy`` in float32 at the
highest matmul precision, one sequence, no cache, no batching, no kernels,
and no import from the program's models: the equations are written out here.

It reads the engine's own weight arrays (stacked on a leading layer axis,
``x @ W`` orientation) and upcasts one layer at a time inside the scan, so
it needs one layer's weights in float32 plus the output head, not the model.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``: the distribution over the next token after each
position in ``at``.  Tokens after the last position of interest are padding
and, under the causal mask, touch nothing before them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def rope(x, theta):
    """x [T, H, D] at positions 0..T-1; pairs (i, i + D/2) rotate together."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(h, lp, cfg, qk_norm=False):
    """Causal grouped-query attention of one layer; h [T, Dm] -> [T, Dm].
    Query head j reads key/value head j // (Hq / Hk)."""
    t = h.shape[0]
    hq, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = (h @ f32(lp["wq"])).reshape(t, hq, d)
    k = (h @ f32(lp["wk"])).reshape(t, hk, d)
    v = (h @ f32(lp["wv"])).reshape(t, hk, d)
    if qk_norm:     # Qwen3: RMSNorm over the head, before the rotation
        q = rms_norm(q, lp["q_norm"], cfg["rms_norm_eps"])
        k = rms_norm(k, lp["k_norm"], cfg["rms_norm_eps"])
    q, k = rope(q, cfg["rope_theta"]), rope(k, cfg["rope_theta"])
    q = q.reshape(t, hk, hq // hk, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * (d ** -0.5)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, hq * d) @ f32(lp["wo"])


def swiglu(h, lp):
    return (jax.nn.silu(h @ f32(lp["w_gate"])) * (h @ f32(lp["w_up"]))) @ f32(lp["w_down"])


def decoder(params, tokens, at, cfg, layer):
    """Embedding, the layers (a scan, so one layer is upcast at a time),
    final norm, output head, log-softmax at the positions asked for."""
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp), None), x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])[at]
        head = params["embed"].T if cfg.get("tie_word_embeddings") else params["lm_head"]
        return jax.nn.log_softmax(x @ f32(head), axis=-1)


def make_forward(cfg: dict):
    eps = cfg["rms_norm_eps"]

    def layer(x, lp):
        x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg)
        return x + swiglu(rms_norm(x, lp["mlp_norm"], eps), lp)

    return lambda params, tokens, at: decoder(params, tokens, at, cfg, layer)

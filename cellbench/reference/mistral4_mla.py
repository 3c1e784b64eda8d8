"""Plain reference of Mistral-Small-4 (``mistral4``) as one chip's share of an
expert-parallel deployment: multi-head latent attention in its *expanded*
per-head form over every earlier position, YaRN with a position-dependent
query scale, softmax-routed experts of which only those held here add to the
sum, one shared expert in every layer.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no batching, no kernels, and no import from the program: the equations
are written out here from the configuration file's keys.  It reads the
engine's own weight arrays (``params["groups"]["sparse_none"]``, stacked over
the layers, ``x @ W`` orientation) and upcasts what one layer needs.

    h <- h + Attn(RMSNorm(h));  h <- h + MoE(RMSNorm(h));  final RMSNorm; head

Attention, per head i, for the token at position p: c_q = RMSNorm(x W_qa);
[q_nope_i ‖ q_pe_i] from c_q W_qb; [c_kv ‖ k_pe] = x W_kva, c_kv <-
RMSNorm(c_kv), k_pe one head shared by all; [k_nope_i ‖ v_i] = c_kv W_kvb,i;
RoPE on q_pe and k_pe over adjacent pairs with YaRN's frequencies;
score(t, s) = sigma · lambda(p_t) · (q_nope_i·k_nope_i(s) + q_pe_i·k_pe(s)),
s <= t; softmax; o_i = Σ P v_i; out = [o_1 .. o_H] W_o.  The program serves
the absorbed form over a cache of (c_kv ‖ k_pe) rows, the same sum reordered.

Where this departs from the published description, each for a stated reason
(the configuration file's ``assumed`` has the same list):

  * sigma = d_qk^-1/2 · m(mscale_all_dim)², m(s) = 0.1·s·ln(factor) + 1: the
    config states only the YaRN keys; this is how the DeepSeek-V3 code, whose
    keys they are, reads ``mscale_all_dim`` ≠ 0.  cos and sin are multiplied
    by m(mscale) / m(mscale_all_dim) = 1.
  * lambda(p) = 1 + beta·ln(1 + floor(p / original_max_position_embeddings)),
    beta = ``llama_4_scaling_beta``, multiplies the query (both parts): the
    family's position-dependent scale; its form is not in the config.
  * routing: softmax over all router outputs, the k largest, divided by
    their sum, no correction bias, no groups (``n_group`` 1): the config has
    neither ``scoring_func`` nor ``topk_method``.
  * only experts ``first_expert .. first_expert + held - 1`` add to the sum;
    what the other chips' experts would add is left out, as in the program.
  * no vision tower: the tokens are text.

Long sequences: scores are computed for a block of queries at a time and one
head at a time, experts are visited one at a time, so that 33 k tokens fit
beside a served model.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``make_layer(config)`` the expert layer alone
(the share test).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time, one head: [512, T]
FFN_BLOCK = 1024        # rows of a feed-forward layer at a time
GROUP = "sparse_none"   # the one kind of layer: experts, no indexer


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def yarn(rope: dict, dim: int):
    """(inverse frequencies [dim/2], m(mscale_all_dim)) of the config's
    ``rope_parameters``.  Pair j keeps f_j = theta^(-2j/dim) where it turns
    more than beta_fast times over the trained context, is divided by
    ``factor`` where it turns less than beta_slow times, and is blended
    linearly in the pair index between floor(d(beta_fast)) and
    ceil(d(beta_slow)), d(r) = dim·ln(L / 2πr) / (2 ln theta)."""
    theta, factor = float(rope["rope_theta"]), float(rope["factor"])
    trained = float(rope["original_max_position_embeddings"])
    f = [theta ** (-2.0 * j / dim) for j in range(dim // 2)]

    def d(turns):
        return dim * math.log(trained / (2 * math.pi * turns)) / (2 * math.log(theta))

    lo = max(math.floor(d(float(rope["beta_fast"]))), 0)
    hi = min(math.ceil(d(float(rope["beta_slow"]))), dim - 1)
    inv = []
    for j, fj in enumerate(f):
        g = min(max((j - lo) / max(hi - lo, 0.001), 0.0), 1.0)
        inv.append(fj * (1.0 - g) + fj / factor * g)
    m = 0.1 * float(rope["mscale_all_dim"]) * math.log(factor) + 1.0
    return jnp.asarray(inv, F32), m


def rope_pairs(x, inv):
    """x [T, ..., D] at positions 0..T-1; adjacent pairs (2i, 2i+1) rotate
    together (``rope_interleave``)."""
    t, d = x.shape[0], x.shape[-1]
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def query_scale(t: int, rope: dict):
    """lambda(p) for p = 0..t-1, [T, 1]."""
    beta = float(rope.get("llama_4_scaling_beta", 0.0))
    trained = int(rope["original_max_position_embeddings"])
    return (1.0 + beta * jnp.log1p(f32(jnp.arange(t) // trained)))[:, None]


def query_block(t: int) -> int:
    return max(d for d in range(1, min(QUERY_BLOCK, t) + 1) if t % d == 0)


def attention(x, lp, cfg):
    """Expanded latent attention of one layer; x is the normed input
    [T, Dm]; returns [T, Dm]."""
    t = x.shape[0]
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps, rope = cfg["rms_norm_eps"], cfg["rope_parameters"]
    inv, m = yarn(rope, dr)
    sigma = (dn + dr) ** -0.5 * m * m
    lam = query_scale(t, rope)
    qb = query_block(t)
    c_q = rms_norm(x @ f32(lp["q_a"]), lp["q_a_norm"], eps)
    ckv = x @ f32(lp["kv_a"])
    c_kv = rms_norm(ckv[:, :r], lp["kv_a_norm"], eps)
    k_pe = rope_pairs(ckv[:, r:], inv)                           # [T, dr]
    w_q = lp["q_b"].reshape(-1, h, dn + dr).transpose(1, 0, 2)   # [H, ql, dn+dr]
    w_kv = lp["kv_b"].reshape(r, h, dn + dv).transpose(1, 0, 2)  # [H, r, dn+dv]
    w_o = lp["wo"].reshape(h, dv, -1)                            # [H, dv, Dm]
    at = jnp.arange(t)

    def head(acc, w):
        wq, wkv, wo = (f32(a) for a in w)
        q = c_q @ wq
        q = jnp.concatenate([q[:, :dn], rope_pairs(q[:, dn:], inv)], axis=-1) * lam
        kv = c_kv @ wkv
        k = jnp.concatenate([kv[:, :dn], k_pe], axis=-1)         # [T, dn+dr]
        v = kv[:, dn:]

        def block(start):
            rows = start + jnp.arange(qb)
            s = jax.lax.dynamic_slice_in_dim(q, start, qb) @ k.T * sigma
            s = jnp.where(at[None, :] <= rows[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, dv)
        return acc + o @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), (w_q, w_kv, w_o))
    return out


def gates(x, lp, cfg):
    """[T, E_router]: each token's weight on each expert, zero off its top-k."""
    s = jax.nn.softmax(x @ f32(lp["router"]), axis=-1)
    _, topi = jax.lax.top_k(s, cfg["num_experts_per_tok"])
    rows = jnp.arange(x.shape[0])[:, None]
    chosen = jnp.zeros_like(s).at[rows, topi].set(1.0) * s
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


def ffn(x, w_gate, w_up, w_down):
    """SwiGLU, a block of rows at a time."""
    w_gate, w_up, w_down = f32(w_gate), f32(w_up), f32(w_down)

    def rows(h):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    t = x.shape[0]
    if t <= FFN_BLOCK or t % FFN_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(t // FFN_BLOCK, FFN_BLOCK, -1)).reshape(x.shape)


def routed(x, lp, cfg):
    """The part of the layer's sum that the experts held here give: one
    expert is upcast at a time, applied to every token and weighted by its
    gate."""
    first = int((cfg.get("expert_parallel") or {}).get("first_expert", 0))
    held = lp["w_gate"].shape[0]
    g = gates(x, lp, cfg)[:, first:first + held]

    def one(acc, e):
        w_gate, w_up, w_down, ge = e
        return acc + ge[:, None] * ffn(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], g.T))
    return out


def shared(x, lp):
    return ffn(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def forward(params, tokens, at, cfg):
    eps = cfg["rms_norm_eps"]
    group = params["groups"][GROUP]
    with jax.default_matmul_precision("highest"):
        def layer(x, lp):
            x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg)
            h = rms_norm(x, lp["mlp_norm"], eps)
            return x + routed(h, lp, cfg) + shared(h, lp), None

        # every layer is of the one kind, so the stack is a scan over the
        # stacked weights: one layer's are sliced out (and upcast) at a time
        x, _ = jax.lax.scan(layer, f32(params["embed"][tokens]), group)
        x = rms_norm(x, params["final_norm"], eps)[at]
        return jax.nn.log_softmax(x @ f32(params["lm_head"]), axis=-1)


def make_forward(cfg: dict):
    return lambda params, tokens, at: forward(params, tokens, at, cfg)


def make_layer(cfg: dict):
    """``f(lp, x [T, Dm]) -> (routed part, shared part)`` of one expert
    layer on its normed input: what the share test adds up."""
    def layer(lp, x):
        with jax.default_matmul_precision("highest"):
            return routed(f32(x), lp, cfg), shared(f32(x), lp)
    return layer

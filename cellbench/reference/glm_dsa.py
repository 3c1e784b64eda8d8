"""Plain reference of GLM-5.2 (``glm_moe_dsa``) as one chip's share of an
expert-parallel deployment: multi-head latent attention in its *expanded*
per-head form, a learned sparse-attention indexer whose selection later
layers share, sigmoid-routed experts of which only those held here add to
the sum, one shared expert.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no batching, no kernels, and no import from the program: the equations
are written out here (the configuration file's ``assumed`` lists where they
leave the published inference code).  It reads the engine's own weight arrays
(``params["groups"][<mlp>_<indexer>]``, stacked per kind of layer, ``x @ W``
orientation) and upcasts what one layer needs.

    h <- h + Attn(RMSNorm(h));  h <- h + FFN(RMSNorm(h));  final RMSNorm; head

Latent attention, per head i (the program serves the absorbed form, which is
the same sum reordered): c_q = RMSNorm(x W_qa); q_i = [q_nope ‖ RoPE(q_rope)]
from c_q W_qb; [c_kv ‖ k_rope] = x W_kva, c_kv <- RMSNorm(c_kv), RoPE(k_rope)
shared by all heads; [k_nope_i ‖ v_i] = c_kv W_kvb,i; score of query t on key
j: (q_nope_i·k_nope_ji + q_rope_i·k_rope_j) / sqrt(d_qk) over j in S_t.

Indexer (layers whose ``indexer_types`` entry is ``full``): q^I = c_q W^I_q
(Hi heads of Di), k^I = LayerNorm(x W^I_k), RoPE on the first d_rope
dimensions of both, w = x W^I_w; I[t, j] = (Hi·Di)^-1/2 Σ_h w[t,h]
relu(q^I[t,h]·k^I[j]) for j <= t; S_t = the ``index_topk`` positions of
largest I[t, ·] (all j <= t while t < index_topk).  A ``shared`` layer uses
the S_t of the nearest ``full`` layer before it.

Experts: s = sigmoid(x W_r) over all ``router_experts``; the top-k of s + b
are chosen, gate = routed_scaling_factor · s_e / Σ_chosen s; only experts
``first_expert .. first_expert + n_routed_experts - 1`` are held here and
add g_e FFN_e(x); the shared expert always adds.

Long sequences: scores are computed for a block of queries at a time and one
head at a time, and a selection is kept as one bit a (query, key) pair, so
that 33 k tokens fit beside a served model.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``.  ``make_probe(config)`` returns the same with the
index scores of the rows ``at`` and an optional override of every ``full``
layer's selection (scripts/glm_longctx_check.py).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time, one head: [512, T]
INDEX_BLOCK = 64        # queries indexed at a time, all heads: [64, Hi, T]
FFN_BLOCK = 1024        # rows of a feed-forward layer at a time
INDEX_NORM_EPS = 1e-6


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def layer_norm(x, weight, bias, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mu) ** 2, axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * f32(weight) + f32(bias)


def rope_pairs(x, theta):
    """x [T, ..., D] at positions 0..T-1; adjacent pairs (2i, 2i+1) rotate
    together (the interleaved form)."""
    t, d = x.shape[0], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=F32) / d))
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    ang = ang.reshape(t, *([1] * (x.ndim - 2)), d // 2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(x.shape)


def blocks(t: int, most: int = QUERY_BLOCK) -> int:
    """Queries handled at a time: the largest multiple of 32 up to ``most``
    that divides the sequence (whose length is a multiple of 32: a selection
    is kept 32 keys a word)."""
    if t % 32:
        raise ValueError(f"sequence length {t} is not a multiple of 32")
    return max(d for d in range(32, min(most, t) + 1, 32) if t % d == 0)


def pack_bits(mask):
    """bool [Q, T] -> uint32 [Q, T/32]."""
    q, t = mask.shape
    w = mask.reshape(q, t // 32, 32).astype(jnp.uint32)
    return jnp.sum(w << jnp.arange(32, dtype=jnp.uint32), axis=-1, dtype=jnp.uint32)


def unpack_bits(bits):
    q, n = bits.shape
    return ((bits[:, :, None] >> jnp.arange(32, dtype=jnp.uint32)) & 1).astype(bool).reshape(q, n * 32)


def index_scores(x, c_q, lp, cfg, rows):
    """I[rows, :] of one ``full`` layer: [len(rows), T], -inf after the
    query's own position."""
    t = x.shape[0]
    hi, di, dr = cfg["index_n_heads"], cfg["index_head_dim"], cfg["qk_rope_head_dim"]
    theta = cfg["rope_parameters"]["rope_theta"]
    q = (c_q @ f32(lp["idx_wq_b"])).reshape(t, hi, di)
    q = jnp.concatenate([rope_pairs(q[..., :dr], theta), q[..., dr:]], axis=-1)
    k = layer_norm(x @ f32(lp["idx_wk"]), lp["idx_k_norm_w"], lp["idx_k_norm_b"],
                   INDEX_NORM_EPS)
    k = jnp.concatenate([rope_pairs(k[..., :dr], theta), k[..., dr:]], axis=-1)
    w = x @ f32(lp["idx_weights"])
    dots = jax.nn.relu(jnp.einsum("qhd,jd->qhj", q[rows], k))
    scores = jnp.einsum("qhj,qh->qj", dots, w[rows]) * (hi * di) ** -0.5
    seen = jnp.arange(t)[None, :] <= rows[:, None]
    return jnp.where(seen, scores, -jnp.inf)


def select(scores, topk: int):
    """bool [Q, T]: the ``topk`` largest finite scores of each row (all of
    them where a row has fewer); of equal scores the earlier position."""
    k = min(topk, scores.shape[-1])
    _, idx = jax.lax.top_k(scores, k)
    rows = jnp.arange(scores.shape[0])[:, None]
    picked = jnp.zeros(scores.shape, bool).at[rows, idx].set(True)
    return picked & jnp.isfinite(scores)


def selection_bits(x, c_q, lp, cfg):
    """The selection of one ``full`` layer for every query, one bit a pair."""
    t = x.shape[0]
    qb = blocks(t, INDEX_BLOCK)

    def one(start):
        rows = start + jnp.arange(qb)
        return pack_bits(select(index_scores(x, c_q, lp, cfg, rows), cfg["index_topk"]))

    return jax.lax.map(one, jnp.arange(0, t, qb)).reshape(t, t // 32)


def attention(x, c_q, lp, cfg, bits):
    """Expanded latent attention of one layer over the selection ``bits``;
    x is the normed input [T, Dm]; returns [T, Dm]."""
    t = x.shape[0]
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    eps, theta = cfg["rms_norm_eps"], cfg["rope_parameters"]["rope_theta"]
    qb = blocks(t)
    ckv = x @ f32(lp["kv_a"])
    c_kv = rms_norm(ckv[:, :r], lp["kv_a_norm"], eps)
    k_rope = rope_pairs(ckv[:, r:], theta)                       # [T, dr]
    w_q = lp["q_b"].reshape(-1, h, dn + dr).transpose(1, 0, 2)   # [H, ql, dn+dr]
    w_kv = lp["kv_b"].reshape(r, h, dn + dv).transpose(1, 0, 2)  # [H, r, dn+dv]
    w_o = lp["wo"].reshape(h, dv, -1)                            # [H, dv, Dm]

    def head(acc, w):
        wq, wkv, wo = (f32(a) for a in w)
        q = c_q @ wq
        q = jnp.concatenate([q[:, :dn], rope_pairs(q[:, dn:], theta)], axis=-1)
        kv = c_kv @ wkv
        k = jnp.concatenate([kv[:, :dn], k_rope], axis=-1)       # [T, dn+dr]
        v = kv[:, dn:]

        def block(start):
            sel = unpack_bits(jax.lax.dynamic_slice_in_dim(bits, start, qb))
            s = jax.lax.dynamic_slice_in_dim(q, start, qb) @ k.T * (dn + dr) ** -0.5
            p = jax.nn.softmax(jnp.where(sel, s, -jnp.inf), axis=-1)
            return jnp.where(sel, p, 0.0) @ v

        o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, dv)
        return acc + o @ wo, None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), (w_q, w_kv, w_o))
    return out


def gates(x, lp, cfg):
    """[T, E_router]: each token's weight on each expert, zero off its top-k."""
    s = jax.nn.sigmoid(x @ f32(lp["router"]))
    _, topi = jax.lax.top_k(s + f32(lp["router_bias"]), cfg["num_experts_per_tok"])
    rows = jnp.arange(x.shape[0])[:, None]
    chosen = jnp.zeros_like(s).at[rows, topi].set(1.0) * s
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg["routed_scaling_factor"]


def ffn(x, w_gate, w_up, w_down):
    """SwiGLU, a block of rows at a time: 33 k rows of a 12,288-wide layer
    are 1.6 GB an intermediate in float32."""
    w_gate, w_up, w_down = f32(w_gate), f32(w_up), f32(w_down)

    def rows(h):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    t = x.shape[0]
    if t <= FFN_BLOCK or t % FFN_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(t // FFN_BLOCK, FFN_BLOCK, -1)).reshape(x.shape)


def experts(x, lp, cfg):
    """The part of the layer's sum that the experts held here give, plus the
    shared expert.  The experts are walked in a scan: one is upcast at a
    time, applied to every token and weighted by its gate."""
    first = int((cfg.get("expert_parallel") or {}).get("first_expert", 0))
    held = lp["w_gate"].shape[0]
    g = gates(x, lp, cfg)[:, first:first + held]

    def one(acc, e):
        w_gate, w_up, w_down, ge = e
        return acc + ge[:, None] * ffn(x, w_gate, w_up, w_down), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], g.T))
    return out + ffn(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def layer_params(params, cfg):
    """(kind, this layer's arrays) for every layer, in order."""
    kinds = [f"{m}_{i}" for m, i in zip(cfg["mlp_layer_types"], cfg["indexer_types"])]
    seen: dict = {}
    out = []
    for kind in kinds:
        i = seen.get(kind, 0)
        seen[kind] = i + 1
        out.append((kind, jax.tree.map(lambda a, i=i: a[i], params["groups"][kind])))
    return out


def forward(params, tokens, at, cfg, override=None):
    """(log-probabilities [n, V], index scores of the rows ``at`` per full
    layer [Lf, n, T], selections per full layer [Lf, T, T/32])."""
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        bits, probes, all_bits = None, [], []
        for kind, lp in layer_params(params, cfg):
            h = rms_norm(x, lp["attn_norm"], eps)
            c_q = rms_norm(h @ f32(lp["q_a"]), lp["q_a_norm"], eps)
            if kind.endswith("_full"):
                probes.append(index_scores(h, c_q, lp, cfg, at))
                bits = (selection_bits(h, c_q, lp, cfg) if override is None
                        else override[len(all_bits)])
                all_bits.append(bits)
            x = x + attention(h, c_q, lp, cfg, bits)
            h = rms_norm(x, lp["mlp_norm"], eps)
            x = x + (ffn(h, lp["w_gate"], lp["w_up"], lp["w_down"])
                     if kind.startswith("dense") else experts(h, lp, cfg))
        x = rms_norm(x, params["final_norm"], eps)[at]
        return (jax.nn.log_softmax(x @ f32(params["lm_head"]), axis=-1),
                jnp.stack(probes), jnp.stack(all_bits))


def make_forward(cfg: dict):
    return lambda params, tokens, at: forward(params, tokens, at, cfg)[0]


def make_probe(cfg: dict):
    return lambda params, tokens, at, override=None: forward(
        params, tokens, at, cfg, override)

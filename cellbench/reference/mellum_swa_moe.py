"""Plain reference of Mellum 2 (``mellum``, Mellum2-12B-A2.5B): a GQA decoder
whose layers are of two kinds by ``layer_types`` — ``sliding_attention`` (the
query at position p sees key j iff 0 <= p - j < ``sliding_window``) and
``full_attention`` (causal) — each kind with its own rope from
``rope_parameters``, and in every layer a softmax-routed mixture of experts.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no batching, no kernels, and no import from the program: the equations
are written out here from the configuration file's keys.  It reads the
engine's own weight arrays (``params["layers"]``, stacked over the layers,
``x @ W`` orientation) where they lie: the layers are scanned by index, one
layer's attention weights and one expert's three matrices are upcast at a time.

    h <- h + W_o Attn(rope_kind(q), rope_kind(k), v) over RMSNorm(h)
    h <- h + MoE(RMSNorm(h));   final RMSNorm;   head

Attention: q, k, v = x W_q, x W_k, x W_v; 32 query heads share 4 key-value
heads (query head j reads key-value head j // 8); a per-head RMSNorm on q and
k; the layer kind's rope on both (rotate-half: pairs (i, i + D/2)); scores
q·k · 128^-1/2 under the kind's mask; softmax; the weighted sum of v.

The two ropes, the HF way (``rope_parameters[kind]``):
  * ``default``: angle(p, i) = p · theta^(-2i/D); cos and sin as they are.
  * ``yarn``: pair i keeps f_i = theta^(-2i/D) where it turns more than
    ``beta_fast`` times over ``original_max_position_embeddings``, is divided
    by ``factor`` where it turns less than ``beta_slow`` times, and is blended
    linearly in the pair index between floor(d(beta_fast)) and
    ceil(d(beta_slow)), d(r) = D·ln(L / 2πr) / (2 ln theta) (``truncate``
    true, HF's default) — at EVERY position, not only past the trained
    context; and cos and sin are both multiplied by ``attention_factor``
    (the config's; 0.1·ln(factor) + 1 where it has none), so a full layer's
    scores carry its square.

MoE: p = softmax(x W_r) over all experts in float32, the
``num_experts_per_tok`` largest, divided by their sum (``norm_topk_prob``),
y = Σ_e w_e · W_down,e (SiLU(x W_gate,e) ⊙ x W_up,e): every expert is applied
to every token and weighted by its gate, which is zero where the router did
not choose it.  ``intermediate_size`` is used by no layer.

Where this departs from the published description, each for a stated reason
(the configuration file's ``assumed`` has the same list):

  * the per-head RMSNorm on q and k (``q_norm`` / ``k_norm``, over the head,
    before the rope): no key of the config announces it; its key set
    (``use_sliding_window``, ``max_window_layers``, ``norm_topk_prob``,
    ``moe_intermediate_size``) is the Qwen3-MoE family's, whose class carries
    the norm without a key.
  * no multi-token-prediction head: the language model alone.
  * ``kv_round`` (a negative control of the comparison, no part of the model):
    keys (after the rope) and values rounded to a narrower float, as a cache
    one precision down would hold them.

Long sequences: scores are computed for one key-value head and a block of
queries at a time, experts are visited one at a time, and the head a block of
the vocabulary at a time, so that 21 k tokens fit beside a served model.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``: the distribution over the next token after each
position in ``at``.  Tokens after the last position of interest are padding
and, under the causal mask, touch nothing before them.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32
QUERY_BLOCK = 256       # queries scored at a time, one key-value head
VOCAB_BLOCK = 8192      # columns of the head at a time
NO_WINDOW = 1 << 30     # a full layer's window: longer than any sequence
EXPERTS = ("w_gate", "w_up", "w_down")


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def rope_of(rope: dict, dim: int) -> tuple[np.ndarray, float]:
    """(inverse frequencies [dim/2], the factor on cos and sin) of one
    kind's ``rope_parameters``."""
    theta = float(rope["rope_theta"])
    f = np.asarray([theta ** (-2.0 * i / dim) for i in range(dim // 2)])
    kind = rope.get("rope_type", "default")
    if kind == "default":
        return f, 1.0
    if kind != "yarn":
        raise ValueError(f"no equations here for rope_type {kind!r}")
    factor = float(rope["factor"])
    trained = float(rope["original_max_position_embeddings"])

    def d(turns):
        return dim * math.log(trained / (2 * math.pi * turns)) / (2 * math.log(theta))

    lo = max(math.floor(d(float(rope.get("beta_fast", 32)))), 0)
    hi = min(math.ceil(d(float(rope.get("beta_slow", 1)))), dim - 1)
    g = np.clip((np.arange(dim // 2) - lo) / max(hi - lo, 0.001), 0.0, 1.0)
    m = rope.get("attention_factor") or 0.1 * math.log(factor) + 1.0
    return f * (1.0 - g) + f / factor * g, float(m)


def rope(x, inv, m):
    """x [T, H, D] at positions 0..T-1; pairs (i, i + D/2) rotate together;
    cos and sin are multiplied by ``m``."""
    t, _, d = x.shape
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = (jnp.cos(ang) * m)[:, None, :], (jnp.sin(ang) * m)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def query_block(t: int) -> int:
    return max(d for d in range(1, min(QUERY_BLOCK, t) + 1) if t % d == 0)


def attention(x, lp, cfg, inv, m, window, kv_round=None):
    """One layer's attention on its normed input [T, Dm] -> [T, Dm]; ``inv``,
    ``m`` and ``window`` are the layer kind's."""
    t = x.shape[0]
    hq, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    eps = cfg["rms_norm_eps"]
    q = rms_norm((x @ f32(lp["wq"])).reshape(t, hq, d), lp["q_norm"], eps)
    k = rms_norm((x @ f32(lp["wk"])).reshape(t, hk, d), lp["k_norm"], eps)
    v = (x @ f32(lp["wv"])).reshape(t, hk, d)
    q, k = rope(q, inv, m), rope(k, inv, m)
    if kv_round is not None:
        k, v = (jax.lax.reduce_precision(a, *kv_round) for a in (k, v))
    qb = query_block(t)
    at = jnp.arange(t)

    def head(qkv):
        qh, kh, vh = qkv                    # [T, G, D], [T, D], [T, D]

        def block(start):
            rows = start + jnp.arange(qb)
            gap = rows[:, None] - at[None, :]
            s = jnp.einsum("qgd,sd->gqs",
                           jax.lax.dynamic_slice_in_dim(qh, start, qb),
                           kh) * d ** -0.5
            s = jnp.where((gap >= 0) & (gap < window), s, -jnp.inf)
            return jnp.einsum("gqs,sd->qgd", jax.nn.softmax(s, axis=-1), vh)

        return jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, hq // hk, d)

    out = jax.lax.map(head, (q.reshape(t, hk, hq // hk, d).transpose(1, 0, 2, 3),
                             k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    return out.transpose(1, 0, 2, 3).reshape(t, hq * d) @ f32(lp["wo"])


def gates(h, router, cfg):
    """[T, E]: each token's weight on each expert, zero off its top-k."""
    probs = jax.nn.softmax(h @ f32(router), axis=-1)
    topv, topi = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg.get("norm_topk_prob", False):
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    rows = jnp.arange(h.shape[0])[:, None]
    return jnp.zeros_like(probs).at[rows, topi].set(topv)


def experts(h, router, stacks, layer, cfg):
    """The layer's mixture: expert e of layer ``layer`` is read from the
    stacked [L, E, ...] arrays where it lies, one at a time."""
    g = gates(h, router, cfg)

    def one(acc, e):
        w_gate, w_up, w_down = (f32(stacks[k][layer, e]) for k in EXPERTS)
        y = (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down
        return acc + g[:, e, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(h),
                          jnp.arange(stacks["w_gate"].shape[1]))
    return out


def head_logprobs(x, head):
    """log-softmax of x [n, Dm] @ head [Dm, V], a block of columns at a time:
    the float32 copy of the whole head is never made."""
    v = head.shape[1]
    blk = max(d for d in range(1, min(VOCAB_BLOCK, v) + 1) if v % d == 0)
    logits = jax.lax.map(
        lambda c: x @ f32(jax.lax.dynamic_slice_in_dim(head, c, blk, axis=1)),
        jnp.arange(0, v, blk))                           # [V/blk, n, blk]
    logits = logits.transpose(1, 0, 2).reshape(x.shape[0], v)
    return jax.nn.log_softmax(logits, axis=-1)


def layer_tables(cfg: dict) -> dict:
    """What a layer's kind decides, as arrays the layer scan indexes:
    ``inv`` [L, D/2] and ``factor`` [L] (its rope), ``band`` [L] (its
    window, ``NO_WINDOW`` for a full layer)."""
    kinds = list(cfg["layer_types"])
    ropes = {kind: rope_of(cfg["rope_parameters"][kind], cfg["head_dim"])
             for kind in set(kinds)}
    window = cfg["sliding_window"] if cfg.get("use_sliding_window", True) else None
    return {
        "inv": jnp.asarray(np.stack([ropes[k][0] for k in kinds]), F32),
        "factor": jnp.asarray([ropes[k][1] for k in kinds], F32),
        "band": jnp.asarray([window if k == "sliding_attention" and window
                             else NO_WINDOW for k in kinds], jnp.int32)}


def make_run(cfg: dict, kv_round=None):
    """``f(params, tokens, at, tables)``: the forward pass with the layers'
    tables as an argument, so that one compiled program serves the model and
    the controls that differ from it in a window or a rope
    (scripts/mellum_longctx_check.py)."""
    eps = cfg["rms_norm_eps"]

    def run(params, tokens, at, tables):
        layers = params["layers"]
        stacks = {k: layers[k] for k in EXPERTS}
        rest = {k: w for k, w in layers.items() if k not in EXPERTS}
        with jax.default_matmul_precision("highest"):
            def layer(x, l):
                lp = jax.tree.map(lambda w: w[l], rest)
                x = x + attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg,
                                  tables["inv"][l], tables["factor"][l],
                                  tables["band"][l], kv_round)
                h = rms_norm(x, lp["mlp_norm"], eps)
                return x + experts(h, lp["router"], stacks, l, cfg), None

            x, _ = jax.lax.scan(layer, f32(params["embed"][tokens]),
                                jnp.arange(len(cfg["layer_types"])))
            x = rms_norm(x, params["final_norm"], eps)[at]
            head = (params["embed"].T if cfg.get("tie_word_embeddings")
                    else params["lm_head"])
            return head_logprobs(x, head)

    return run


def make_forward(cfg: dict, kv_round=None):
    run, tables = make_run(cfg, kv_round), layer_tables(cfg)
    return lambda params, tokens, at: run(params, tokens, at, tables)

"""Plain reference of a looped decoder (Ouro): ``L`` sandwich-norm layers run
``T`` = ``total_ut_steps`` times over the same weights, the final norm after
every pass, an exit gate that chooses per token which pass's state goes to
the output head.  ``jax.numpy`` in float32 at the highest matmul precision,
one sequence, no cache, no batching, no kernels, and no import from the
program's models: the equations are written out here.

    h(0) = E[tokens]
    for t in 0..T-1:                                   # passes, shared weights
        x = h(t)
        for l in 0..L-1:                               # the same W_l in every pass
            a = RMSNorm(x; n1_l)                       # input_layernorm
            q, k, v = a Wq_l, a Wk_l, a Wv_l;  rope(q), rope(k)
            a = softmax(q K^T / sqrt(D) over positions <= own, THIS pass's K, V) V Wo_l
            x = x + RMSNorm(a; n2_l)                   # input_layernorm_2 (sandwich)
            m = RMSNorm(x; n3_l)                       # post_attention_layernorm
            m = (silu(m Wg_l) * (m Wu_l)) Wd_l
            x = x + RMSNorm(m; n4_l)                   # post_attention_layernorm_2
        h(t+1) = RMSNorm(x; n_final)                   # closes EVERY pass, feeds the next
        g_t = h(t+1) . w_gate + b_gate                 # exit gate, Linear(Dm -> 1)
    lam_t = sigmoid(g_t);  p_t = lam_t prod_{j<t}(1 - lam_j) for t < T-1;
    p_{T-1} = prod_{j<T-1}(1 - lam_j)
    exit pass s = first t with sum_{j<=t} p_j >= early_exit_threshold, else T-1
    logits = h(s+1) W_head

With no cache a pass's keys and values are simply what that pass computed:
a token of pass t never sees another pass's K/V.  All T passes run for every
token; the gate only selects.  What the published ``config.json`` does not
pin (no attention bias, no q/k norm, the gate's bias, the norm between
passes, the selection rule) is listed in the configuration's ``assumed``.

It reads the engine's own weight arrays (stacked on a leading layer axis,
``x @ W`` orientation: ``attn_norm`` = n1, ``post_attn_norm`` = n2,
``mlp_norm`` = n3, ``post_mlp_norm`` = n4, ``exit_gate_w`` / ``exit_gate_b``)
and upcasts one layer at a time inside the scan.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``: the distribution over the next token after each
position in ``at``.
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


def attention(a, lp, cfg):
    """Causal attention of one layer application; a [T, Dm] -> [T, Dm].
    Query head j reads key/value head j // (Hq / Hk) (Ouro: Hq == Hk)."""
    t = a.shape[0]
    hq, hk, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rope((a @ f32(lp["wq"])).reshape(t, hq, d), cfg["rope_theta"])
    k = rope((a @ f32(lp["wk"])).reshape(t, hk, d), cfg["rope_theta"])
    v = (a @ f32(lp["wv"])).reshape(t, hk, d)
    q = q.reshape(t, hk, hq // hk, d)
    scores = jnp.einsum("tkgd,skd->kgts", q, k) * (d ** -0.5)
    causal = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    scores = jnp.where(causal[None, None], scores, -jnp.inf)
    out = jnp.einsum("kgts,skd->tkgd", jax.nn.softmax(scores, axis=-1), v)
    return out.reshape(t, hq * d) @ f32(lp["wo"])


def swiglu(m, lp):
    return (jax.nn.silu(m @ f32(lp["w_gate"])) * (m @ f32(lp["w_up"]))) @ f32(lp["w_down"])


def layer(x, lp, cfg):
    eps = cfg["rms_norm_eps"]
    a = attention(rms_norm(x, lp["attn_norm"], eps), lp, cfg)
    x = x + rms_norm(a, lp["post_attn_norm"], eps)
    m = swiglu(rms_norm(x, lp["mlp_norm"], eps), lp)
    return x + rms_norm(m, lp["post_mlp_norm"], eps)


def passes(params, tokens, cfg):
    """(states [T, tokens, Dm], gates [T, tokens]): h(t+1) and g_t of every pass."""
    x = f32(params["embed"][tokens])
    states, gates = [], []
    for _ in range(int(cfg["total_ut_steps"])):
        x, _ = jax.lax.scan(lambda x, lp: (layer(x, lp, cfg), None), x, params["layers"])
        x = rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])
        states.append(x)
        gates.append(x @ f32(params["exit_gate_w"]) + f32(params["exit_gate_b"]))
    return jnp.stack(states), jnp.stack(gates)


def exit_pass(gates, threshold):
    """Per token, the first pass whose cumulated exit probability reaches the
    threshold, else the last.  gates [T, tokens] -> [tokens] int32."""
    n = gates.shape[0]
    lam = jax.nn.sigmoid(gates)
    alive = jnp.concatenate(      # prod_{j<t}(1 - lam_j)
        [jnp.ones_like(lam[:1]), jnp.cumprod(1.0 - lam, axis=0)[:-1]])
    p = jnp.concatenate([(lam * alive)[:-1], alive[-1:]])
    reached = jnp.cumsum(p, axis=0) >= threshold
    return jnp.where(reached.any(axis=0), jnp.argmax(reached, axis=0), n - 1)


def make_forward(cfg: dict):
    def forward(params, tokens, at):
        with jax.default_matmul_precision("highest"):
            states, gates = passes(params, tokens, cfg)
            chosen = exit_pass(gates[:, at], cfg.get("early_exit_threshold", 1.0))
            x = states[chosen, at]
            head = params["embed"].T if cfg.get("tie_word_embeddings") else params["lm_head"]
            return jax.nn.log_softmax(x @ f32(head), axis=-1)

    return forward

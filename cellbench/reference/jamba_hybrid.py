"""Plain reference of AI21-Jamba2-3B (``jamba``) whole: Mamba-1 selective
state-space layers, softmax attention with no positional term where layer i
has i % ``attn_layer_period`` == ``attn_layer_offset``, and one gated MLP
with no router behind every mixer (``num_experts`` 1).

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no chunks, no kernels, and no import from the program: the equations
are written out here from the configuration file's keys.  They are those of
``transformers/models/jamba/modeling_jamba.py`` (``JambaMambaMixer.
slow_forward``, ``JambaAttention``, ``JambaMLP``, the two decoder layers); a
test runs this file against ``JambaForCausalLM`` on the same weights.  It
reads the engine's own weight arrays (``params["groups"]["gqa" | "linear"]``,
stacked over the layers of a kind, ``x @ W`` orientation) and upcasts what
one layer needs.

    h0 = E[tokens]
    u  = h + Mixer(RMSNorm(h))
    h' = u + W_down(SiLU(W_gate n) ⊙ W_up n),   n = RMSNorm(u)
    logits = RMSNorm(h_L) Eᵀ                    (tied embeddings)

Mamba mixer, inner width I = mamba_expand · hidden_size, state width N,
rank R of the step's bottleneck, x_t the normed input:
  1. x̃ ‖ z = x W_in   (I + I columns, no bias);
  2. x̂_t = SiLU(sum_{i=0..K-1} w_i ⊙ x̃_{t-K+1+i} + b_conv), inputs before the
     first token zero;
  3. δ ‖ B ‖ C = x̂ W_x (R + N + N columns); δ, B and C each through an RMS
     norm of its own (Jamba's addition to Mamba);
  4. Δ_t = softplus(δ_t W_dt + b_dt) a channel; A = -exp(A_log) [N, I];
  5. h a channel in R^N, zero before the first token, **one token at a time
     under ``lax.scan``**:  h = exp(Δ_t ⊗ A) ⊙ h + (Δ_t ⊙ x̂_t) ⊗ B_t;
     y_t = h C_t + D ⊙ x̂_t;
  6. out = (y_t ⊙ SiLU(z_t)) W_out.
Attention layer: softmax(q k^T / sqrt(d)) v over the causal past, Hq query
heads on Hk key/value heads of d = hidden_size / Hq, no rope, no bias, no q/k
norm, no gate.

Where this departs from the published code, each for a stated reason (the
configuration file's ``assumed`` has the same list):

  * ``slow_forward`` rounds h to the activation dtype before the product
    with C (``ssm_state.to(dtype)``); here, and in the program, h stays
    float32 through the read-out (in float32, which the test against
    ``transformers`` runs, the two are the same);
  * A_log lies [N, I], the published [I, N] turned, as the program's state
    lies; W_in, W_x, W_dt, W_out and the convolution are the program's
    ``x @ W`` arrays;
  * A_log, D, b_dt, the convolution and the norms' weights are read from the
    parameter tree, which the seed fills (the config holds no values).

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``mamba_mixer`` and ``attention`` one layer's
mixer on its normed input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time
FFN_BLOCK = 1024        # rows of a feed-forward layer at a time
HEAD_BLOCK = 8192       # rows of the tied embedding at a time


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def dt_rank(cfg: dict) -> int:
    rank = cfg.get("mamba_dt_rank", "auto")
    return -(-cfg["hidden_size"] // 16) if rank == "auto" else rank


def mamba_mixer(x, lp, cfg):
    """One Mamba-1 layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    n, kk, eps = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["rms_norm_eps"]
    inner, rank = cfg["mamba_expand"] * cfg["hidden_size"], dt_rank(cfg)
    t = x.shape[0]
    proj = x @ f32(lp["w_in"])
    xin, z = proj[:, :inner], proj[:, inner:]
    padded = jnp.concatenate([jnp.zeros((kk - 1, inner), F32), xin])
    xs = jax.nn.silu(sum(padded[i:i + t] * f32(lp["conv_w"])[:, i]
                         for i in range(kk)) + f32(lp["conv_b"]))
    dbc = xs @ f32(lp["w_x"])
    delta = rms_norm(dbc[:, :rank], lp["dt_norm"], eps)
    b = rms_norm(dbc[:, rank:rank + n], lp["b_norm"], eps)
    c = rms_norm(dbc[:, rank + n:], lp["c_norm"], eps)
    step = jax.nn.softplus(delta @ f32(lp["w_dt"]) + f32(lp["dt_bias"]))  # [T, I]
    a = -jnp.exp(f32(lp["a_log"]))                                        # [N, I]

    def token(h, xs_):
        xt, bt, ct, st = xs_                          # [I] [N] [N] [I]
        h = jnp.exp(st[None, :] * a) * h + (st * xt)[None, :] * bt[:, None]
        return h, ct @ h

    _, y = jax.lax.scan(token, jnp.zeros((n, inner), F32), (xs, b, c, step))
    y = (y + f32(lp["d_skip"]) * xs) * jax.nn.silu(z)
    return y @ f32(lp["wo"])


def query_block(t: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)


def attention(x, lp, cfg):
    """One attention layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    t = x.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg["hidden_size"] // hq
    rep = hq // hk
    q = (x @ f32(lp["wq"])).reshape(t, hk, rep, d)
    k = (x @ f32(lp["wk"])).reshape(t, hk, d)
    v = (x @ f32(lp["wv"])).reshape(t, hk, d)
    at = jnp.arange(t)
    qb = query_block(t)

    def block(start):
        rows = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb)          # [qb, Hk, rep, d]
        s = jnp.einsum("qgrd,kgd->grqk", qs, k) * d ** -0.5
        s = jnp.where(at[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, hq * d)
    return o @ f32(lp["wo"])


def mlp(x, lp):
    """SwiGLU with no router, a block of rows at a time."""
    w_gate, w_up, w_down = f32(lp["mlp_gate"]), f32(lp["mlp_up"]), f32(lp["mlp_down"])

    def rows(h):
        return (jax.nn.silu(h @ w_gate) * (h @ w_up)) @ w_down

    t = x.shape[0]
    if t <= FFN_BLOCK or t % FFN_BLOCK:
        return rows(x)
    return jax.lax.map(rows, x.reshape(t // FFN_BLOCK, FFN_BLOCK, -1)).reshape(x.shape)


def runs(cfg: dict) -> list[tuple]:
    """[(kind, first index within the kind's stack, count)] for each run of
    consecutive layers of one kind: layer i attends iff i % attn_layer_period
    == attn_layer_offset (``JambaConfig.layers_block_type``)."""
    out, seen = [], {"gqa": 0, "linear": 0}
    for i in range(cfg["num_hidden_layers"]):
        attends = i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
        kind = "gqa" if attends else "linear"
        if out and out[-1][0] == kind:
            out[-1] = (kind, out[-1][1], out[-1][2] + 1)
        else:
            out.append((kind, seen[kind], 1))
        seen[kind] += 1
    return out


def head_block(vocab: int) -> int:
    return max(n for n in range(1, min(HEAD_BLOCK, vocab) + 1) if vocab % n == 0)


def forward(params, tokens, at, cfg):
    """The layers of a run go through ``lax.scan`` over their index, and the
    tied head a block of the vocabulary at a time, for memory alone: one
    layer's float32 copies are alive at a time beside the served model (as
    reference/granite_hybrid.py)."""
    eps = cfg["rms_norm_eps"]

    def layer(kind):
        mixer = attention if kind == "gqa" else mamba_mixer

        def one(x, i):
            lp = jax.tree.map(lambda a: a[i], params["groups"][kind])
            x = x + mixer(rms_norm(x, lp["attn_norm"], eps), lp, cfg)
            return x + mlp(rms_norm(x, lp["mlp_norm"], eps), lp), None
        return one

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        for kind, start, count in runs(cfg):
            x, _ = jax.lax.scan(layer(kind), x, start + jnp.arange(count))
        x = rms_norm(x, params["final_norm"], eps)[at]
        embed = params["embed"]
        rows = head_block(embed.shape[0])
        logits = jax.lax.map(lambda block: x @ f32(block).T,
                             embed.reshape(-1, rows, embed.shape[1]))
        logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], -1)
        return jax.nn.log_softmax(logits, axis=-1)


def make_forward(cfg: dict):
    return lambda params, tokens, at: forward(params, tokens, at, cfg)

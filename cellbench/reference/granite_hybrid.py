"""Plain reference of granite-4.0-h-small (``granitemoehybrid``) as one chip's
share of an expert-parallel deployment: Mamba-2 state-space layers where
``layer_types`` says ``mamba``, softmax attention with no positional term
where it says ``attention``, softmax-routed experts of which only those held
here add to the sum, one shared MLP in every layer, and Granite's four
multipliers.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no chunks of the recurrence, no kernels, and no import from the
program: the equations are written out here from the configuration file's
keys (they are the published ``GraniteMoeHybrid`` code's: Mamba-2's SSD,
arXiv:2405.21060, and ``GraniteMoeShared``'s experts).  It reads the engine's own weight arrays
(``params["groups"]["gqa" | "linear"]``, stacked over the layers of a kind,
``x @ W`` orientation) and upcasts what one layer needs.

    h0 = E[tokens] · embedding_multiplier
    u  = h + residual_multiplier · Mixer(RMSNorm(h))
    h' = u + residual_multiplier · (Routed(n) + SharedMLP(n)),  n = RMSNorm(u)
    logits = RMSNorm(h_L) Eᵀ / logits_scaling          (tied embeddings)

Mamba-2 mixer, H heads of width P, state width N, G groups, x_t the normed
input, I = H·P:
  1. z ‖ xBC ‖ dt = x W_in   (I + (I + 2·G·N) + H columns, no bias);
  2. xBC_t = SiLU(sum_{i=0..K-1} w_i ⊙ xBC_{t-K+1+i} + b_conv), inputs before
     the first token zero; split x [H, P], B [G, N], C [G, N];
  3. Δ_t = softplus(dt_t + dt_bias) a head; a_t = exp(Δ_t · A), A = -exp(A_log)
     a head (a scalar);
  4. S a head in R^{P x N}, zero before the first token, **one token at a
     time under ``lax.scan``**:  S = a_t S + (Δ_t x_t) ⊗ B_t;
     y_t = S C_t + D ⊙ x_t   (head h reads group h // (H / G));
  5. y_t = RMSNorm_I(y_t ⊙ SiLU(z_t)) · w_norm  (the gate inside the norm, one
     group over the whole width);  out = y_t W_out.
Attention layer: softmax(q k^T · attention_multiplier) v over the causal past,
Hq query heads on Hk key/value heads, no rope, no bias, no q/k norm, no gate.
Experts: x W_r over all the router's experts; the k largest logits; softmax
over those k; SwiGLU experts; the shared MLP the same form, unweighted.

Where this departs from the published config, each for a stated reason (the
configuration file's ``assumed`` has the same list):

  * A_log, dt_bias, D, the convolution and the norms' weights are read from
    the parameter tree, which the seed fills (the config holds no values);
  * ``time_step_limit`` is (0, inf): Δ is not clamped;
  * only experts ``first_expert .. first_expert + held - 1`` add to the sum;
    what the other chips' experts would add is left out, as in the program.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``make_layer(config)`` the expert layer alone
(the share test); ``mamba_mixer`` and ``attention`` one layer's mixer on its
normed input.
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


def mamba_mixer(x, lp, cfg):
    """One Mamba-2 layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    h, p, n, g = (cfg["mamba_n_heads"], cfg["mamba_d_head"],
                  cfg["mamba_d_state"], cfg["mamba_n_groups"])
    inner, kk = h * p, cfg["mamba_d_conv"]
    t = x.shape[0]
    proj = x @ f32(lp["w_in"])
    z, xbc, dt = (proj[:, :inner], proj[:, inner:2 * inner + 2 * g * n],
                  proj[:, 2 * inner + 2 * g * n:])
    padded = jnp.concatenate([jnp.zeros((kk - 1, xbc.shape[1]), F32), xbc])
    xbc = jax.nn.silu(sum(padded[i:i + t] * f32(lp["conv_w"])[:, i]
                          for i in range(kk)) + f32(lp["conv_b"]))
    xs = xbc[:, :inner].reshape(t, h, p)
    b = jnp.repeat(xbc[:, inner:inner + g * n].reshape(t, g, n), h // g, axis=1)
    c = jnp.repeat(xbc[:, inner + g * n:].reshape(t, g, n), h // g, axis=1)
    step = jax.nn.softplus(dt + f32(lp["dt_bias"]))              # [T, H]
    decay = jnp.exp(step * -jnp.exp(f32(lp["a_log"])))

    def token(s, xs_):
        xt, bt, ct, st, at = xs_                 # [H,P] [H,N] [H,N] [H] [H]
        s = at[:, None, None] * s + (st[:, None] * xt)[:, :, None] * bt[:, None, :]
        return s, jnp.einsum("hpn,hn->hp", s, ct)

    _, y = jax.lax.scan(token, jnp.zeros((h, p, n), F32),
                        (xs, b, c, step, decay))
    y = (y + f32(lp["d_skip"])[:, None] * xs).reshape(t, inner)
    y = rms_norm(y * jax.nn.silu(z), lp["out_norm"], cfg["rms_norm_eps"])
    return y @ f32(lp["wo"])


def query_block(t: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)


def attention(x, lp, cfg):
    """One attention layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    t = x.shape[0]
    hq, hk = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = cfg.get("head_dim") or cfg["hidden_size"] // hq
    rep = hq // hk
    q = (x @ f32(lp["wq"])).reshape(t, hk, rep, d)
    k = (x @ f32(lp["wk"])).reshape(t, hk, d)
    v = (x @ f32(lp["wv"])).reshape(t, hk, d)
    at = jnp.arange(t)
    qb = query_block(t)

    def block(start):
        rows = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb)          # [qb, Hk, rep, d]
        s = jnp.einsum("qgrd,kgd->grqk", qs, k) * cfg["attention_multiplier"]
        s = jnp.where(at[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("grqk,kgd->qgrd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, hq * d)
    return o @ f32(lp["wo"])


def gates(x, lp, cfg):
    """[T, E_router]: each token's weight on each expert, zero off its top-k:
    a softmax over the k largest logits."""
    logits = x @ f32(lp["router"])
    top, topi = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    rows = jnp.arange(x.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, topi].set(jax.nn.softmax(top, axis=-1))


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
    """The part of the layer's sum that the experts held here give: every
    expert held is applied to every token and weighted by its gate (zero
    where the router did not choose it)."""
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


def runs(layer_types: list) -> list[tuple]:
    """[(kind, first index within the kind's stack, count)] for each run of
    consecutive layers of one kind."""
    out, seen = [], {"gqa": 0, "linear": 0}
    for layer_type in layer_types:
        kind = "gqa" if layer_type == "attention" else "linear"
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
    layer's float32 copies are alive at a time beside the served model (a
    Python loop lets the compiler hoist every layer's; the whole embedding
    in float32 is 0.8 GB)."""
    eps, res = cfg["rms_norm_eps"], cfg["residual_multiplier"]

    def layer(kind):
        mixer = attention if kind == "gqa" else mamba_mixer

        def one(x, i):
            lp = jax.tree.map(lambda a: a[i], params["groups"][kind])
            x = x + res * mixer(rms_norm(x, lp["attn_norm"], eps), lp, cfg)
            h = rms_norm(x, lp["mlp_norm"], eps)
            return x + res * (routed(h, lp, cfg) + shared(h, lp)), None
        return one

    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens]) * cfg["embedding_multiplier"]
        for kind, start, count in runs(cfg["layer_types"]):
            x, _ = jax.lax.scan(layer(kind), x, start + jnp.arange(count))
        x = rms_norm(x, params["final_norm"], eps)[at]
        embed = params["embed"]
        rows = head_block(embed.shape[0])
        logits = jax.lax.map(lambda block: x @ f32(block).T,
                             embed.reshape(-1, rows, embed.shape[1]))
        logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], -1)
        return jax.nn.log_softmax(logits / cfg["logits_scaling"], axis=-1)


def make_forward(cfg: dict):
    return lambda params, tokens, at: forward(params, tokens, at, cfg)


def make_layer(cfg: dict):
    """``f(lp, x [T, Dm]) -> (routed part, shared part)`` of one expert
    layer on its normed input: what the share test adds up."""
    def layer(lp, x):
        with jax.default_matmul_precision("highest"):
            return routed(f32(x), lp, cfg), shared(f32(x), lp)
    return layer

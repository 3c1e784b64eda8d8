"""Plain reference of Solar-Open2 (``solar_open2``) as one chip's share of an
expert-parallel deployment: gated delta-rule linear attention with a decay per
key channel in the layers that ``gqa_layers`` does not name, gated softmax
attention with no positional term in those it does, sigmoid-routed experts of
which only those held here add to the sum, one shared expert in every layer.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no chunks, no kernels, and no import from the program: the equations
are written out here from the configuration file's keys.  It reads the
engine's own weight arrays (``params["groups"]["gqa" | "linear"]``, stacked
over the layers of a kind, ``x @ W`` orientation) and upcasts what one layer
needs.

    h <- h + Attn(RMSNorm(h));  h <- h + MoE(RMSNorm(h));  final RMSNorm; head

Linear layer, H heads of width d, x_t the normed input:
  1. q̂, k̂, v̂ = x W_q, x W_k, x W_v;
  2. c_t = SiLU(sum_{i=0..K-1} w_i ⊙ ĉ_{t-K+1+i}), inputs before the first
     token zero (K shifted adds, K = ``short_conv_kernel_size``);
  3. q_t = c^q / |c^q| · d^-1/2, k_t = c^k / |c^k| a head (eps 1e-6), v = c^v;
  4. g_t = -exp(A_log[h]) · softplus((x W_f↓) W_f↑ + b_dt) a key channel,
     alpha_t = exp g_t;  beta_t = 2 sigmoid(x W_β) a head;
  5. S a head in R^{d x d}, zero before the first token, **one token at a
     time under ``lax.scan``**:  S' = Diag(alpha_t) S;  u = beta_t (v_t -
     S'^T k_t);  S = S' + k_t u^T;  o_t = S^T q_t;
  6. y_t = [RMSNorm_d(o_t) ⊙ sigmoid((x W_g↓) W_g↑)] W_o.
GQA layer: softmax(q k^T d^-1/2) v over the causal past, Hq query heads on Hk
key/value heads, no rope, no q/k norm; o ⊙ sigmoid(x W_gate); W_o.
Experts: sigmoid(x W_r); the k largest of score + correction bias; the chosen
scores over their sum × ``routed_scaling_factor``; SwiGLU experts.

Where this departs from the published config, each for a stated reason (the
configuration file's ``assumed`` has the same list):

  * the config names the mechanism's switches (``kda_use_full_proj`` false,
    ``kda_allow_neg_eigval`` true, ``short_conv_kernel_size``) and not its
    formulas: steps 2-6 are Kimi Delta Attention's (arXiv:2510.26692) with
    the low-rank decay projection and beta in (0, 2); the gates' rank is
    ``head_dim`` and they have no bias;
  * routing follows GLM-4.5's (sigmoid, correction bias, one group): the
    config has neither ``scoring_func`` nor ``topk_method``;
  * the GQA gate is one an element, from the layer's normed input;
  * only experts ``first_expert .. first_expert + held - 1`` add to the sum;
    what the other chips' experts would add is left out, as in the program;
  * no vision tower, no multi-token-prediction layer: the tokens are text.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``make_layer(config)`` the expert layer alone
(the share test); ``linear_attention`` and ``gqa_attention`` one layer's
mixer on its normed input.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time, one kv head
FFN_BLOCK = 1024        # rows of a feed-forward layer at a time
QK_NORM_EPS = 1e-6


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def conv_silu(c, w):
    """c [T, D], w [D, K]: SiLU(sum_i w[:, i] ⊙ c_{t-K+1+i}), zeros before
    the first token — K shifted adds."""
    t, kk = c.shape[0], w.shape[1]
    padded = jnp.concatenate([jnp.zeros((kk - 1, c.shape[1]), F32), c])
    return jax.nn.silu(sum(padded[i:i + t] * f32(w)[:, i] for i in range(kk)))


def linear_attention(x, lp, cfg):
    """One linear layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    lin = cfg["linear_attn_config"]
    h, d = lin["num_heads"], lin["head_dim"]
    t = x.shape[0]
    conv_w = lp["conv_w"].reshape(3, h * d, -1)
    q, k, v = (conv_silu(x @ f32(lp[name]), conv_w[i]).reshape(t, h, d)
               for i, name in enumerate(("wq", "wk", "wv")))

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + QK_NORM_EPS)

    q, k = unit(q) * d ** -0.5, unit(k)
    a = (x @ f32(lp["decay_down"])) @ f32(lp["decay_up"]) + f32(lp["dt_bias"])
    g = -jnp.exp(f32(lp["a_log"]))[:, None] * jax.nn.softplus(a.reshape(t, h, d))
    beta = 2.0 * jax.nn.sigmoid(x @ f32(lp["w_beta"]))              # [T, H]

    def token(s, xs):
        qt, kt, vt, gt, bt = xs                  # [H, d] x4, [H]
        s = s * jnp.exp(gt)[..., None]           # Diag(alpha) S, rows = keys
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[..., None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, qt)
        return s, o

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), F32), (q, k, v, g, beta))
    o = rms_norm(o, lp["out_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid((x @ f32(lp["gate_down"])) @ f32(lp["gate_up"]))
    return (o.reshape(t, h * d) * gate) @ f32(lp["wo"])


def query_block(t: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)


def gqa_attention(x, lp, cfg):
    """One GQA layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    t = x.shape[0]
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
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
    return (o * jax.nn.sigmoid(x @ f32(lp["w_gate_attn"]))) @ f32(lp["wo"])


def gates(x, lp, cfg):
    """[T, E_router]: each token's weight on each expert, zero off its top-k."""
    s = jax.nn.sigmoid(x @ f32(lp["router"]))
    _, topi = jax.lax.top_k(s + f32(lp["router_bias"]), cfg["num_experts_per_tok"])
    rows = jnp.arange(x.shape[0])[:, None]
    chosen = jnp.zeros_like(s).at[rows, topi].set(1.0) * s
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / jnp.sum(chosen, axis=-1, keepdims=True)
    return chosen * cfg.get("routed_scaling_factor", 1.0)


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


def forward(params, tokens, at, cfg):
    eps = cfg["rms_norm_eps"]
    attending = set(cfg["gqa_layers"])
    seen = {"gqa": 0, "linear": 0}
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        for layer in range(cfg["num_hidden_layers"]):
            kind = "gqa" if layer in attending else "linear"
            lp = jax.tree.map(lambda a, i=seen[kind]: a[i], params["groups"][kind])
            seen[kind] += 1
            h = rms_norm(x, lp["attn_norm"], eps)
            x = x + (gqa_attention(h, lp, cfg) if kind == "gqa"
                     else linear_attention(h, lp, cfg))
            h = rms_norm(x, lp["mlp_norm"], eps)
            x = x + routed(h, lp, cfg) + shared(h, lp)
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

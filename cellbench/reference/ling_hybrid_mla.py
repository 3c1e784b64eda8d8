"""Plain reference of Ling-3.0-flash's language model as one chip's share of
an expert-parallel stage: Kimi-Delta-Attention layers (the delta rule with a
decay per key channel in its lower-bound form, both gates full-rank) with a
latent-attention (MLA) layer closing every ``layer_group_size`` layers, a
dense SwiGLU in the layers below ``first_k_dense_replace`` and, in the others,
sigmoid-routed experts chosen inside the ``topk_group`` best of ``n_group``
groups, of which only those held here add to the sum, plus one shared expert.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no chunks, no kernels, the latent attention in its *expanded* form
(keys and values a head, nothing absorbed), and no import from the program:
the equations are written out here from the configuration file's keys.  It
reads the engine's own weight arrays (``params["groups"][kind]``, kind the
mixer ``linear`` | ``mla`` with ``_dense`` behind it where the layer ends in
the dense MLP, stacked over the layers of a kind, ``x @ W`` orientation) and
upcasts what one layer — of the experts, one expert — needs.

    h <- h + Mixer(RMSNorm(h));  h <- h + FFN(RMSNorm(h));  final RMSNorm; head

The file's layer j is published layer L = ``published_layers[j]`` (the file
may be a cut of the stack); L is an MLA layer iff (L + 1) % ``layer_group_size``
== 0, ends in the dense MLP iff L < ``first_k_dense_replace``.

KDA layer, H heads of width d, x_t the normed input:
  1. q̂, k̂, v̂ = x W_q, x W_k, x W_v;
  2. c_t = SiLU(sum_{i=0..K-1} w_i ⊙ ĉ_{t-K+1+i}), inputs before the first
     token zero (K = ``short_conv_kernel_size``);
  3. q_t = c^q / |c^q| · d^-1/2, k_t = c^k / |c^k| a head (eps 1e-6), v = c^v;
     no positional term;
  4. g_t = b · sigmoid(exp(A_log[h]) · (x W_f + b_dt)) a key channel, b =
     ``kda_lower_bound`` < 0, alpha_t = exp g_t in (e^b, 1);
     beta_t = sigmoid(x W_β) a head, in (0, 1);
  5. S a head in R^{d x d}, zero before the first token, **one token at a
     time under ``lax.scan``**:  S' = Diag(alpha_t) S;  u = beta_t (v_t -
     S'^T k_t);  S = S' + k_t u^T;  o_t = S^T q_t;
  6. y_t = [RMSNorm_d(o_t) ⊙ sigmoid(x W_g)] W_o.
MLA layer: q = x W_q a head (nope ‖ rope); c ‖ k_pe = x W_kva; ĉ =
RMSNorm(c); k_nope ‖ v = ĉ W_kvb a head; rotary (adjacent pairs, θ
``rope_theta``) on q's rope part and on k_pe, which every head shares; causal
softmax at scale (nope + rope)^-1/2; o_h ⊙ sigmoid(x W_gate)_h; W_o.
Experts: s = sigmoid(x W_r); c = s + bias; a group's score the sum of its two
largest c; the ``topk_group`` best groups kept, c of the others set to 0; the
k largest c; weights s of the chosen / (their sum + 1e-20) ×
``routed_scaling_factor``; SwiGLU experts; the shared expert unscaled.

Where this departs from the published config, each for a stated reason (the
configuration file's ``assumed`` has the same list):

  * the config names the KDA switches (``no_kda_lora``, ``kda_safe_gate``,
    ``kda_lower_bound``, ``short_conv_kernel_size``, ``use_qk_norm``,
    ``linear_silu``, ``group_norm_size``) and not its formulas: steps 2-6 are
    Kimi Delta Attention's (arXiv:2510.26692) with flash-linear-attention's
    lower-bound gate; no rope in a KDA layer; the gates are full-rank from the
    layer's normed input, without bias but b_dt;
  * the layer rule (L + 1) % ``layer_group_size``, the head-wise gate from
    the normed input, no q/k norm inside MLA beyond ĉ's, rope by adjacent
    pairs without scaling, untied embeddings;
  * the router is DeepSeek-V3's grouped ``noaux_tc``;
  * only experts ``first_expert .. first_expert + held - 1`` add to the sum;
    what the other chips' experts would add is left out, as in the program;
  * no vision tower, no multi-token-prediction layer: the tokens are text.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``make_layer(config)`` the expert layer alone
(the share test); ``kda_attention`` and ``mla_attention`` one layer's mixer on
its normed input; ``gates`` the router.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time, one head
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


def kda_attention(x, lp, cfg):
    """One KDA layer's mixer; x [T, Dm] normed; returns [T, Dm]."""
    h, d = cfg["num_attention_heads"], cfg["head_dim"]
    t = x.shape[0]
    conv_w = lp["conv_w"].reshape(3, h * d, -1)
    q, k, v = (conv_silu(x @ f32(lp[name]), conv_w[i]).reshape(t, h, d)
               for i, name in enumerate(("wq", "wk", "wv")))

    def unit(y):
        return y * jax.lax.rsqrt(jnp.sum(y * y, axis=-1, keepdims=True) + QK_NORM_EPS)

    q, k = unit(q) * d ** -0.5, unit(k)
    a = (x @ f32(lp["w_decay"]) + f32(lp["dt_bias"])).reshape(t, h, d)
    g = float(cfg["kda_lower_bound"]) * jax.nn.sigmoid(
        jnp.exp(f32(lp["a_log"]))[:, None] * a)
    beta = jax.nn.sigmoid(x @ f32(lp["w_beta"]))                    # [T, H]

    def token(s, xs):
        qt, kt, vt, gt, bt = xs                  # [H, d] x4, [H]
        s = s * jnp.exp(gt)[..., None]           # Diag(alpha) S, rows = keys
        u = bt[:, None] * (vt - jnp.einsum("hkv,hk->hv", s, kt))
        s = s + kt[..., None] * u[:, None, :]
        o = jnp.einsum("hkv,hk->hv", s, qt)
        return s, o

    _, o = jax.lax.scan(token, jnp.zeros((h, d, d), F32), (q, k, v, g, beta))
    o = rms_norm(o, lp["out_norm"], cfg["rms_norm_eps"])
    gate = jax.nn.sigmoid(x @ f32(lp["w_out_gate"]))
    return (o.reshape(t, h * d) * gate) @ f32(lp["wo"])


def rope_pairs(x, theta: float):
    """x [T, D] at positions 0..T-1; adjacent pairs (2i, 2i+1) rotate
    together by p · theta^(-2i/D)."""
    t, d = x.shape
    inv = theta ** (-jnp.arange(0, d, 2, dtype=F32) / d)
    ang = jnp.arange(t, dtype=F32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    pairs = x.reshape(t, d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], axis=-1).reshape(t, d)


def query_block(t: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)


def mla_attention(x, lp, cfg):
    """Expanded latent attention of one layer; x the normed input [T, Dm];
    returns [T, Dm]."""
    t = x.shape[0]
    h, dn, dr, dv, r = (cfg["num_attention_heads"], cfg["qk_nope_head_dim"],
                        cfg["qk_rope_head_dim"], cfg["v_head_dim"], cfg["kv_lora_rank"])
    theta = float(cfg["rope_theta"])
    ckv = x @ f32(lp["kv_a"])
    c_kv = rms_norm(ckv[:, :r], lp["kv_a_norm"], cfg["rms_norm_eps"])
    k_pe = rope_pairs(ckv[:, r:], theta)                         # [T, dr]
    gate = jax.nn.sigmoid(x @ f32(lp["w_gate_heads"]))           # [T, H]
    w_q = lp["wq"].reshape(-1, h, dn + dr).transpose(1, 0, 2)    # [H, Dm, dn+dr]
    w_kv = lp["kv_b"].reshape(r, h, dn + dv).transpose(1, 0, 2)  # [H, r, dn+dv]
    w_o = lp["wo"].reshape(h, dv, -1)                            # [H, dv, Dm]
    at = jnp.arange(t)
    qb = query_block(t)

    def head(acc, w):
        wq, wkv, wo, gh = w
        q = x @ f32(wq)
        q = jnp.concatenate([q[:, :dn], rope_pairs(q[:, dn:], theta)], axis=-1)
        kv = c_kv @ f32(wkv)
        k = jnp.concatenate([kv[:, :dn], k_pe], axis=-1)         # [T, dn+dr]
        v = kv[:, dn:]

        def block(start):
            rows = start + jnp.arange(qb)
            s = jax.lax.dynamic_slice_in_dim(q, start, qb) @ k.T * (dn + dr) ** -0.5
            s = jnp.where(at[None, :] <= rows[:, None], s, -jnp.inf)
            return jax.nn.softmax(s, axis=-1) @ v

        o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, dv)
        return acc + (o * gh[:, None]) @ f32(wo), None

    out, _ = jax.lax.scan(head, jnp.zeros_like(x), (w_q, w_kv, w_o, gate.T))
    return out


def gates(x, lp, cfg):
    """[T, E_router]: each token's weight on each expert, zero off its top-k
    (DeepSeek-V3's ``get_topk_indices``: groups ranked by the sum of their two
    largest score + bias, the others' set to 0)."""
    t = x.shape[0]
    s = jax.nn.sigmoid(x @ f32(lp["router"]))
    c = s + f32(lp["router_bias"])
    n_group, kept = int(cfg.get("n_group", 1)), int(cfg.get("topk_group", 1))
    if n_group > 1:
        per_group = c.reshape(t, n_group, -1)
        rank = jnp.sort(per_group, axis=-1)[..., -2:].sum(axis=-1)
        _, gidx = jax.lax.top_k(rank, kept)
        keep = jnp.zeros((t, n_group)).at[jnp.arange(t)[:, None], gidx].set(1.0)
        c = (per_group * keep[..., None]).reshape(c.shape)
    _, topi = jax.lax.top_k(c, cfg["num_experts_per_tok"])
    chosen = jnp.zeros_like(s).at[jnp.arange(t)[:, None], topi].set(1.0) * s
    if cfg.get("norm_topk_prob", True):
        chosen = chosen / (jnp.sum(chosen, axis=-1, keepdims=True) + 1e-20)
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


EXPERT_KEYS = ("w_gate", "w_up", "w_down")


def routed(x, lp, cfg, expert=None):
    """The part of the layer's sum that the experts held here give: every
    expert held is applied to every token and weighted by its gate (zero
    where the router did not choose it).  ``expert(e)`` gives expert e's
    three matrices — by default out of ``lp``'s own stacks; ``forward`` reads
    them where they lie in the stack over layers — so that one expert is
    sliced and upcast at a time: a layer's 128 are 1.4 GB in bf16 and 2.8 GB
    in float32, beside a chip's 11.6 GB."""
    first = int((cfg.get("expert_parallel") or {}).get("first_expert", 0))
    if expert is None:
        held = lp["w_gate"].shape[0]
        expert = lambda e: tuple(lp[k][e] for k in EXPERT_KEYS)
    else:
        held = cfg["num_experts"]
    g = gates(x, lp, cfg)[:, first:first + held]

    def one(acc, e):
        return acc + g[:, e][:, None] * ffn(x, *expert(e)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), jnp.arange(held))
    return out


def shared(x, lp):
    return ffn(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])


def layer_kinds(cfg: dict) -> list[str]:
    """The parameter group of each of the file's layers."""
    n, period = cfg["num_hidden_layers"], cfg["layer_group_size"]
    published = cfg.get("published_layers") or list(range(n))
    return [("mla" if (i + 1) % period == 0 else "linear")
            + ("_dense" if i < cfg.get("first_k_dense_replace", 0) else "")
            for i in published]


def forward(params, tokens, at, cfg):
    eps = cfg["rms_norm_eps"]
    seen: dict[str, int] = {}
    with jax.default_matmul_precision("highest"):
        x = f32(params["embed"][tokens])
        for kind in layer_kinds(cfg):
            i = seen.get(kind, 0)
            seen[kind] = i + 1
            group = params["groups"][kind]
            lp = {k: a[i] for k, a in group.items() if k not in EXPERT_KEYS}
            h = rms_norm(x, lp["attn_norm"], eps)
            x = x + (mla_attention(h, lp, cfg) if kind.startswith("mla")
                     else kda_attention(h, lp, cfg))
            h = rms_norm(x, lp["mlp_norm"], eps)
            if kind.endswith("_dense"):
                x = x + ffn(h, lp["mlp_gate"], lp["mlp_up"], lp["mlp_down"])
            else:
                x = x + shared(h, lp) + routed(
                    h, lp, cfg, lambda e, group=group, i=i: tuple(
                        group[k][i, e] for k in EXPERT_KEYS))
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

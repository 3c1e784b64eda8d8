"""Plain reference of ZAYA1-8B (``model_type`` ``zaya``): in every layer a
compressed convolutional attention (CCA) sublayer and an expert sublayer
behind an MLP router whose hidden state is carried through the depth.

``jax.numpy`` in float32 at the highest matmul precision, one sequence, no
cache, no chunks, no kernels, and no import from the program: the equations
are written out here from the configuration file's keys and from the
published descriptions (*Compressed Convolutional Attention*,
arXiv:2510.04476; the ZAYA1 report, arXiv:2511.17127).  What the config's
keys do not pin is listed in the configuration file's ``assumed``.  It reads
the engine's own weight arrays (``params["layers"]``, stacked over the
layers, ``x @ W`` orientation) and upcasts what one layer needs.

Layer l on the residual stream h [T, Dm]; Hq query heads on Hk key/value
heads of width d, G = Hq / Hk, C = (Hq + Hk) · d:

  CCA sublayer, x = RMSNorm(h):
    1. q̃ = x W_q [T, Hq·d], k̃ = x W_k [T, Hk·d], c = q̃ ‖ k̃ [T, C];
    2. two causal convolutions over time, zeros before position 0: the first
       depth-wise with ``cca_time0`` taps, u_t = Σ_i w0[:, i] ⊙ c_{t-K0+1+i}
       + b0; the second grouped by head (Hq + Hk groups of d channels, a
       d x d matrix a head and tap) with ``cca_time1`` taps,
       z_t[g] = Σ_i u_{t-K1+1+i}[g] A[i, g] + b1[g];
    3. the q-k mean of the *pre-convolution* projections:
       m_q[t, i] = ½ (q̃[t, i] + k̃[t, i // G]),  m_k[t, j] = mean over the G
       query heads i of group j of m_q[t, i];  q = z_q + m_q, k = z_k + m_k;
    4. every head of q and k L2-normalised and scaled by √d, k also by
       exp(temp_j) a key/value head;
    5. rotary (rotate-half, ``rope_theta`` of ``rope_parameters.hybrid``) on
       the first d · ``partial_rotary_factor`` dimensions of every head, the
       others as they are;
    6. the value shift: v_t is two heads, head 0 = x_t W_v1, head 1 =
       x_{t-1} W_v2 (zero at t = 0);
    7. causal softmax attention, scale d^-1/2, query head i on key/value head
       i // G; the heads side by side times W_o.
  Expert sublayer, x = RMSNorm(h), r the router's state [T, R]:
    1. r_l = x W_d + b_d;  r_l <- r_l + γ_l ⊙ r_{l-1}, r_{l-1} as layer l-1
       left it (nothing is added in the first layer held);
    2. s = RMSNorm(r_l);  z = gelu(gelu(s W_1 + b_1) W_2 + b_2) W_3  [T, E+1]:
       E experts and one skip output;  p = softmax(z);
    3. e = argmax(p + β);  out = p_e · W_down,e (silu(x W_gate,e) ⊙ x W_up,e)
       for e < E and 0 for the skip.  Top-1, no renormalisation.
  Either sublayer f joins the stream as
    h <- (a_r ⊙ h + b_r) + (a_o ⊙ f(x) + b_o)      (four learned vectors).
  Final RMSNorm, tied head.

``make_forward(config)`` returns ``f(params, tokens [T], at [n]) ->
log-probabilities [n, V]``; ``cca``, ``route`` and ``experts`` are one
sublayer's parts on its normed input (the unit tests call them).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

F32 = jnp.float32
QUERY_BLOCK = 512       # queries scored at a time
HEAD_BLOCK = 8192       # rows of the tied embedding at a time
L2_EPS = 1e-6


def f32(x):
    return x.astype(F32)


def rms_norm(x, weight, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * f32(weight)


def shifted(x, by: int):
    """x_{t-by} for every t, zeros before position 0.  x [T, ...]."""
    if by == 0:
        return x
    return jnp.concatenate([jnp.zeros_like(x[:by]), x[:-by]])


def rotate(x, at, theta: float, width: int):
    """Rotate-half rotary on the first ``width`` dimensions of every head.
    x [T, H, d], at [T] positions."""
    half = width // 2
    inv = 1.0 / theta ** (jnp.arange(half, dtype=F32) * 2.0 / width)
    ang = at.astype(F32)[:, None] * inv
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:width]
    return jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin, x[..., width:]], axis=-1)


def unit(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + L2_EPS)


def query_block(t: int) -> int:
    return max(n for n in range(1, min(QUERY_BLOCK, t) + 1) if t % n == 0)


def cca(x, lp, cfg):
    """The CCA sublayer's mixer on its normed input x [T, Dm] -> [T, Dm]."""
    t = x.shape[0]
    hq, hk, d = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                 cfg["head_dim"])
    g = hq // hk
    k0, k1 = cfg["cca_time0"], cfg["cca_time1"]
    qt = x @ f32(lp["wq"])                                   # [T, Hq·d]
    kt = x @ f32(lp["wk"])                                   # [T, Hk·d]
    c = jnp.concatenate([qt, kt], axis=-1)
    w0 = f32(lp["conv0_w"])                                  # [C, K0]
    u = sum(shifted(c, k0 - 1 - i) * w0[:, i] for i in range(k0)) + f32(lp["conv0_b"])
    a = f32(lp["conv1_w"])                                   # [K1, Hq+Hk, d, d]
    ug = u.reshape(t, hq + hk, d)
    z = sum(jnp.einsum("tgi,gio->tgo", shifted(ug, k1 - 1 - i), a[i])
            for i in range(k1)) + f32(lp["conv1_b"]).reshape(hq + hk, d)
    qh, kh = qt.reshape(t, hk, g, d), kt.reshape(t, hk, 1, d)
    mq = 0.5 * (qh + kh)                                     # [T, Hk, G, d]
    mk = mq.mean(axis=2)                                     # [T, Hk, d]
    q = z[:, :hq] + mq.reshape(t, hq, d)
    k = z[:, hq:] + mk
    q = unit(q) * d ** 0.5
    k = unit(k) * d ** 0.5 * jnp.exp(f32(lp["temp"]))[:, None]
    rope = cfg["rope_parameters"]["hybrid"]
    width = int(d * rope["partial_rotary_factor"])
    at = jnp.arange(t)
    q = rotate(q, at, float(rope["rope_theta"]), width)
    k = rotate(k, at, float(rope["rope_theta"]), width)
    v = jnp.stack([x @ f32(lp["wv1"]), shifted(x @ f32(lp["wv2"]), 1)], axis=1)
    q = q.reshape(t, hk, g, d)
    qb = query_block(t)

    def block(start):
        rows = start + jnp.arange(qb)
        qs = jax.lax.dynamic_slice_in_dim(q, start, qb)      # [qb, Hk, G, d]
        s = jnp.einsum("qjrd,kjd->jrqk", qs, k) * d ** -0.5
        s = jnp.where(at[None, :] <= rows[:, None], s, -jnp.inf)
        return jnp.einsum("jrqk,kjd->qjrd", jax.nn.softmax(s, axis=-1), v)

    o = jax.lax.map(block, jnp.arange(0, t, qb)).reshape(t, hq * d)
    return o @ f32(lp["wo"])


def route(x, r_prev, lp, cfg):
    """(p [T, E+1] the softmax over the experts and the skip output, e [T]
    the pick, r [T, R] the router's state as this layer leaves it)."""
    r = x @ f32(lp["router_down"]) + f32(lp["router_down_b"])
    r = r + f32(lp["router_eda"]) * r_prev
    s = rms_norm(r, lp["router_norm"], cfg["rms_norm_eps"])
    hid = jax.nn.gelu(s @ f32(lp["router_w1"]) + f32(lp["router_b1"]),
                      approximate=False)
    hid = jax.nn.gelu(hid @ f32(lp["router_w2"]) + f32(lp["router_b2"]),
                      approximate=False)
    p = jax.nn.softmax(hid @ f32(lp["router_w3"]), axis=-1)
    return p, jnp.argmax(p + f32(lp["router_bias"]), axis=-1), r


def experts(x, p, e, lp):
    """p_e · expert_e(x) a token, 0 where e is the skip output: every expert
    is applied to every token and weighted by its gate (zero where the router
    did not pick it)."""
    n = lp["w_gate"].shape[0]
    gate = jnp.where(e[:, None] == jnp.arange(n), p[:, :n], 0.0)   # [T, E]

    def one(acc, xs):
        w_gate, w_up, w_down, ge = xs
        y = (jax.nn.silu(x @ f32(w_gate)) * (x @ f32(w_up))) @ f32(w_down)
        return acc + ge[:, None] * y, None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x),
                          (lp["w_gate"], lp["w_up"], lp["w_down"], gate.T))
    return out


def merge(h, out, res):
    """(a_r ⊙ h + b_r) + (a_o ⊙ out + b_o); res [4, Dm] = a_r, b_r, a_o, b_o."""
    res = f32(res)
    return (res[0] * h + res[1]) + (res[2] * out + res[3])


def head_block(vocab: int) -> int:
    return max(n for n in range(1, min(HEAD_BLOCK, vocab) + 1) if vocab % n == 0)


def forward(params, tokens, at, cfg):
    """The layers go through ``lax.scan`` over their index and the tied head a
    block of the vocabulary at a time, for memory alone: one layer's float32
    copies are alive at a time beside the served model (the whole embedding
    in float32 is 2.1 GB)."""
    eps = cfg["rms_norm_eps"]

    def layer(carry, lp):
        h, r = carry
        h = merge(h, cca(rms_norm(h, lp["attn_norm"], eps), lp, cfg),
                  lp["attn_res"])
        x = rms_norm(h, lp["mlp_norm"], eps)
        p, e, r = route(x, r, lp, cfg)
        return (merge(h, experts(x, p, e, lp), lp["mlp_res"]), r), None

    with jax.default_matmul_precision("highest"):
        h = f32(params["embed"][tokens])
        r0 = jnp.zeros((h.shape[0], cfg["router_hidden_size"]), F32)
        (h, _), _ = jax.lax.scan(layer, (h, r0), params["layers"])
        x = rms_norm(h, params["final_norm"], eps)[at]
        embed = params["embed"]
        rows = head_block(embed.shape[0])
        logits = jax.lax.map(lambda block: x @ f32(block).T,
                             embed.reshape(-1, rows, embed.shape[1]))
        logits = jnp.moveaxis(logits, 0, 1).reshape(x.shape[0], -1)
        return jax.nn.log_softmax(logits, axis=-1)


def make_forward(cfg: dict):
    return lambda params, tokens, at: forward(params, tokens, at, cfg)

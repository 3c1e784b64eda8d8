"""ZAYA1 (``model_type`` ``zaya``): a decoder in which **every** layer attends
through the paged K/V pool *and* keeps a per-sequence tail, and ends in top-1
routed experts behind an MLP router whose hidden state is carried from layer
to layer.  cellbench/reference/zaya_cca.py writes the equations out;
docs/linear_state.md says how the tails use the slot contract.

Per layer, on the residual stream h (either sublayer f joins it as
(a_r ⊙ h + b_r) + (a_o ⊙ f(RMSNorm(h)) + b_o), four learned vectors):

  * **Compressed convolutional attention.**  c = q̃ ‖ k̃ = x W_q ‖ x W_k; a
    depth-wise causal convolution over time (``cca_time0`` taps) and then one
    grouped by head (``cca_time1`` taps, a d x d matrix a head and tap:
    ops/linear_state.py ``short_conv`` / ``grouped_conv``); the q-k mean of
    the pre-convolution projections added; q and k L2-normalised a head and
    scaled by √d, k also by a learned temperature a key/value head; rotary on
    the first ``rotary_dim`` dimensions of a head; the value's second head
    shifted by one token; GQA through the pool and the Pallas kernels every
    dense model here uses (models/hybrid_linear.py ``paged_gqa``); W_o.  The
    pool holds the *mixed* k and v in LlamaModel's layout.
  * **Experts.**  r_l = x W_d + b_d + γ_l ⊙ r_{l-1}; logits over the experts
    and one **skip** output from a two-hidden-layer gelu MLP on RMSNorm(r_l);
    softmax, the pick by p + β, top-1 with no renormalisation; the skip
    output is a router output that no expert here holds, so
    ``grouped_expert_dispatch(held=)`` leaves those tokens out and they get
    exactly nothing.  The router is float32 at the highest matmul precision
    (a near-tie picks another whole expert).

The layer scan carries (h, r).  Parameters are stacked over the layers.

**What a sequence keeps beside its K/V rows** is held per engine slot, under
the contract of models/hybrid_linear.py (``slot_rows``: a row that starts at
position 0 starts from zeros, a row with no real token leaves its slot bit
for bit, ``state_pos`` and the position check): ``state`` [L, slots, W] is
the *last* c, the last output u of the first convolution and the last x W_v2
of each sequence and layer — what the next token's two convolutions and
value shift read — in the model's dtype.  One chip only, prefix reuse off,
and nothing that moves blocks knows the tails: the engine refuses those
paths at start-up (``private_cache_layout``, ``recurrent_state``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.glm_dsa import ROUTER_BIAS_STD
from dynamo_tpu.models.hybrid_linear import (
    QK_NORM_EPS,
    STATE_COUNT_KEYS,
    decode_rows_by_length,
    paged_gqa,
    slot_rows,
)
from dynamo_tpu.models.llama import (
    EXPERT_COUNT_KEYS,
    apply_rope,
    experts_touched,
    grouped_expert_dispatch,
    rms_norm,
)
from dynamo_tpu.ops import linear_state

Params = Any

__all__ = ["ZayaConfig", "ZayaModel", "route", "SKIP_COUNT_KEYS"]

_HI = jax.lax.Precision.HIGHEST
# what the seed gives the vectors a checkpoint would hold at a learned value
# and an initialisation at its neutral one: far enough from neutral that a
# term left out fails the comparison with the reference
RESIDUAL_STD = 0.02       # a_r, a_o round 1; b_r, b_o round 0
# the keys' log temperature, round TEMP_MEAN.  At 0 the attention logits of
# seeded weights have a standard deviation of 1: over a thousand rows of
# context every query reads nearly the mean of the values, the residual
# stream collapses onto what all rows share, twenty routers in a row send a
# decode step's rows to the same few experts (10.7-12.2 of 16 touched on the
# chip, by the seed) and the step's time follows the seed.  At 1.5 (logits
# x 4.5: a few rows of the context carry a query's weight, as a trained
# attention's do) a row keeps its identity and 64 rows touch what an even
# router's would, 15.4 of 16, at every depth (PERF.md section 6, PR 56)
TEMP_MEAN = 1.5
TEMP_STD = 0.1
BIAS_STD = 0.02           # the router's b_d, b_1, b_2
# the router's last matrix times this: fan-in-scaled weights behind two
# gelus give logits of standard deviation ~0.45, a softmax within a few
# percent of uniform and an expert sublayer that adds a sixteenth of an
# expert's output; at 4 the pick's probability is ~0.3-0.6
ROUTER_OUT_STD = 4.0
# the count an expert layer adds behind ``EXPERT_COUNT_KEYS``: picks on the
# skip output (router picks = held picks + skip picks)
SKIP_COUNT_KEYS = ("moe_skip_picks_total",)
_EXPERT_KEYS = ("w_gate", "w_up", "w_down")


@dataclass
class ZayaConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    num_kv_heads: int
    head_dim: int
    conv_taps: tuple               # (cca_time0, cca_time1)
    rotary_dim: int
    rope_theta: float
    moe_intermediate_size: int
    n_routed_experts: int          # the router has one output more: the skip
    router_hidden_size: int
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    dtype: str = "bfloat16"

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def router_outputs(self) -> int:
        return self.n_routed_experts + 1

    @property
    def conv_width(self) -> int:
        """q̃ ‖ k̃ of one token: what both convolutions run over."""
        return (self.num_heads + self.num_kv_heads) * self.head_dim

    @property
    def tail_width(self) -> int:
        """One slot's tail in one layer: the taps - 1 last c, the taps - 1
        last u, the last x W_v2."""
        k0, k1 = self.conv_taps
        return (k0 - 1 + k1 - 1) * self.conv_width + self.head_dim

    @classmethod
    def from_hf_config(cls, cfg: dict, dtype: str = "bfloat16") -> "ZayaConfig":
        """The published ``zaya`` keys -> ZayaConfig.  Raises, by name, on
        what this port does not compute."""
        g = cfg.get
        if g("model_type") != "zaya":
            raise NotImplementedError(f"model_type {g('model_type')!r}")
        n = int(g("num_hidden_layers"))
        kinds = list(g("layer_types") or ())
        if len(kinds) != n or set(kinds) - {"hybrid"}:
            raise NotImplementedError(
                f"layer_types {sorted(set(kinds))} over {len(kinds)} entries "
                f"for {n} layers ('hybrid' a layer; a 'hybrid_sliding' "
                "layer's window is not built)")
        if g("sliding_window") is not None:
            raise NotImplementedError(
                f"sliding_window {g('sliding_window')!r}")
        for key in ("attention_bias", "lm_head_bias"):
            if bool(g(key, False)):
                raise NotImplementedError(f"{key}=True")
        if not bool(g("tie_word_embeddings", True)):
            raise NotImplementedError("tie_word_embeddings=False")
        if g("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {g('hidden_act')!r}")
        if int(g("num_experts_per_tok", 1)) != 1:
            raise NotImplementedError(
                f"num_experts_per_tok {g('num_experts_per_tok')} (top-1)")
        rope = (g("rope_parameters") or {}).get("hybrid") or {}
        if rope.get("rope_type", "default") != "default":
            raise NotImplementedError(f"rope_type {rope.get('rope_type')!r}")
        hq, hk = int(g("num_attention_heads")), int(g("num_key_value_heads"))
        if hk != 2 or hq % hk:
            raise NotImplementedError(
                f"num_key_value_heads {hk} (the value shift is written for "
                "two key/value heads: one of this token, one of the last)")
        d = int(g("head_dim"))
        factor = float(rope.get("partial_rotary_factor",
                                g("partial_rotary_factor", 1.0)))
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=int(g("hidden_size")),
            num_layers=n, num_heads=hq, num_kv_heads=hk, head_dim=d,
            conv_taps=(int(g("cca_time0")), int(g("cca_time1"))),
            rotary_dim=int(d * factor),
            rope_theta=float(rope.get("rope_theta", g("rope_theta", 10000.0))),
            moe_intermediate_size=int(g("moe_intermediate_size")),
            n_routed_experts=int(g("num_experts")),
            router_hidden_size=int(g("router_hidden_size")),
            rms_norm_eps=float(g("rms_norm_eps", 1e-5)),
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype)


def route(lp: dict, x, r_prev, eps: float):
    """ZAYA's router on the normed input ``x`` [T, Dm] and the state
    ``r_prev`` [T, R] float32 the layer before left (zeros in the first):
    (p [T, E+1] float32, the softmax over the experts and the skip output;
    the pick [T] int32, the largest p + β; r [T, R] as this layer leaves it).
    float32 throughout, every product at the highest precision of the matrix
    unit: a near-tie must resolve as the reference's does."""
    f32 = jnp.float32

    def dot(a, w):
        return jnp.matmul(a, w.astype(f32), precision=_HI)

    with jax.named_scope("down"):
        r = dot(x.astype(f32), lp["router_down"]) + lp["router_down_b"].astype(f32)
    with jax.named_scope("eda"):
        r = r + lp["router_eda"].astype(f32) * r_prev
    with jax.named_scope("mlp"):
        s = rms_norm(r, lp["router_norm"].astype(f32), eps)
        hid = jax.nn.gelu(dot(s, lp["router_w1"]) + lp["router_b1"].astype(f32),
                          approximate=False)
        hid = jax.nn.gelu(dot(hid, lp["router_w2"]) + lp["router_b2"].astype(f32),
                          approximate=False)
        logits = dot(hid, lp["router_w3"])
    with jax.named_scope("pick"):
        p = jax.nn.softmax(logits, axis=-1)
        pick = jnp.argmax(p + lp["router_bias"].astype(f32), axis=-1)
    return p, pick.astype(jnp.int32), r


class ZayaModel:
    """Engine-facing functional model (same protocol as LlamaModel)."""

    private_cache_layout = True
    # tails per engine slot beside the pool: the engine hands ``forward`` the
    # slots of a prefill dispatch's rows, keeps prefix reuse off, and refuses
    # what packs several sequences into one row axis
    recurrent_state = True
    moe_count_keys = EXPERT_COUNT_KEYS + SKIP_COUNT_KEYS + STATE_COUNT_KEYS
    supports_ragged_prefill = False
    supports_unified_dispatch = False
    supports_seq_parallel = False
    # ``prefix_blocks`` goes to ``prefill_attention`` and nowhere else
    prefix_blocks_sizes_forward = False

    def __init__(self, config: ZayaConfig, kept=None):
        """``kept``: a function every array a sequence keeps between
        dispatches (its K/V rows and its tails) goes through before it is
        written; None: as computed.  The negative control of the check rounds
        them one precision down there (scripts/zaya_longctx_check.py)."""
        self.config = config
        self.kept = kept or (lambda x: x)
        self.sm_scale = float(config.head_dim ** -0.5)
        self._draw = jax.jit(self._draw_params)

    # ------------------------------------------------------------------ init
    def init_params(self, rng: jax.Array) -> Params:
        """Seeded weights: normal / sqrt(fan-in), norms 1; the convolutions a
        Conv1d's default U(±fan-in^-1/2), bias included; the residual vectors,
        the router's biases round their neutral values (``RESIDUAL_STD``,
        ``BIAS_STD``), the keys' log temperature round ``TEMP_MEAN``; γ
        uniform in [0.3, 0.7]; β as GLM's correction bias; the router's second and last
        matrices centred over their inputs (gelu's positive mean would
        otherwise give every token the same few experts: a share of the
        picks 0.05-5.6 times the even one, against 0.5-1.8 centred, which is
        what a trained router's balancing keeps) and the last scaled by
        ``ROUTER_OUT_STD``.  One program, as models/hybrid_linear.py's
        draw."""
        return self._draw(rng)

    def _draw_params(self, rng: jax.Array) -> Params:
        cfg = self.config
        dt, f32 = cfg.jax_dtype, jnp.float32
        n, dm, d = cfg.num_layers, cfg.hidden_size, cfg.head_dim
        hq, hk, c = cfg.num_heads, cfg.num_kv_heads, cfg.conv_width
        e, f, r = (cfg.n_routed_experts, cfg.moe_intermediate_size,
                   cfg.router_hidden_size)
        k0, k1 = cfg.conv_taps
        keys = iter(jax.random.split(rng, 40))

        def normal(shape, std=1.0, mean=0.0, dtype=dt):
            return (mean + std * jax.random.normal(next(keys), shape, f32)
                    ).astype(dtype)

        def dense(shape, fan_in):
            return normal(shape, 1.0 / math.sqrt(fan_in))

        def uniform(shape, bound, dtype=dt):
            return jax.random.uniform(next(keys), shape, f32, -bound,
                                      bound).astype(dtype)

        def residual():
            """a_r, b_r, a_o, b_o."""
            return normal((n, 4, dm), RESIDUAL_STD,
                          jnp.array([1.0, 0.0, 1.0, 0.0], f32)[:, None])

        def centred(shape, scale=1.0):
            """Fan-in-scaled, every column summing to zero over its inputs:
            what is the same in every hidden unit (gelu's positive mean) then
            adds nothing to any output."""
            w = jax.random.normal(next(keys), shape, f32)
            return ((w - w.mean(axis=1, keepdims=True))
                    * scale / math.sqrt(shape[1]))

        layers = {
            "attn_norm": jnp.ones((n, dm), dt),
            "wq": dense((n, dm, hq * d), dm),
            "wk": dense((n, dm, hk * d), dm),
            "wv1": dense((n, dm, d), dm),
            "wv2": dense((n, dm, d), dm),
            "conv0_w": uniform((n, c, k0), k0 ** -0.5),
            "conv0_b": uniform((n, c), k0 ** -0.5),
            "conv1_w": uniform((n, k1, hq + hk, d, d), (k1 * d) ** -0.5),
            "conv1_b": uniform((n, c), (k1 * d) ** -0.5),
            "temp": normal((n, hk), TEMP_STD, TEMP_MEAN, dtype=f32),
            "wo": dense((n, hq * d, dm), hq * d),
            "attn_res": residual(),
            "mlp_norm": jnp.ones((n, dm), dt),
            "router_down": dense((n, dm, r), dm),
            "router_down_b": normal((n, r), BIAS_STD, dtype=f32),
            "router_eda": jax.random.uniform(next(keys), (n, r), f32, 0.3, 0.7),
            "router_norm": jnp.ones((n, r), f32),
            "router_w1": dense((n, r, r), r),
            "router_b1": normal((n, r), BIAS_STD, dtype=f32),
            "router_w2": centred((n, r, r)).astype(dt),
            "router_b2": normal((n, r), BIAS_STD, dtype=f32),
            "router_w3": centred((n, r, e + 1), ROUTER_OUT_STD).astype(dt),
            "router_bias": normal((n, e + 1), ROUTER_BIAS_STD, dtype=f32),
            "w_gate": dense((n, e, dm, f), dm),
            "w_up": dense((n, e, dm, f), dm),
            "w_down": dense((n, e, f, dm), f),
            "mlp_res": residual(),
        }
        return {"embed": dense((cfg.vocab_size, dm), dm), "layers": layers,
                "final_norm": jnp.ones((dm,), dt)}

    def partition_specs(self) -> Params:
        raise NotImplementedError(
            "ZayaModel serves one pipeline stage on one chip; it has no "
            "partition specs (neither the hand-over between stages nor an "
            "exchange of experts across chips is built)")

    def cache_spec(self, quant: bool = False):
        if quant:
            raise NotImplementedError("int8 K/V beside per-slot tails")
        return {"kv": P(), "state": P(), "state_pos": P(), "moe_counts": P()}

    # --------------------------------------------------------------- kv cache
    def init_kv_cache(self, num_blocks: int, block_size: int, dtype=None,
                      slots: int | None = None):
        """``kv``: the K/V pool in LlamaModel's layout over every layer,
        [L, N, 2, Bs, Hk·D] (the mixed k and v), first in the pytree's order;
        ``state`` [L, slots, tail_width]: c ‖ u ‖ x W_v2 of the last token(s)
        of each slot's sequence, in the model's dtype; ``state_pos`` [slots];
        ``moe_counts`` int32 [L, 1, 8]: a layer's four expert counts and its
        skip picks, and in row 0 the slot contract's three."""
        cfg = self.config
        if dtype is not None and jnp.dtype(dtype) != jnp.dtype(cfg.jax_dtype):
            raise NotImplementedError(f"K/V cache dtype {dtype!r}")
        if slots is None:
            raise ValueError(
                "the tails are held per engine slot: init_kv_cache needs "
                "slots= (EngineCore passes max_batch_size)")
        return {
            "kv": jnp.zeros(
                (cfg.num_layers, num_blocks, 2, block_size,
                 cfg.num_kv_heads * cfg.head_dim), cfg.jax_dtype),
            "state": jnp.zeros((cfg.num_layers, slots, cfg.tail_width),
                               cfg.jax_dtype),
            "state_pos": jnp.zeros((slots,), jnp.int32),
            "moe_counts": jnp.zeros(
                (cfg.num_layers, 1, len(self.moe_count_keys)), jnp.int32),
        }

    def state_bytes_per_slot(self) -> int:
        cfg = self.config
        return (cfg.num_layers * cfg.tail_width
                * jnp.dtype(cfg.jax_dtype).itemsize)

    def state_update_impl(self) -> tuple[str, str]:
        return "xla", "a tail is three rows a slot and layer: no kernel"

    # ---------------------------------------------------------------- forward
    @staticmethod
    def _merge(h, out, res):
        """(a_r ⊙ h + b_r) + (a_o ⊙ out + b_o) in float32, rounded once."""
        f32 = jnp.float32
        res = res.astype(f32)
        return ((res[0] * h.astype(f32) + res[1])
                + (res[2] * out.astype(f32) + res[3])).astype(h.dtype)

    def _cca(self, lp, li, h, kv, state, rows, positions, block_tables,
             seq_lens, slot_idx, prefix_blocks, by_length):
        """The CCA sublayer on ``h`` [B, S, Dm]; ``kv`` / ``state`` are the
        whole leaves, ``li`` this layer's row of them.  ``rows`` as
        ``slot_rows`` gives them."""
        cfg = self.config
        b, s, _ = h.shape
        hq, hk, d, c = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                        cfg.conv_width)
        g = hq // hk
        k0, k1 = cfg.conv_taps
        slots, fresh, alive, n_real, _ = rows
        f32 = jnp.float32
        with jax.named_scope("attn_proj"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            qk = jnp.concatenate([x @ lp["wq"], x @ lp["wk"]], axis=-1)
            v1, v2 = x @ lp["wv1"], x @ lp["wv2"]
        with jax.named_scope("attn"):
            with jax.named_scope("cca"):
                at = li if slots is None else (li, slots)    # row i is slot i
                old = state[at]                              # [B, W]
                tail = jnp.where(fresh[:, None], 0, old)
                c_tail, u_tail, v_tail = jnp.split(
                    tail, ((k0 - 1) * c, (k0 - 1 + k1 - 1) * c), axis=-1)
                u, c_new = linear_state.short_conv(
                    qk, lp["conv0_w"], c_tail.reshape(b, k0 - 1, c), n_real,
                    lp["conv0_b"])
                # rounded here, so that the row the next dispatch reads from
                # the tail is the row this one multiplied
                u = u.astype(h.dtype)
                z, u_new = linear_state.grouped_conv(
                    u, lp["conv1_w"], u_tail.reshape(b, k1 - 1, c), n_real,
                    lp["conv1_b"])
                qt = qk[..., :hq * d].astype(f32).reshape(b, s, hk, g, d)
                kt = qk[..., hq * d:].astype(f32).reshape(b, s, hk, 1, d)
                mq = 0.5 * (qt + kt)
                z = z.reshape(b, s, hq + hk, d)
                q = z[:, :, :hq] + mq.reshape(b, s, hq, d)
                k = z[:, :, hq:] + mq.mean(axis=3)

                def unit(t):
                    return t * jax.lax.rsqrt(
                        jnp.sum(t * t, axis=-1, keepdims=True) + QK_NORM_EPS)

                q = unit(q) * d ** 0.5
                k = unit(k) * (d ** 0.5 * jnp.exp(lp["temp"].astype(f32)))[:, None]
                q = apply_rope(q, positions, cfg.rope_theta,
                               rotary_dim=cfg.rotary_dim).astype(h.dtype)
                k = apply_rope(k, positions, cfg.rope_theta,
                               rotary_dim=cfg.rotary_dim).astype(h.dtype)
                # the value shift: head 1 is the token before's
                vv = jnp.concatenate([v_tail[:, None].astype(v2.dtype), v2],
                                     axis=1)
                v = jnp.stack([v1, vv[:, :s]], axis=2)       # [B, S, 2, d]
                v_new = linear_state.carried_tail(vv, n_real, 1, state.dtype)
                new = jnp.concatenate(
                    [c_new.reshape(b, -1), u_new.reshape(b, -1),
                     v_new.reshape(b, -1)], axis=-1)
                # a row with no real token keeps its slot bit for bit
                state = state.at[at].set(
                    jnp.where(alive[:, None], self.kept(new), old))
                k, v = self.kept(k), self.kept(v)
            attn, kv = paged_gqa(q, k, v, kv, li, positions, block_tables,
                                 seq_lens, slot_idx, prefix_blocks, by_length,
                                 self.sm_scale)
        with jax.named_scope("attn_out"):
            out = attn.reshape(b, s, hq * d) @ lp["wo"]
            h = self._merge(h, out, lp["attn_res"])
        return h, kv, state

    def _experts(self, layers: dict, lp: dict, li, h, r, valid):
        """The expert sublayer and the layer's five counts: router picks,
        picks on an expert (not skipped), 1 (the call), experts touched, skip
        picks."""
        cfg = self.config
        b, s, dm = h.shape
        e = cfg.n_routed_experts
        xf = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps).reshape(b * s, dm)
        with jax.named_scope("router"):
            p, pick, r = route(lp, xf, r, cfg.rms_norm_eps)
            topi = pick[:, None]
            weights = jnp.take_along_axis(p, topi, axis=-1)
            real = valid.reshape(b * s, 1)
            here = (topi < e) & real
            skipped = (topi == e) & real
            counted = jnp.stack([
                real.sum(dtype=jnp.int32), here.sum(dtype=jnp.int32),
                jnp.int32(1), experts_touched(topi, 0, e),
                skipped.sum(dtype=jnp.int32)])
        with jax.named_scope("moe_experts"):
            # the skip output is a router output no expert here holds: its
            # tokens sort behind the last group and add exactly nothing
            routed = grouped_expert_dispatch(
                xf, weights, topi, cfg.router_outputs,
                layers["w_gate"], layers["w_up"], layers["w_down"],
                jax.nn.silu, layer=li, held=(0, e))
        h = self._merge(h, routed.reshape(b, s, dm), lp["mlp_res"])
        return h, r, counted

    def forward(self, params, tokens, positions, cache, block_tables,
                seq_lens, slot_idx, prefix_blocks=None, seq_slots=None):
        """(hidden [B,S,Dm], cache).  ``seq_slots`` int32 [B]: the engine
        slot of each row; None: row i is slot i, and B is the number of
        slots (a decode over the slot array).  Each row's S tokens are
        consecutive positions of one sequence, real tokens first."""
        cfg = self.config
        b, s = tokens.shape
        n_slots = cache["state_pos"].shape[0]
        if seq_slots is None and b != n_slots:
            raise ValueError(
                f"{b} rows without seq_slots, {n_slots} slots: a dispatch "
                "that is not over the slot array names its rows' slots")
        rows, state_pos, counted = slot_rows(
            cache["state_pos"], positions, slot_idx, seq_slots,
            cfg.num_layers)
        valid = rows[-1]
        by_length = (decode_rows_by_length(block_tables, seq_lens, positions)
                     if s == 1 else None)
        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(cfg.jax_dtype)
        n_expert_counts = len(EXPERT_COUNT_KEYS) + len(SKIP_COUNT_KEYS)
        counts = cache["moe_counts"].at[0, 0, n_expert_counts:].add(counted)
        layers = params["layers"]
        sliced = {k: v for k, v in layers.items() if k not in _EXPERT_KEYS}

        def step(carry, li):
            h, r, kv, state, counts = carry
            lp = jax.tree.map(lambda a: a[li], sliced)
            h, kv, state = self._cca(
                lp, li, h, kv, state, rows, positions, block_tables,
                seq_lens, slot_idx, prefix_blocks, by_length)
            with jax.named_scope("mlp"):
                h, r, picked = self._experts(layers, lp, li, h, r, valid)
                counts = counts.at[li, 0, :n_expert_counts].add(picked)
            return (h, r, kv, state, counts), None

        r0 = jnp.zeros((b * s, cfg.router_hidden_size), jnp.float32)
        (hidden, _, kv, state, counts), _ = jax.lax.scan(
            step, (hidden, r0, cache["kv"], cache["state"], counts),
            jnp.arange(cfg.num_layers, dtype=jnp.int32))
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return hidden, {"kv": kv, "state": state, "state_pos": state_pos,
                        "moe_counts": counts}

    def compute_logits(self, params, hidden):
        with jax.named_scope("logits"):
            w = params["embed"].T
            return jnp.matmul(hidden.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

"""DeepSeek-V2/V3 family (MLA + DeepSeekMoE) for paged serving.

Multi-head Latent Attention projects hidden states through low-rank
latents (``kv_a`` → norm → ``kv_b``) and splits queries/keys into a
no-position part and a small rotary part shared across heads; the MoE
layers combine routed experts (``moe_route``: softmax or sigmoid scores,
optionally group-limited, or chosen under V3's correction bias) scaled by
``routed_scaling_factor`` with always-on shared experts, and the first
``first_k_dense_replace`` layers use a plain dense MLP.  The variant with a
learned sparse-attention indexer is models/glm_dsa.py, which holds the
latent once in a cache of its own (ops/latent_cache.py).

TPU mapping:
  * Default ``attn_impl="absorbed"`` — the MLA deployment shape: the
    paged cache stores ONE shared latent row per token (c_hat ‖ roped
    k_pe, width kv_lora_rank+rope), queries absorb kv_b's K-half into
    latent space, attention runs as GQA with a single KV head, and the
    attended latent expands per head through kv_b's V-half.  This class
    keeps the generic K/V pool, whose two planes both hold the row, so
    a token costs 2·(kv_lora+rope) elements a layer (1,152 for
    DeepSeek-V2 vs 49,152 expanded at 128 heads); models/glm_dsa.py
    holds it once.  Logit-exact vs transformers.
  * ``attn_impl="expanded"`` keeps the per-head K/V oracle (V padded to
    qk_head_dim) — parity baseline and debugging aid.
  * Two ``lax.scan`` stacks — dense-MLP layers then MoE layers — because
    the two layer kinds carry different parameter pytrees; attention
    parameters are stacked per group.
  * Routed experts run the same sort-by-expert + ``lax.ragged_dot``
    grouped dispatch as the Llama-family MoE (models/llama.py), sharded
    TP-within-experts.
  * RoPE is DeepSeek's INTERLEAVED complex-pair form (adjacent element
    pairs rotate together), unlike the Llama rotate-half layout.
  * Attention goes through the generic dispatch
    (ops/paged_attention.py) as one kv head of width kv_lora+rope; the
    dispatch decides kernel or XLA as for any other model, and no
    environment variable is needed to serve this family.

Reference parity: the reference serves DeepSeek through vLLM (its patch
carries a DeepSeek MoE tweak, container/deps/vllm patch:4074); here the
family is native.  HF oracle: transformers DeepseekV2ForCausalLM.
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.llama import (
    grouped_expert_dispatch,
    rms_norm,
    rope_inv_freq,
)
# canonical axis names (utils/mesh.py) — same alias convention as llama.py
from dynamo_tpu.utils.mesh import AXIS_MODEL as _TP
from dynamo_tpu.utils.mesh import AXIS_SP
from dynamo_tpu.ops.paged_attention import (
    paged_attention_layer,
    write_kv_cache_layer,
)

Params = Any

__all__ = ["DeepseekConfig", "DeepseekModel", "convert_hf_state_dict"]


@dataclass
class DeepseekConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    q_lora_rank: Optional[int] = None      # None = direct q_proj (V2-Lite)
    intermediate_size: int = 0             # dense-MLP layers
    moe_intermediate_size: int = 0
    n_routed_experts: int = 0
    num_experts_per_tok: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    topk_method: str = "greedy"   # "group_limited_greedy" | "noaux_tc"
    scoring_func: str = "softmax"          # or "sigmoid"
    norm_topk_prob: bool = False
    n_group: int = 1
    topk_group: int = 1
    first_k_dense_replace: int = 0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    max_position_embeddings: int = 4096
    dtype: str = "bfloat16"
    attention_bias: bool = False
    # "absorbed" (default, the MLA deployment shape: latent cache, one
    # shared KV head) or "expanded" (per-head K/V oracle)
    attn_impl: str = "absorbed"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # ---- engine-facing surface (duck-typed like ModelConfig) ----
    @property
    def num_kv_heads(self) -> int:
        return 1 if self.attn_impl == "absorbed" else self.num_heads

    @property
    def head_dim(self) -> int:
        if self.attn_impl == "absorbed":
            return self.kv_lora_rank + self.qk_rope_head_dim
        return self.qk_head_dim  # cache row width (V padded up to it)

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @classmethod
    def from_hf(cls, cfg) -> "DeepseekConfig":
        """transformers DeepseekV2Config (object or dict) → DeepseekConfig."""
        g = (lambda k, d=None: cfg.get(k, d)) if isinstance(cfg, dict) \
            else (lambda k, d=None: getattr(cfg, k, d))
        # loud rejection of anything this port would get silently WRONG —
        # same policy as ModelConfig's rope_scaling handling
        if int(g("moe_layer_freq", 1)) != 1:
            raise NotImplementedError("moe_layer_freq != 1")
        if g("rope_scaling") not in (None, {}):
            raise NotImplementedError(
                "DeepSeek rope_scaling (yarn + mscale softmax correction) "
                "is not implemented yet — loading this checkpoint would "
                "produce silently wrong logits at every position"
            )
        method = g("topk_method", "greedy")
        if method not in ("greedy", "group_limited_greedy", "noaux_tc"):
            raise NotImplementedError(f"topk_method {method!r}")
        if g("scoring_func", "softmax") not in ("softmax", "sigmoid"):
            raise NotImplementedError(
                f"scoring_func {g('scoring_func')!r}"
            )
        if bool(g("attention_bias", False)):
            raise NotImplementedError(
                "attention_bias=True (biases would be silently dropped)"
            )
        return cls(
            vocab_size=g("vocab_size"),
            hidden_size=g("hidden_size"),
            num_layers=g("num_hidden_layers"),
            num_heads=g("num_attention_heads"),
            qk_nope_head_dim=g("qk_nope_head_dim"),
            qk_rope_head_dim=g("qk_rope_head_dim"),
            v_head_dim=g("v_head_dim"),
            kv_lora_rank=g("kv_lora_rank"),
            q_lora_rank=g("q_lora_rank"),
            intermediate_size=g("intermediate_size"),
            moe_intermediate_size=g("moe_intermediate_size", 0) or 0,
            n_routed_experts=g("n_routed_experts", 0) or 0,
            num_experts_per_tok=g("num_experts_per_tok", 0) or 0,
            n_shared_experts=g("n_shared_experts", 0) or 0,
            routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
            topk_method=method,
            scoring_func=g("scoring_func", "softmax"),
            norm_topk_prob=bool(g("norm_topk_prob", False)),
            n_group=g("n_group", 1) or 1,
            topk_group=g("topk_group", 1) or 1,
            first_k_dense_replace=g("first_k_dense_replace", 0) or 0,
            rms_norm_eps=float(g("rms_norm_eps", 1e-6)),
            rope_theta=float(g("rope_theta", 10000.0)),
            max_position_embeddings=g("max_position_embeddings", 4096),
            attention_bias=bool(g("attention_bias", False)),
        )


def apply_rope_interleaved(x: jax.Array, positions: jax.Array,
                           inv_freq: jax.Array) -> jax.Array:
    """DeepSeek rotary: adjacent element PAIRS (2i, 2i+1) rotate by
    pos·inv_freq[i] (the complex ``freqs_cis`` form in transformers),
    unlike Llama's rotate-half layout.  x: [B,S,H,Dr]."""
    b, s, h, d = x.shape
    angles = positions.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,d/2]
    sin = jnp.sin(angles)[:, :, None, :]
    xr = x.astype(jnp.float32).reshape(b, s, h, d // 2, 2)
    x0, x1 = xr[..., 0], xr[..., 1]
    out = jnp.stack([x0 * cos - x1 * sin, x0 * sin + x1 * cos], axis=-1)
    return out.reshape(b, s, h, d).astype(x.dtype)


def routing_groups(cfg: dict, total: int) -> dict:
    """``n_group`` / ``topk_group`` of the published keys ``cfg`` as the
    fields of a configuration, checked against the router's ``total``
    outputs."""
    groups = int(cfg.get("n_group", 1) or 1)
    kept = int(cfg.get("topk_group", 1) or 1)
    if total % groups or not 1 <= kept <= groups:
        raise ValueError(
            f"{total} router outputs in n_group {groups}, topk_group {kept}")
    return {"n_group": groups, "topk_group": kept}


def moe_route(cfg, router: jax.Array, xf: jax.Array, bias=None):
    """The DeepSeek family's routing.  ``xf`` [T, Dm] -> (weights [T, k]
    f32, expert ids [T, k]).  Scores are a softmax or, per expert, a
    sigmoid of the router's logits, computed in f32 (inputs AND weights
    cast before the matmul, as HF does: near-tie logits must resolve to the
    same experts).  The k experts are those of largest score — within the
    ``topk_group`` best groups for ``group_limited_greedy`` (V2's: a group's
    score is its largest); of largest score + ``bias`` for ``noaux_tc`` (the
    correction bias steers the choice only, the weight is the unbiased
    score), and with ``n_group`` > 1 within the ``topk_group`` groups whose
    two largest score + bias sum highest, the others' set to 0 (V3's
    ``get_topk_indices``, letter for letter).  ``norm_topk_prob`` divides
    the chosen weights by their sum; all are scaled by
    ``routed_scaling_factor``."""
    t = xf.shape[0]
    logits = xf.astype(jnp.float32) @ router.astype(jnp.float32)  # [T,E]
    if cfg.scoring_func == "sigmoid":
        scores = jax.nn.sigmoid(logits)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
    choice = scores
    if cfg.topk_method == "group_limited_greedy":
        e = scores.shape[-1]
        gs = scores.reshape(t, cfg.n_group, -1).max(axis=-1)  # [T,G]
        _, gidx = jax.lax.top_k(gs, cfg.topk_group)
        gmask = jnp.zeros_like(gs).at[
            jnp.arange(t)[:, None], gidx
        ].set(1.0)
        choice = scores = scores * jnp.repeat(
            gmask, e // cfg.n_group, axis=-1)
    elif cfg.topk_method == "noaux_tc":
        if bias is not None:
            choice = scores + bias.astype(jnp.float32)
        groups = cfg.n_group
        if groups > 1:
            per_group = choice.reshape(t, groups, -1)
            best_two, _ = jax.lax.top_k(per_group, 2)
            _, gidx = jax.lax.top_k(best_two.sum(axis=-1), cfg.topk_group)
            kept = jnp.zeros((t, groups), bool).at[
                jnp.arange(t)[:, None], gidx].set(True)
            choice = jnp.where(kept[:, :, None], per_group, 0.0).reshape(
                choice.shape)
    _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)  # [T,k]
    weights = jnp.take_along_axis(scores, topi, axis=-1)
    if cfg.norm_topk_prob:
        weights = weights / (weights.sum(axis=-1, keepdims=True) + 1e-20)
    return weights * cfg.routed_scaling_factor, topi


class DeepseekModel:
    """Engine-facing functional model (same protocol as LlamaModel)."""

    def __init__(self, config: DeepseekConfig):
        self.config = config
        self.sm_scale = float(config.qk_head_dim ** -0.5)
        self.inv_freq = rope_inv_freq(config.qk_rope_head_dim,
                                      config.rope_theta)

    # ------------------------------------------------------------------ init
    def _attn_params(self, keys, n_layers: int) -> dict:
        cfg = self.config
        dt = cfg.jax_dtype
        dm, h = cfg.hidden_size, cfg.num_heads
        qk, rope, v = cfg.qk_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)

        p = {
            "attn_norm": jnp.ones((n_layers, dm), dt),
            "mlp_norm": jnp.ones((n_layers, dm), dt),
            "kv_a": dense(next(keys), (n_layers, dm, cfg.kv_lora_rank + rope), dm),
            "kv_a_norm": jnp.ones((n_layers, cfg.kv_lora_rank), dt),
            "kv_b": dense(next(keys),
                          (n_layers, cfg.kv_lora_rank,
                           h * (cfg.qk_nope_head_dim + v)), cfg.kv_lora_rank),
            "wo": dense(next(keys), (n_layers, h * v, dm), h * v),
        }
        if cfg.q_lora_rank is None:
            p["wq"] = dense(next(keys), (n_layers, dm, h * qk), dm)
        else:
            p["q_a"] = dense(next(keys), (n_layers, dm, cfg.q_lora_rank), dm)
            p["q_a_norm"] = jnp.ones((n_layers, cfg.q_lora_rank), dt)
            p["q_b"] = dense(next(keys), (n_layers, cfg.q_lora_rank, h * qk),
                             cfg.q_lora_rank)
        return p

    def init_params(self, rng: jax.Array) -> Params:
        cfg = self.config
        dt = cfg.jax_dtype
        dm = cfg.hidden_size
        keys = iter(jax.random.split(rng, 32))

        def dense(key, shape, fan_in):
            return (jax.random.normal(key, shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)

        ld = cfg.first_k_dense_replace
        lm = cfg.num_layers - ld
        dense_layers = self._attn_params(keys, ld)
        dense_layers.update(
            w_gate=dense(next(keys), (ld, dm, cfg.intermediate_size), dm),
            w_up=dense(next(keys), (ld, dm, cfg.intermediate_size), dm),
            w_down=dense(next(keys), (ld, cfg.intermediate_size, dm),
                         cfg.intermediate_size),
        )
        fm = cfg.moe_intermediate_size
        fs = fm * cfg.n_shared_experts
        e = cfg.n_routed_experts
        moe_layers = self._attn_params(keys, lm)
        moe_layers.update(
            router=(jax.random.normal(next(keys), (lm, dm, e), jnp.float32)
                    / math.sqrt(dm)).astype(dt),
            w_gate=dense(next(keys), (lm, e, dm, fm), dm),
            w_up=dense(next(keys), (lm, e, dm, fm), dm),
            w_down=dense(next(keys), (lm, e, fm, dm), fm),
            shared_gate=dense(next(keys), (lm, dm, fs), dm),
            shared_up=dense(next(keys), (lm, dm, fs), dm),
            shared_down=dense(next(keys), (lm, fs, dm), fs),
        )
        if cfg.topk_method == "noaux_tc":
            # e_score_correction_bias: non-zero so that seeded weights
            # exercise the choice-only bias
            moe_layers["router_bias"] = 0.1 * jax.random.normal(
                next(keys), (lm, e), jnp.float32)
        return {
            "embed": dense(next(keys), (cfg.vocab_size, dm), dm),
            "dense_layers": dense_layers,
            "moe_layers": moe_layers,
            "final_norm": jnp.ones((dm,), dt),
            "lm_head": dense(next(keys), (dm, cfg.vocab_size), dm),
        }

    # -------------------------------------------------------------- sharding
    def partition_specs(self) -> Params:
        """TP over "model": attention heads column-split, wo row-split,
        MoE experts TP-within-experts (FFN dim), shared experts like a
        dense MLP.  (Single-host tested; mesh execution follows the same
        GSPMD path as the Llama family.)"""
        cfg = self.config

        def attn(n):
            p = {
                "attn_norm": P(None, None), "mlp_norm": P(None, None),
                "kv_a": P(None, None, None),
                "kv_a_norm": P(None, None),
                "kv_b": P(None, None, _TP),
                "wo": P(None, _TP, None),
            }
            if cfg.q_lora_rank is None:
                p["wq"] = P(None, None, _TP)
            else:
                p.update(q_a=P(None, None, None), q_a_norm=P(None, None),
                         q_b=P(None, None, _TP))
            return p

        dense_layers = attn(cfg.first_k_dense_replace)
        dense_layers.update(
            w_gate=P(None, None, _TP), w_up=P(None, None, _TP),
            w_down=P(None, _TP, None),
        )
        moe_layers = attn(cfg.num_layers - cfg.first_k_dense_replace)
        moe_layers.update(
            router=P(None, None, None),
            w_gate=P(None, None, None, _TP),
            w_up=P(None, None, None, _TP),
            w_down=P(None, None, _TP, None),
            shared_gate=P(None, None, _TP),
            shared_up=P(None, None, _TP),
            shared_down=P(None, _TP, None),
        )
        if cfg.topk_method == "noaux_tc":
            moe_layers["router_bias"] = P(None, None)
        return {
            "embed": P(None, None),
            "dense_layers": dense_layers,
            "moe_layers": moe_layers,
            "final_norm": P(None),
            "lm_head": P(None, _TP),
        }

    def cache_spec(self, quant: bool = False):
        if self.config.attn_impl == "absorbed":
            # ONE shared latent row per token (num_kv_heads == 1):
            # nothing head-sharded to split — the latent replicates (it
            # is tiny: kv_lora+rope), and so does its one-scale-per-token
            # pool
            data = P(None, None, None, None, None)
            scale_head = None
        else:
            data = P(None, None, None, None, _TP)
            # scale-pool head axis shards only when tile-exact (see
            # LlamaModel.cache_spec for the padded-axis rationale)
            scale_head = (_TP if self.config.num_kv_heads % 8 == 0
                          else None)
        if not quant:
            return data
        from dynamo_tpu.ops.kv_quant import QuantKvCache

        return QuantKvCache(data, P(None, None, None, scale_head, None))

    # --------------------------------------------------------------- kv cache
    def init_kv_cache(self, num_blocks: int, block_size: int, dtype=None):
        cfg = self.config
        # the engine-facing num_kv_heads/head_dim properties encode the
        # two cache forms: absorbed = ONE latent row of kv_lora+rope per
        # token (still ~43x smaller than expanded at V2's 128 heads),
        # expanded = per-head rows of qk_head_dim (V padded up to it)
        hk = cfg.num_kv_heads
        width = hk * cfg.head_dim
        shape = (cfg.num_layers, num_blocks, 2, block_size, width)
        dt = dtype or cfg.jax_dtype
        if str(dt) in ("int8", "<dtype: int8>") or dt == jnp.int8:
            # int8 on top of the latent cache is what fits real DeepSeek
            # shapes on 16GiB chips: same QuantKvCache layout as the GQA
            # models (per-token-per-head scales; ONE scale/token for the
            # absorbed latent), transparently handled by the write and
            # attention paths (ops/kv_quant.py)
            from dynamo_tpu.ops.kv_quant import QuantKvCache, scale_tile

            hp, sp = scale_tile(hk, block_size)
            return QuantKvCache(
                jnp.zeros(shape, jnp.int8),
                jnp.ones((cfg.num_layers, num_blocks, 2, hp, sp),
                         jnp.float32),
            )
        if str(dt) not in (str(cfg.jax_dtype), cfg.dtype):
            raise NotImplementedError(f"MLA cache dtype {dt!r}")
        return jnp.zeros(shape, cfg.jax_dtype)

    # ---------------------------------------------------------------- forward
    def _qkv_latent(self, lp, x, positions):
        """Shared front half of both attention forms: per-head queries
        (nope ‖ roped pe) and the per-token latent pieces."""
        cfg = self.config
        b, s, _ = x.shape
        nh, nope = cfg.num_heads, cfg.qk_nope_head_dim
        if cfg.q_lora_rank is None:
            q = x @ lp["wq"]
        else:
            q = rms_norm(x @ lp["q_a"], lp["q_a_norm"], cfg.rms_norm_eps) \
                @ lp["q_b"]
        q = q.reshape(b, s, nh, cfg.qk_head_dim)
        q_nope, q_pe = q[..., :nope], q[..., nope:]
        q_pe = apply_rope_interleaved(q_pe, positions, self.inv_freq)

        ckv = x @ lp["kv_a"]  # [B,S, kv_lora + rope]
        c_kv, k_pe = ckv[..., :cfg.kv_lora_rank], ckv[..., cfg.kv_lora_rank:]
        c_hat = rms_norm(c_kv, lp["kv_a_norm"], cfg.rms_norm_eps)
        k_pe = apply_rope_interleaved(
            k_pe[:, :, None, :], positions, self.inv_freq
        )  # [B,S,1,rope] — shared across heads
        return q_nope, q_pe, c_hat, k_pe

    def _attention(self, lp, li, h_in, positions, cache, block_tables,
                   seq_lens, slot_idx):
        if self.config.attn_impl == "absorbed":
            return self._attention_absorbed(
                lp, li, h_in, positions, cache, block_tables, seq_lens,
                slot_idx,
            )
        return self._attention_expanded(
            lp, li, h_in, positions, cache, block_tables, seq_lens, slot_idx,
        )

    def _attention_expanded(self, lp, li, h_in, positions, cache,
                            block_tables, seq_lens, slot_idx):
        """Oracle form: materialise per-head K/V like a GQA model (cache
        row H·qk_head_dim, V padded).  Logit-exact, memory-hungry."""
        cfg = self.config
        b, s = positions.shape
        nh = cfg.num_heads
        nope, rope, vd = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        x = rms_norm(h_in, lp["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_pe, c_hat, k_pe = self._qkv_latent(lp, x, positions)
        kv = (c_hat @ lp["kv_b"]).reshape(b, s, nh, nope + vd)
        k_nope, v = kv[..., :nope], kv[..., nope:]

        q = jnp.concatenate([q_nope, q_pe], axis=-1)  # [B,S,H,qk_head]
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_pe, (*k_nope.shape[:-1], rope))],
            axis=-1,
        )
        # V padded to the cache row width; sliced back after attention
        v_pad = jnp.pad(v, ((0, 0), (0, 0), (0, 0),
                            (0, cfg.qk_head_dim - vd)))
        cache = write_kv_cache_layer(cache, li, k, v_pad, slot_idx)
        attn = paged_attention_layer(
            q, cache, li, block_tables, seq_lens, positions,
            sm_scale=self.sm_scale,
        )  # [B,S,H,qk_head]
        attn = attn[..., :vd].reshape(b, s, nh * vd)
        return h_in + attn @ lp["wo"], cache

    def _absorbed_qkv(self, lp, h_in, positions):
        """Shared absorption front-end (paged `_attention_absorbed` AND
        the ring `forward_seq_parallel`): queries projected INTO the
        latent space through kv_b's K-half, and the one shared KV row.
        Returns (q_lat [B,S,H,r+rope], row [B,S,1,r+rope], w_v).  The
        absorption identity:
          q_nope[h]·k_nope[h] = q_nope[h]·(Wk[h]ᵀ c_hat)
                              = (Wk[h] q_nope[h]) · c_hat."""
        cfg = self.config
        nh = cfg.num_heads
        nope, vd, r = (cfg.qk_nope_head_dim, cfg.v_head_dim,
                       cfg.kv_lora_rank)
        x = rms_norm(h_in, lp["attn_norm"], cfg.rms_norm_eps)
        q_nope, q_pe, c_hat, k_pe = self._qkv_latent(lp, x, positions)
        kv_b = lp["kv_b"].reshape(r, nh, nope + vd)
        w_k = kv_b[..., :nope]            # [r, H, nope]
        w_v = kv_b[..., nope:]            # [r, H, vd]
        q_eff = jnp.einsum("bshn,rhn->bshr", q_nope, w_k)
        q_lat = jnp.concatenate([q_eff, q_pe], axis=-1)
        row = jnp.concatenate(
            [c_hat[:, :, None, :], k_pe], axis=-1
        )  # the ONE shared KV row; K == V == latent
        return q_lat, row, w_v

    def _absorbed_out(self, lp, h_in, attn, w_v):
        """Shared absorption back-end: expand attended latents per head
        through kv_b's V-half and project out."""
        cfg = self.config
        b, s = h_in.shape[:2]
        out = jnp.einsum("bshr,rhv->bshv",
                         attn[..., :cfg.kv_lora_rank], w_v)
        return h_in + out.reshape(b, s, cfg.num_heads * cfg.v_head_dim) \
            @ lp["wo"]

    def _attention_absorbed(self, lp, li, h_in, positions, cache,
                            block_tables, seq_lens, slot_idx):
        """Absorbed form (the MLA deployment shape): attention runs as
        GQA with ONE shared KV head whose row is the cached latent
        (c_hat ‖ k_pe) — see `_absorbed_qkv` for the identity.  Cache
        cost per token: the latent row (stored twice — the pool's K/V
        planes) vs 2·H·qk_head_dim expanded."""
        q_lat, row, w_v = self._absorbed_qkv(lp, h_in, positions)
        cache = write_kv_cache_layer(cache, li, row, row, slot_idx)
        attn = paged_attention_layer(
            q_lat, cache, li, block_tables, seq_lens, positions,
            sm_scale=self.sm_scale,
        )  # [B,S,H,r+rope] — attended latents per head
        return self._absorbed_out(lp, h_in, attn, w_v), cache

    def _moe_mlp(self, lp, x):
        """DeepSeekMoE: ``moe_route``'s choice and weights through the
        grouped ragged_dot dispatch, plus the always-on shared experts."""
        cfg = self.config
        b, s, d = x.shape
        t = b * s
        xf = x.reshape(t, d)
        weights, topi = moe_route(cfg, lp["router"], xf,
                                  lp.get("router_bias"))
        routed = grouped_expert_dispatch(
            xf, weights, topi, cfg.n_routed_experts,
            lp["w_gate"], lp["w_up"], lp["w_down"], jax.nn.silu,
        )

        shared = (jax.nn.silu(xf @ lp["shared_gate"]) * (xf @ lp["shared_up"])
                  ) @ lp["shared_down"]
        return (routed + shared).reshape(b, s, d)

    def forward(self, params, tokens, positions, cache, block_tables,
                seq_lens, slot_idx, prefix_blocks=None):
        """(hidden [B,S,Dm], cache).  ``prefix_blocks`` is accepted for
        engine compatibility; MLA always takes the generic paged path."""
        cfg = self.config
        hidden = params["embed"][tokens].astype(cfg.jax_dtype)

        def dense_step(carry, layer_in):
            h, cache = carry
            lp, li = layer_in
            h, cache = self._attention(lp, li, h, positions, cache,
                                       block_tables, seq_lens, slot_idx)
            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
            h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) \
                @ lp["w_down"]
            return (h, cache), None

        def moe_step(carry, layer_in):
            h, cache = carry
            lp, li = layer_in
            h, cache = self._attention(lp, li, h, positions, cache,
                                       block_tables, seq_lens, slot_idx)
            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
            h = h + self._moe_mlp(lp, x)
            return (h, cache), None

        ld = cfg.first_k_dense_replace
        carry = (hidden, cache)
        if ld:
            carry, _ = jax.lax.scan(
                dense_step, carry,
                (params["dense_layers"], jnp.arange(ld, dtype=jnp.int32)),
            )
        carry, _ = jax.lax.scan(
            moe_step, carry,
            (params["moe_layers"],
             jnp.arange(ld, cfg.num_layers, dtype=jnp.int32)),
        )
        hidden, cache = carry
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return hidden, cache

    @property
    def supports_seq_parallel(self) -> bool:
        """Ring-attention prefill exists only for the absorbed cache form
        (the expanded oracle is not a deployment shape) — the engine's
        construction-time guard reads this so an unsupported config fails
        at startup, not on the first long prompt."""
        return self.config.attn_impl == "absorbed"

    def forward_seq_parallel(self, params, tokens, positions, mesh,
                             sp_axis: str = AXIS_SP):
        """Long-context MLA prefill with ring attention (context
        parallelism), the engine's SP path for prompts beyond one chip's
        comfort (EngineConfig.sp_prefill_threshold).

        The absorbed form is ring-friendly: each device's sequence chunk
        computes its latent rows (c_hat ‖ k_pe) and latent-space queries;
        attention runs as GQA with ONE shared KV head whose rows rotate
        over ICI (ops/ring_attention.py — hq/hk=H broadcast fuses into
        the matmuls), and the attended latent expands per head through
        kv_b's V-half — the same absorption identity as the paged form
        (`_attention_absorbed`), so results match it exactly.

        Returns (hidden [B,S,Dm], kv [L,2,B,S,width]) with the sequence
        sharding kept; the kv output is the latent row duplicated into
        the generic pool's K/V planes, exactly what the engine scatters
        into paged-cache blocks after a long prefill.
        """
        from dynamo_tpu.ops.ring_attention import ring_attention

        cfg = self.config
        if cfg.attn_impl != "absorbed":
            raise NotImplementedError(
                "seq-parallel MLA prefill needs attn_impl='absorbed' "
                "(the expanded oracle is not a deployment shape)")
        hidden = params["embed"][tokens].astype(cfg.jax_dtype)

        def attn_sp(lp, h_in):
            q_lat, row, w_v = self._absorbed_qkv(lp, h_in, positions)
            attn = ring_attention(
                q_lat, row, row, positions, positions, mesh=mesh,
                axis=sp_axis, sm_scale=self.sm_scale,
            )  # [B,S,H,r+rope] attended latents per head
            return self._absorbed_out(lp, h_in, attn, w_v), row[:, :, 0]

        def dense_step(h, lp):
            h, row = attn_sp(lp, h)
            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
            h = h + (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) \
                @ lp["w_down"]
            return h, jnp.stack([row, row], axis=0)  # K == V == latent

        def moe_step(h, lp):
            h, row = attn_sp(lp, h)
            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
            h = h + self._moe_mlp(lp, x)
            return h, jnp.stack([row, row], axis=0)

        h = hidden
        kvs = []
        if cfg.first_k_dense_replace:
            h, kv_d = jax.lax.scan(dense_step, h, params["dense_layers"])
            kvs.append(kv_d)
        h, kv_m = jax.lax.scan(moe_step, h, params["moe_layers"])
        kvs.append(kv_m)
        kv = jnp.concatenate(kvs, axis=0) if len(kvs) > 1 else kvs[0]
        hidden = rms_norm(h, params["final_norm"], cfg.rms_norm_eps)
        return hidden, kv  # kv: [L, 2, B, S, kv_lora+rope]

    def compute_logits(self, params, hidden):
        w = params["lm_head"]
        return jnp.matmul(hidden.astype(w.dtype), w,
                          preferred_element_type=jnp.float32)


# ------------------------------------------------------------- HF weights ----
def convert_hf_state_dict(sd: dict, cfg: DeepseekConfig) -> Params:
    """transformers DeepseekV2ForCausalLM state dict → DeepseekModel
    params (numpy in, jnp out).  Linear weights transpose to [in, out]."""
    import numpy as _np

    dt = cfg.jax_dtype

    def w(name):
        return _np.asarray(sd[name], dtype=_np.float32)

    def lin(name):
        return w(name).T  # torch [out, in] -> [in, out]

    def stack(fmt, layers, f):
        return jnp.asarray(_np.stack([f(fmt.format(i)) for i in layers]), dt)

    ld = cfg.first_k_dense_replace
    dense_idx = list(range(ld))
    moe_idx = list(range(ld, cfg.num_layers))

    def attn_group(idx):
        pre = "model.layers.{}."
        g = {
            "attn_norm": stack(pre + "input_layernorm.weight", idx, w),
            "mlp_norm": stack(pre + "post_attention_layernorm.weight", idx, w),
            "kv_a": stack(pre + "self_attn.kv_a_proj_with_mqa.weight", idx, lin),
            "kv_a_norm": stack(pre + "self_attn.kv_a_layernorm.weight", idx, w),
            "kv_b": stack(pre + "self_attn.kv_b_proj.weight", idx, lin),
            "wo": stack(pre + "self_attn.o_proj.weight", idx, lin),
        }
        if cfg.q_lora_rank is None:
            g["wq"] = stack(pre + "self_attn.q_proj.weight", idx, lin)
        else:
            g["q_a"] = stack(pre + "self_attn.q_a_proj.weight", idx, lin)
            g["q_a_norm"] = stack(pre + "self_attn.q_a_layernorm.weight", idx, w)
            g["q_b"] = stack(pre + "self_attn.q_b_proj.weight", idx, lin)
        return g

    dense_layers = attn_group(dense_idx)
    dense_layers.update(
        w_gate=stack("model.layers.{}.mlp.gate_proj.weight", dense_idx, lin),
        w_up=stack("model.layers.{}.mlp.up_proj.weight", dense_idx, lin),
        w_down=stack("model.layers.{}.mlp.down_proj.weight", dense_idx, lin),
    )

    def experts(kind):
        e = cfg.n_routed_experts

        def per_layer(i):
            return _np.stack([
                lin(f"model.layers.{i}.mlp.experts.{j}.{kind}.weight")
                for j in range(e)
            ])

        return jnp.asarray(_np.stack([per_layer(i) for i in moe_idx]), dt)

    moe_layers = attn_group(moe_idx)
    moe_layers.update(
        router=stack("model.layers.{}.mlp.gate.weight", moe_idx, lin),
        w_gate=experts("gate_proj"),
        w_up=experts("up_proj"),
        w_down=experts("down_proj"),
        shared_gate=stack(
            "model.layers.{}.mlp.shared_experts.gate_proj.weight", moe_idx, lin),
        shared_up=stack(
            "model.layers.{}.mlp.shared_experts.up_proj.weight", moe_idx, lin),
        shared_down=stack(
            "model.layers.{}.mlp.shared_experts.down_proj.weight", moe_idx, lin),
    )
    if cfg.topk_method == "noaux_tc":
        moe_layers["router_bias"] = jnp.asarray(_np.stack([
            w(f"model.layers.{i}.mlp.gate.e_score_correction_bias")
            for i in moe_idx]), jnp.float32)
    return {
        "embed": jnp.asarray(w("model.embed_tokens.weight"), dt),
        "dense_layers": dense_layers,
        "moe_layers": moe_layers,
        "final_norm": jnp.asarray(w("model.norm.weight"), dt),
        "lm_head": jnp.asarray(lin("lm_head.weight"), dt),
    }

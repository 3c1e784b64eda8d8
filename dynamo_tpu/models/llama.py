"""Llama-family decoder in pure JAX, built for paged serving on TPU.

Design (TPU-first, not a port):
  * One unified forward pass serves prefill, chunked prefill and decode —
    the S new tokens of each sequence scatter K/V into the paged cache then
    run paged attention over their full context (ops/paged_attention.py).
  * ``lax.scan`` over layers: per-layer weights are stacked on a leading L
    axis so the whole stack compiles once — fast XLA compiles even at 80
    layers.  A stack whose layers differ in kind (``ModelConfig.layer_types``:
    window and full attention, a rope each; docs/window_layers.md) keeps the
    one stacked pytree, because every layer's parameters have one shape, and
    scans PERIODS of the pattern, the period's layers unrolled in the body,
    each with its kind's static window, frequencies and score factor.  What
    the loop body reads decides how a stack reaches it:
      - norms, attention projections and the dense FFN ride the scan as
        ``xs``; their consumers are XLA dot fusions, which read the
        layer's slice in place (the chip's trace shows the FFN matrices
        at the bandwidth roofline; ``wq``/``wk`` are re-laid-out per
        head, a layout choice of the dot, ROADMAP S1b);
      - the KV cache is scan CARRY updated in place by scatter and indexed
        by the layer number (never sliced per layer), so decode traffic
        is O(tokens), not O(cache);
      - bf16 MoE expert stacks are CLOSED OVER and indexed by the layer
        number too: their consumer, ``lax.ragged_dot``, is a Mosaic
        custom call on the TPU, a custom call takes whole buffers, and a
        slice riding ``xs`` was materialised — three copies of E·Dm·F
        weights a layer, 62% of Qwen3-30B-A3B's device time on the v5e
        (PERF.md §6, PR 26).  int8 (QTensor) experts still ride ``xs``
        (``experts_in_place``).
  * Static shapes everywhere; bf16 weights/activations on the MXU, f32
    norms/softmax/logits.
  * A looped decoder (``ModelConfig.ut_steps`` > 1) runs the layer scan
    that many times over the same weights inside one more ``lax.scan``
    with ``(hidden, cache)`` as its carry; pass t reads and writes cache
    layer ``t * num_layers + l``, so the cache has ``cache_layers`` =
    ut_steps x num_layers layers under the one block table
    (docs/looped_layers.md).  With ``ut_steps`` 1 there is no outer loop.
  * Tensor parallelism is declarative: :meth:`partition_specs` returns a
    PartitionSpec pytree over mesh axes ("data", "model") and GSPMD inserts
    the collectives (all-gather/psum over ICI) — no NCCL-style plumbing.
  * MoE (Mixtral-style) uses grouped dispatch: token→expert assignments
    sort by expert and each projection runs as ONE ``lax.ragged_dot``
    (XLA's grouped matmul) — exactly k experts of FLOPs per token and
    [T·k, F] intermediates.  Experts shard their FFN dim over "model"
    (TP-within-experts), so compute/memory balance is routing-independent.
    A dense one-hot oracle path remains for parity tests (DYNAMO_MOE_DENSE).

The reference has no model code at all (engines are external, SURVEY.md
§2.4); this module plus engine/ is the "native JAX/XLA engine" the rebuild
adds (BASELINE.json north star).
"""

from __future__ import annotations

import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

# canonical axis names (utils/mesh.py): _TP is the tensor-parallel mesh
# axis every spec below shards over — shardcheck audits specs under the
# same constants, so a renamed axis breaks loudly instead of replicating
from dynamo_tpu.utils.mesh import AXIS_MODEL as _TP
from dynamo_tpu.utils.mesh import AXIS_SP

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.quant import (
    QTensor,
    dequantize,
    matmul,
    quantize_params,
    random_qtensor,
    stacked_channel_axes,
    take_rows,
)
from dynamo_tpu.ops.paged_attention import (
    MQ_MAX_S,
    paged_attention_layer,
    prefill_attention,
    ragged_prefill_attention,
    rows_by_length,
    softcap,
    tp_size,
    write_kv_cache_layer,
)

Params = Any  # pytree of jax.Array


def rms_norm(x: jax.Array, weight: jax.Array, eps: float,
             unit_offset: bool = False) -> jax.Array:
    xf = x.astype(jnp.float32)
    norm = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    w = weight.astype(jnp.float32)
    if unit_offset:  # Gemma stores zero-centred scales: multiply by (1 + w)
        w = w + 1.0
    return (norm * w).astype(x.dtype)


def rope_inv_freq(head_dim: int, theta: float,
                  rope_scaling: Optional[dict] = None) -> jax.Array:
    """Rotary inverse frequencies [D/2], with HF rope_scaling applied.

    llama3 scaling (Llama-3.1+): low-frequency components divide by
    ``factor``, high-frequency ones stay, the band between interpolates —
    matching transformers' _compute_llama3_parameters.  "linear" divides
    every frequency by ``factor``.
    """
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) * 2.0
                           / head_dim))
    if rope_scaling:
        kind = rope_scaling.get("rope_type") or rope_scaling.get("type")
        if kind == "linear":
            inv = inv / float(rope_scaling["factor"])
        elif kind == "llama3":
            factor = float(rope_scaling["factor"])
            low = float(rope_scaling.get("low_freq_factor", 1.0))
            high = float(rope_scaling.get("high_freq_factor", 4.0))
            old_ctx = float(
                rope_scaling.get("original_max_position_embeddings", 8192)
            )
            wavelen = 2.0 * np.pi / inv
            # long wavelengths (low freq): fully scaled; short: untouched;
            # medium: smooth interpolation — transformers parity
            scaled = inv / factor
            smooth = (old_ctx / wavelen - low) / (high - low)
            smooth = np.clip(smooth, 0.0, 1.0)
            interp = (1.0 - smooth) * scaled + smooth * inv
            inv = np.where(wavelen > old_ctx / low, scaled,
                           np.where(wavelen < old_ctx / high, inv, interp))
    return jnp.asarray(inv, jnp.float32)


def yarn_inv_freq(head_dim: int, theta: float, factor: float,
                  original_max: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> jax.Array:
    """YaRN's inverse frequencies [D/2] (transformers'
    ``_compute_yarn_parameters``, ``truncate`` true).  Pair j turns
    ``original_max``·f_j / 2π times over the trained context: pairs that
    turn more than ``beta_fast`` times keep f_j, pairs that turn less than
    ``beta_slow`` times are divided by ``factor``, and the band between is
    a ramp over the pair index, from floor(d(beta_fast)) to
    ceil(d(beta_slow)) with d(r) = D·ln(original_max / 2πr) / (2 ln θ).
    The latent-attention family's readers call it (models/glm_dsa.py), and
    ``kind_rope`` below for a layer kind whose ``rope_parameters`` say
    ``yarn``; as a uniform ``rope_scaling`` ``ModelConfig`` still refuses
    YaRN for the Llama family."""
    half = head_dim // 2
    inv = 1.0 / (theta ** (np.arange(0, half, dtype=np.float64) * 2.0
                           / head_dim))

    def pair_of(turns: float) -> float:
        return (head_dim * math.log(original_max / (turns * 2.0 * math.pi))
                / (2.0 * math.log(theta)))

    lo = max(math.floor(pair_of(beta_fast)), 0)
    hi = min(math.ceil(pair_of(beta_slow)), head_dim - 1)
    ramp = np.clip((np.arange(half, dtype=np.float64) - lo)
                   / max(hi - lo, 0.001), 0.0, 1.0)
    return jnp.asarray(inv * (1.0 - ramp) + inv / factor * ramp, jnp.float32)


def yarn_mscale(factor: float, mscale: float) -> float:
    """m(s) = 0.1·s·ln(factor) + 1: YaRN's attention temperature."""
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def kind_rope(head_dim: int, rope: dict) -> tuple[jax.Array, float]:
    """(inverse frequencies [D/2], the factor on cos and sin) of one layer
    kind's rope, from its entry of HF ``rope_parameters``.  YaRN blends the
    frequencies at every position, not only past the trained context, and
    multiplies cos and sin by ``attention_factor`` (the config's, else
    0.1·ln(factor) + 1), so the kind's scores carry its square."""
    theta = float(rope["rope_theta"])
    if rope.get("rope_type", "default") != "yarn":
        return rope_inv_freq(head_dim, theta, rope), 1.0
    factor = float(rope["factor"])
    inv = yarn_inv_freq(
        head_dim, theta, factor, int(rope["original_max_position_embeddings"]),
        float(rope.get("beta_fast", 32.0)), float(rope.get("beta_slow", 1.0)))
    return inv, float(rope.get("attention_factor")
                      or yarn_mscale(factor, 1.0))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float,
               inv_freq: Optional[jax.Array] = None,
               rotary_dim: Optional[int] = None,
               factor: float = 1.0) -> jax.Array:
    """HF-Llama rotate-half RoPE.  x: [B,S,H,D], positions: [B,S].  With
    ``rotary_dim`` < D (``partial_rotary_factor``) the first ``rotary_dim``
    dimensions of every head are rotated, half against half within them, and
    the others pass as they are.  ``factor`` multiplies cos and sin (YaRN's
    attention factor, the HF way)."""
    if rotary_dim is not None and rotary_dim != x.shape[-1]:
        turned = apply_rope(x[..., :rotary_dim], positions, theta, inv_freq)
        return jnp.concatenate([turned, x[..., rotary_dim:]], axis=-1)
    d = x.shape[-1]
    half = d // 2
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, half, dtype=jnp.float32) * 2.0 / d))
    angles = positions.astype(jnp.float32)[:, :, None] * inv_freq[None, None, :]
    cos = jnp.cos(angles)[:, :, None, :]  # [B,S,1,half]
    sin = jnp.sin(angles)[:, :, None, :]
    if factor != 1.0:
        cos, sin = cos * factor, sin * factor
    x1, x2 = x[..., :half].astype(jnp.float32), x[..., half:].astype(jnp.float32)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


class LlamaModel:
    """Functional model: params pytree + pure forward functions."""

    # forward() accepts the token-budget ragged prefill layout (the engine
    # gates the batched scheduler on this; models without the ragged
    # attention path — expanded-MLA DeepSeek — fall back to per-request)
    supports_ragged_prefill = True
    # forward() additionally accepts the unified mixed layout (decode
    # rows leading the flat axis via ``ragged_row_tokens``) — the engine
    # gates the unified token-budget scheduler on this
    supports_unified_dispatch = True
    # forward() hands a prefill's ``prefix_blocks`` to the attention call
    # and reads it nowhere else, so whether the value keys a program is that
    # call's dispatch rule to say (EngineCore._prefix_blocks)
    prefix_blocks_sizes_forward = False

    def __init__(self, config: ModelConfig):
        if config.ut_steps < 1:
            raise ValueError(f"ut_steps must be >= 1, got {config.ut_steps}")
        self.config = config
        # Gemma2 scales scores by query_pre_attn_scalar**-0.5, not head_dim
        self.sm_scale = float(
            (config.query_pre_attn_scalar or config.head_dim) ** -0.5
        )
        # rotary frequencies with rope_scaling applied (llama3/linear)
        self.inv_freq = rope_inv_freq(
            config.head_dim, config.rope_theta, config.rope_scaling
        )
        # a stack of more than one kind of layer: each kind's rope, and the
        # kinds of one period (forward scans periods)
        self.period = config.period
        self.kind_ropes = {
            kind: kind_rope(config.head_dim, config.rope_parameters[kind])
            for kind in set(self.period or ())}

    @property
    def cache_layers(self) -> int:
        """Layers of K/V cache: one per layer *application* that attends.
        A looped decoder keeps a cache of its own for every pass, so this
        is ``ut_steps`` x the layers of weights."""
        return self.config.num_layers * self.config.ut_steps

    @property
    def supports_seq_parallel(self) -> bool:
        """``forward_seq_parallel`` walks a stack of one kind once: the
        engine refuses ``sp_prefill_threshold`` at start-up for a looped
        decoder and for a stack with ``layer_types`` (ring attention under
        a window or a rope per layer kind is not written)."""
        return self.config.ut_steps == 1 and self.period is None

    # ------------------------------------------------------------------ init
    def init_params(self, rng: jax.Array, quantized: bool = False) -> Params:
        """Random init as ONE compiled program.

        The eager body dispatches ~5 ops per tensor, each its own small
        compile on first use; jitted, init is one program.
        """
        fn = getattr(self, "_init_params_jit", None)
        if fn is None:
            fn = self._init_params_jit = jax.jit(
                self._init_params_impl, static_argnames=("quantized",))
        return fn(rng, quantized=quantized)

    def _init_params_impl(self, rng: jax.Array, quantized: bool = False) -> Params:
        """``quantized=True`` synthesizes int8 QTensor matmul
        weights directly (never materializing the bf16 tensor — 8B bf16
        would not fit the single chip the int8 path exists to fit)."""
        cfg = self.config
        dt = cfg.jax_dtype
        dm, hq, hk, dh, f = (
            cfg.hidden_size,
            cfg.num_heads,
            cfg.num_kv_heads,
            cfg.head_dim,
            cfg.intermediate_size,
        )
        L = cfg.num_layers
        keys = iter(jax.random.split(rng, 16))

        def dense(key, shape, fan_in, channel_axes=None):
            if quantized:
                axes = channel_axes or stacked_channel_axes(len(shape))
                return random_qtensor(key, shape, fan_in, axes)
            return (jax.random.normal(key, shape, jnp.float32) / math.sqrt(fan_in)).astype(dt)

        # Gemma's (1 + w) RMSNorm wants zero-init scales; Llama wants ones
        norm_init = jnp.zeros if cfg.rmsnorm_unit_offset else jnp.ones
        layers: dict[str, jax.Array] = {
            "attn_norm": norm_init((L, dm), dt),
            "wq": dense(next(keys), (L, dm, hq * dh), dm),
            "wk": dense(next(keys), (L, dm, hk * dh), dm),
            "wv": dense(next(keys), (L, dm, hk * dh), dm),
            "wo": dense(next(keys), (L, hq * dh, dm), hq * dh),
            "mlp_norm": norm_init((L, dm), dt),
        }
        if cfg.post_norms:  # Gemma2 / Ouro sandwich norms
            post = norm_init((L, dm), dt)
            if cfg.ut_steps > 1:
                # A looped stack re-enters itself from a state of unit RMS
                # (the final norm closes every pass).  With the sandwich
                # norms seeded at 1 every branch adds a unit-RMS vector to
                # it, and the random model amplifies a rounding error ~2.7x
                # a pass: bf16 against float32 read a median |d log p| of
                # 0.014 after one pass and 0.23 after four (0.20-0.22 on
                # the chip at Ouro-2.6B's widths, PERF.md section 6), which
                # no trained looped model does.  Seeded at 1/sqrt(2L), the
                # 2L branches of a pass add the state's own variance once
                # (GPT-2's scaling of its residual projections): 0.022
                # after four.
                post = post / math.sqrt(2 * L)
            layers.update(post_attn_norm=post, post_mlp_norm=post)
        if cfg.attention_bias:  # Qwen2-style QKV bias
            layers.update(
                bq=jnp.zeros((L, hq * dh), dt),
                bk=jnp.zeros((L, hk * dh), dt),
                bv=jnp.zeros((L, hk * dh), dt),
            )
        if cfg.qk_norm:  # Qwen3 per-head q/k RMSNorm
            layers.update(
                q_norm=jnp.ones((L, dh), dt),
                k_norm=jnp.ones((L, dh), dt),
            )
        if cfg.is_moe:
            e = cfg.num_experts
            # router stays dense even under quantization: it is tiny and
            # its logits pick experts (accuracy-critical, no bandwidth win)
            router_w = (
                jax.random.normal(next(keys), (L, dm, e), jnp.float32)
                / math.sqrt(dm)
            ).astype(dt)
            layers.update(
                router=router_w,
                w_gate=dense(next(keys), (L, e, dm, f), dm),
                w_up=dense(next(keys), (L, e, dm, f), dm),
                w_down=dense(next(keys), (L, e, f, dm), f),
            )
        else:
            layers.update(
                w_gate=dense(next(keys), (L, dm, f), dm),
                w_up=dense(next(keys), (L, dm, f), dm),
                w_down=dense(next(keys), (L, f, dm), f),
            )
        params = {
            # per-row scales so the same tensor serves lookup + tied lm_head
            "embed": dense(next(keys), (cfg.vocab_size, dm), dm, channel_axes=(0,)),
            "layers": layers,
            "final_norm": norm_init((dm,), dt),
        }
        if not cfg.tie_word_embeddings:
            params["lm_head"] = dense(next(keys), (dm, cfg.vocab_size), dm)
        if cfg.ut_steps > 1:
            # the exit gate, Linear(Dm -> 1): tiny, and its logit picks a
            # pass, so it stays dense under quantization like the router
            params["exit_gate_w"] = (
                jax.random.normal(next(keys), (dm,), jnp.float32)
                / math.sqrt(dm)).astype(dt)
            params["exit_gate_b"] = jnp.zeros((), dt)
        return params

    def quantize_params(self, params: Params) -> Params:
        """bf16 params → int8 weight-only QTensor params (models/quant.py)."""
        return quantize_params(params)

    # -------------------------------------------------------------- sharding
    def partition_specs(self) -> Params:
        """PartitionSpec pytree matching init_params — TP over axis "model".

        GSPMD turns these annotations into ICI collectives; this is the whole
        tensor-parallel implementation (cf. reference delegating TP to
        vLLM/Ray, SURVEY.md §2.4 parallelism summary).
        """
        cfg = self.config
        layers = {
            "attn_norm": P(None, None),
            "wq": P(None, None, _TP),
            "wk": P(None, None, _TP),
            "wv": P(None, None, _TP),
            "wo": P(None, _TP, None),
            "mlp_norm": P(None, None),
        }
        if cfg.attention_bias:
            layers.update(
                bq=P(None, _TP), bk=P(None, _TP), bv=P(None, _TP)
            )
        if cfg.qk_norm:
            layers.update(q_norm=P(None, None), k_norm=P(None, None))
        if cfg.post_norms:
            layers.update(
                post_attn_norm=P(None, None), post_mlp_norm=P(None, None)
            )
        if cfg.is_moe:
            # TP-within-experts: shard every expert's FFN intermediate dim
            # F over "model" (same layout as the dense MLP).  Weight memory
            # AND compute split evenly across devices regardless of routing
            # skew, and GSPMD partitions the grouped ragged_dot directly on
            # F.  (Device-EP — sharding the E axis — load-balances only
            # when routing is uniform; at serving batch sizes it idles
            # devices whose experts draw no tokens.)
            layers.update(
                router=P(None, None, None),
                w_gate=P(None, None, None, _TP),
                w_up=P(None, None, None, _TP),
                w_down=P(None, None, _TP, None),
            )
        else:
            layers.update(
                w_gate=P(None, None, _TP),
                w_up=P(None, None, _TP),
                w_down=P(None, _TP, None),
            )
        specs = {
            "embed": P(None, None),
            "layers": layers,
            "final_norm": P(None),
        }
        if not cfg.tie_word_embeddings:
            specs["lm_head"] = P(None, _TP)
        if cfg.ut_steps > 1:
            specs.update(exit_gate_w=P(None), exit_gate_b=P())
        return specs

    def cache_spec(self, quant: bool = False):
        """KV cache [cache_layers,N,2,Bs,Hk*D]: the trailing axis is kv-head-major, so
        sharding it over "model" splits whole kv heads across the mesh.
        For a quantized cache, the scale pool [L,N,2,Hp,Sp] shards its
        head axis the same way — but only when Hk is tile-exact (Hk % 8 ==
        0, so Hp == Hk and shard boundaries land on real head rows); a
        padded head axis replicates instead, since an even split of the
        padded axis would put different heads on a shard than the data's
        head-major lane split does."""
        data = P(None, None, None, None, _TP)
        if not quant:
            return data
        from dynamo_tpu.ops.kv_quant import QuantKvCache

        head_axis = _TP if self.config.num_kv_heads % 8 == 0 else None
        return QuantKvCache(data, P(None, None, None, head_axis, None))

    # --------------------------------------------------------------- kv cache
    def init_kv_cache(self, num_blocks: int, block_size: int, dtype=None) -> jax.Array:
        """One array for the whole model: [cache_layers, N, 2, Bs, Hk*D]
        (``cache_layers`` = L, or ut_steps x L for a looped decoder: pass t
        of layer l is cache layer t*L + l, and one block id is one block
        in every one of them).

        A single multi-layer array (rather than per-layer leaves) is what
        lets (a) the decode kernel index layers with a scalar instead of
        slicing, (b) block transfer move a block id across all layers at
        once (ops/block_copy.py), and (c) the engine donate one buffer.
        K and V of a block are adjacent (k/v axis inside the block axis) so
        the decode kernel's per-block fetch is ONE contiguous DMA.  The
        flat Hk*D minor axis is lane-aligned (512+ for real models).

        ``dtype="int8"`` returns a :class:`QuantKvCache` (int8 payload +
        per-token-per-head scale pool, ops/kv_quant.py) — same layout, half
        the HBM, transparently handled by every write/attention path.
        """
        cfg = self.config
        shape = (
            self.cache_layers,
            num_blocks,
            2,
            block_size,
            cfg.num_kv_heads * cfg.head_dim,
        )
        dt = dtype or cfg.jax_dtype
        if str(dt) in ("int8", "<dtype: int8>") or dt == jnp.int8:
            from dynamo_tpu.ops.kv_quant import QuantKvCache, scale_tile

            hp, sp = scale_tile(cfg.num_kv_heads, block_size)
            return QuantKvCache(
                jnp.zeros(shape, jnp.int8),
                jnp.ones(
                    (self.cache_layers, num_blocks, 2, hp, sp), jnp.float32,
                ),
            )
        return jnp.zeros(shape, dt)

    # ---------------------------------------------------------------- forward
    def forward(
        self,
        params: Params,
        tokens: jax.Array,        # [B, S] int32
        positions: jax.Array,     # [B, S] int32 (absolute; padding rows may be 0)
        kv_cache: jax.Array,      # [L, N, 2, Bs, Hk*D]
        block_tables: jax.Array,  # [B, M] int32
        seq_lens: jax.Array,      # [B] int32 — context length incl. new tokens
        slot_idx: jax.Array,      # [B, S] int32 — cache slot per new token, -1 pad
        prefix_blocks: int | None = None,  # STATIC — prefill fast path (see below)
        ragged: tuple | None = None,       # (seq_ids, starts, row_offsets)
        ragged_row_tokens: int = 0,        # STATIC — unified mixed layout
    ) -> tuple[jax.Array, jax.Array]:
        """Returns (hidden [B,S,Dm], updated kv_cache).

        ``prefix_blocks`` (static int) activates the prefill fast path for
        S>1: attention runs against this chunk's in-register K/V plus at
        most ``prefix_blocks`` cached prefix blocks, instead of gathering
        the whole padded block table.  Requires the S tokens of each row to
        be contiguous from block-aligned position ``positions[:, 0]``
        (exactly how the engine lays out prefill).  None = generic path.

        ``ragged`` switches the prefill fast path to token-budget ragged
        form: B is 1 and the S axis packs several sequences' chunks, each a
        contiguous block-aligned span.  ``seq_ids`` [1, S] names each
        token's owning row (-1 = padding), ``starts``/``row_offsets`` [R]
        give each row's absolute chunk start and flat offset, and
        ``block_tables``/``seq_lens`` are per-ROW ([R, M] / [R]) rather
        than per-batch-row.  Requires ``prefix_blocks`` to be set.

        ``ragged_row_tokens`` (static) marks the unified mixed layout:
        the first that-many flat tokens are DECODE rows — one fresh token
        each, at an arbitrary (non-block-aligned) in-block cache slot —
        so the KV write scatters them per row and only the block-aligned
        prefill spans after them take the block-granular write.  The
        ragged attention itself needs no change: its prefix mask is
        positionally exact for any ``starts``.
        """
        cfg = self.config
        b, s = tokens.shape
        dh, hq, hk = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
        ragged_prefill = (
            ragged is not None and prefix_blocks is not None and s > 1
        )
        fast_prefill = (
            prefix_blocks is not None and s > 1 and not ragged_prefill
        )

        # A decode-shaped step (one token a row, or the few of a speculative
        # verify) runs with its rows grouped by context length: every row is
        # computed on its own, so the order moves no result, and the decode
        # kernel, which walks G consecutive rows to the longest of them,
        # then loops near every row's own length and not at all over a
        # group of empty slots.  Ordered here, once, on the step's integer
        # operands; the hidden state goes back to slot order at the end.
        slot_order = lambda h: h
        if not (ragged_prefill or fast_prefill) and s <= MQ_MAX_S:
            order, inverse = rows_by_length(seq_lens)
            tokens, positions, block_tables, seq_lens, slot_idx = (
                a[order] for a in (tokens, positions, block_tables, seq_lens,
                                   slot_idx))
            slot_order = lambda h: h[inverse]

        # Named scopes (embed, attn_proj, attn, attn_out, mlp with moe_router
        # / moe_experts, logits, sample) put a device operation's place in
        # the model into its profiler metadata; cellbench's device.*_pct
        # read them.  The layer scan carries none (nor does the loop over
        # passes), so what XLA adds around them (per-layer weight slices,
        # layout copies) stays unscoped.  The norm that closes a pass, the
        # exit gate and its selection are the head's (``logits``).
        with jax.named_scope("embed"):
            hidden = take_rows(params["embed"], tokens, cfg.jax_dtype)
            if cfg.scale_embeddings:  # Gemma multiplies by sqrt(hidden_size)
                hidden = hidden * jnp.asarray(
                    math.sqrt(cfg.hidden_size), cfg.jax_dtype
                )

        # The cache rides the scan as CARRY, updated by scatter: XLA keeps
        # one buffer and updates it in place.  (Passing it as xs/ys instead
        # copies the whole multi-GB cache through the loop every step —
        # that copy, not attention, dominated decode ITL.)
        uo = cfg.rmsnorm_unit_offset

        # bf16 expert stacks stay out of the pytree the scan slices:
        # layer_step closes over them and ragged_dot reads layer li's
        # experts where they lie (grouped_expert_dispatch)
        layers, experts = params["layers"], None
        if cfg.is_moe and experts_in_place(layers, tp_size()):
            experts = {k: layers[k] for k in _EXPERT_KEYS}
            layers = {k: w for k, w in layers.items() if k not in experts}

        def attend(q, k, v, cache, ci, window):
            if ragged_prefill:
                seq_ids, seq_starts, row_offsets = ragged
                return ragged_prefill_attention(
                    q, k, v, cache, ci, block_tables, seq_lens,
                    seq_starts, row_offsets, seq_ids, prefix_blocks,
                    sm_scale=self.sm_scale,
                    logit_cap=cfg.attn_logit_softcap,
                    window=window,
                )
            if fast_prefill:
                return prefill_attention(
                    q, k, v, cache, ci, block_tables, seq_lens,
                    positions[:, 0], prefix_blocks,
                    sm_scale=self.sm_scale,
                    logit_cap=cfg.attn_logit_softcap,
                    window=window,
                )
            return paged_attention_layer(
                q, cache, ci, block_tables, seq_lens, positions,
                sm_scale=self.sm_scale,
                logit_cap=cfg.attn_logit_softcap,
                window=window,
            )

        def layer_step(carry, layer_in, first=None, kind=None):
            """``first``: the cache layer of this pass's layer 0 (None: 0,
            the only pass).  ``kind`` (static): the layer's entry of
            ``layer_types`` - its rope, whether the window is its, and the
            scope its attention call shows under (``window`` / ``full``,
            inside ``attn``); None in a stack of one kind."""
            h, cache = carry
            lp, li = layer_in   # this layer's weights, its index in L
            ci = li if first is None else first + li   # its cache layer
            window, rope = cfg.sliding_window, {"inv_freq": self.inv_freq}
            if kind is not None:
                inv_freq, factor = self.kind_ropes[kind]
                rope = {"inv_freq": inv_freq, "factor": factor}
                if kind == "full_attention":
                    window = None
            with jax.named_scope("attn_proj"):
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, uo)
                q, k, v = _qkv_proj(cfg, lp, x, b, s)
                q = apply_rope(q, positions, cfg.rope_theta, **rope)
                k = apply_rope(k, positions, cfg.rope_theta, **rope)
            with jax.named_scope("attn"):
                # fast_prefill/ragged imply the engine's block-aligned
                # contiguous span layout — unlocks the block-granular write
                cache = write_kv_cache_layer(
                    cache, ci, k, v, slot_idx,
                    block_aligned=fast_prefill or ragged_prefill,
                    row_tokens=ragged_row_tokens if ragged_prefill else 0,
                )
                if kind is None:
                    attn = attend(q, k, v, cache, ci, window)
                else:
                    with jax.named_scope(
                            "full" if window is None else "window"):
                        attn = attend(q, k, v, cache, ci, window)
            with jax.named_scope("attn_out"):
                attn_out = matmul(attn.reshape(b, s, hq * dh), lp["wo"])
                if cfg.post_norms:  # sandwich: norm the residual branch
                    attn_out = rms_norm(attn_out, lp["post_attn_norm"],
                                        cfg.rms_norm_eps, uo)
                h = h + attn_out

            with jax.named_scope("mlp"):
                x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps, uo)
                if experts:   # the whole stacks; li picks the layer
                    mlp_out = _moe_mlp(cfg, {**lp, **experts}, x, layer=li)
                else:
                    mlp_out = (_moe_mlp(cfg, lp, x) if cfg.is_moe
                               else _dense_mlp(cfg, lp, x))
                if cfg.post_norms:
                    mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"],
                                       cfg.rms_norm_eps, uo)
                h = h + mlp_out
            return (h, cache), None

        def period_step(carry, period_in, first=None):
            """One period of ``layer_types``, its layers unrolled: each
            with its kind's static window, frequencies and factor.  The
            scanned weights arrive as [P, ...]; the closed-over expert
            stacks are read at layer period x P + j."""
            lps, pi = period_in
            for j, kind in enumerate(self.period):
                lp = jax.tree.map(lambda w: w[j], lps)
                carry, _ = layer_step(
                    carry, (lp, pi * len(self.period) + j), first, kind)
            return carry, None

        def run_pass(hidden, cache, first=None):
            """The layer scan once, then the final norm."""
            if self.period is None:
                (hidden, cache), _ = jax.lax.scan(
                    lambda carry, layer_in: layer_step(carry, layer_in, first),
                    (hidden, cache),
                    (layers, jnp.arange(cfg.num_layers, dtype=jnp.int32)),
                )
            else:
                p = len(self.period)
                (hidden, cache), _ = jax.lax.scan(
                    lambda carry, period_in: period_step(
                        carry, period_in, first),
                    (hidden, cache),
                    (jax.tree.map(
                        lambda w: w.reshape(-1, p, *w.shape[1:]), layers),
                     jnp.arange(cfg.num_layers // p, dtype=jnp.int32)),
                )
            with jax.named_scope("logits"):
                hidden = rms_norm(hidden, params["final_norm"],
                                  cfg.rms_norm_eps, cfg.rmsnorm_unit_offset)
            return hidden, cache

        if cfg.ut_steps == 1:
            hidden, new_cache = run_pass(hidden, kv_cache)
            return slot_order(hidden), new_cache

        # Looped decoder: the same scan ``ut_steps`` times, the weights read
        # where they lie in every pass, (hidden, cache) carried through.  The
        # final norm closes every pass and feeds the next; the exit gate
        # reads it.  Every pass always runs (a later token attends to every
        # pass's K/V): the gate only chooses which pass's state goes to the
        # head — the first at which the cumulated exit probability reaches
        # the threshold, else the last.
        last = cfg.ut_steps - 1
        threshold = jnp.float32(cfg.early_exit_threshold)

        def pass_step(carry, t):
            h, cache, out, alive, cum = carry
            h, cache = run_pass(h, cache, t * cfg.num_layers)
            with jax.named_scope("logits"):
                gate = jnp.einsum(
                    "bsd,d->bs", h.astype(jnp.float32),
                    params["exit_gate_w"].astype(jnp.float32),
                ) + params["exit_gate_b"].astype(jnp.float32)
                lam = jax.nn.sigmoid(gate)
                # p_t = lam_t * prod_{j<t}(1 - lam_j); the last pass takes
                # what is left
                reached = cum + jnp.where(t == last, alive, lam * alive)
                take = (cum < threshold) & ((reached >= threshold) | (t == last))
                out = jnp.where(take[..., None], h, out)
            return (h, cache, out, alive * (1.0 - lam), reached), None

        zeros = jnp.zeros((b, s), jnp.float32)
        (_, new_cache, hidden, _, _), _ = jax.lax.scan(
            pass_step,
            (hidden, kv_cache, jnp.zeros_like(hidden), zeros + 1.0, zeros),
            jnp.arange(cfg.ut_steps, dtype=jnp.int32),
        )
        return slot_order(hidden), new_cache

    def forward_seq_parallel(
        self,
        params: Params,
        tokens: jax.Array,      # [B, S] int32, S sharded over mesh[sp_axis]
        positions: jax.Array,   # [B, S] int32 global positions
        mesh: jax.sharding.Mesh,
        sp_axis: str = AXIS_SP,
    ) -> tuple[jax.Array, jax.Array]:
        """Long-context prefill with ring attention (context parallelism).

        The sequence axis is sharded over ``mesh[sp_axis]``; each device
        computes its chunk's Q/K/V and attention runs blockwise while KV
        chunks rotate over ICI (ops/ring_attention.py) — prompts far beyond
        one chip's HBM prefill exactly, a capability absent from the
        reference (SURVEY.md §5 long-context).

        Returns (hidden [B,S,Dm], kv [L,2,B,S,Hk*D]); the kv output is what
        the engine scatters into paged-cache blocks after a long prefill,
        and both keep the sequence sharding.
        """
        from dynamo_tpu.ops.ring_attention import ring_attention

        cfg = self.config
        if not self.supports_seq_parallel:
            raise NotImplementedError(
                "seq-parallel prefill walks a stack of one kind of layer "
                f"once; a looped decoder (ut_steps={cfg.ut_steps}) or a "
                f"stack with layer_types (period {self.period}) is served "
                "by forward() only: disable sp_prefill_threshold")
        b, s = tokens.shape
        dh, hq, hk = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads

        hidden = take_rows(params["embed"], tokens, cfg.jax_dtype)
        if cfg.scale_embeddings:
            hidden = hidden * jnp.asarray(
                math.sqrt(cfg.hidden_size), cfg.jax_dtype
            )
        uo = cfg.rmsnorm_unit_offset

        def layer_step(h, lp):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps, uo)
            q, k, v = _qkv_proj(cfg, lp, x, b, s)
            q = apply_rope(q, positions, cfg.rope_theta, self.inv_freq)
            k = apply_rope(k, positions, cfg.rope_theta, self.inv_freq)
            attn = ring_attention(
                q, k, v, positions, positions, mesh=mesh, axis=sp_axis,
                sm_scale=self.sm_scale, logit_cap=cfg.attn_logit_softcap,
                window=cfg.sliding_window,
            )
            attn_out = matmul(attn.reshape(b, s, hq * dh), lp["wo"])
            if cfg.post_norms:
                attn_out = rms_norm(attn_out, lp["post_attn_norm"],
                                    cfg.rms_norm_eps, uo)
            h = h + attn_out

            x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps, uo)
            mlp_out = _moe_mlp(cfg, lp, x) if cfg.is_moe else _dense_mlp(cfg, lp, x)
            if cfg.post_norms:
                mlp_out = rms_norm(mlp_out, lp["post_mlp_norm"],
                                   cfg.rms_norm_eps, uo)
            h = h + mlp_out
            kv = jnp.stack(
                [k.reshape(b, s, hk * dh), v.reshape(b, s, hk * dh)], axis=0
            )
            return h, kv

        hidden, kv = jax.lax.scan(layer_step, hidden, params["layers"])
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps,
                          cfg.rmsnorm_unit_offset)
        return hidden, kv  # kv: [L, 2, B, S, Hk*D]

    @jax.named_scope("logits")
    def compute_logits(self, params: Params, hidden: jax.Array) -> jax.Array:
        """hidden [..., Dm] -> logits [..., V] in f32.

        The matmul runs in the weights' dtype with f32 accumulation — an
        explicit f32 cast of the vocab matrix would materialise a copy of
        the largest tensor in the model every step."""
        if self.config.tie_word_embeddings:
            w = params["embed"]
            # embed's per-row scale transposes into lm_head's per-column
            w = QTensor(w.q.T, w.scale.T) if isinstance(w, QTensor) else w.T
        else:
            w = params["lm_head"]
        if isinstance(w, QTensor):
            logits = matmul(hidden, w, preferred_element_type=jnp.float32)
        else:
            logits = jnp.matmul(
                hidden.astype(w.dtype), w, preferred_element_type=jnp.float32
            )
        cap = self.config.final_logit_softcap
        if cap:  # Gemma2 final logit softcap
            logits = softcap(logits, float(cap))
        return logits


def split_heads(y: jax.Array, heads: int) -> jax.Array:
    """[..., H·D] -> [..., H, D] of a projection's result, with the dot
    that made it left a plain 2-D one.

    Given the chance, XLA folds this reshape into the dot, and a dot with
    two free dimensions on the weight side wants its weight as [H, D, Dm]:
    on the TPU the layer scan then transposed all of ``wq`` and ``wk``
    (GLM: ``q_b``, 67 MB) every layer of every step, prefill and decode.
    Behind the barrier the reshape, the q/k norm and RoPE see only the
    (small) result, and the dot reads the stacked weight as it is stored."""
    y = jax.lax.optimization_barrier(y)
    return y.reshape(*y.shape[:-1], heads, y.shape[-1] // heads)


def _qkv_proj(
    cfg: ModelConfig, lp: dict, x: jax.Array, b: int, s: int
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """QKV projections (+ Qwen2 bias / Qwen3 per-head q-k norms)."""
    dh, hq, hk = cfg.head_dim, cfg.num_heads, cfg.num_kv_heads
    q, k, v = matmul(x, lp["wq"]), matmul(x, lp["wk"]), matmul(x, lp["wv"])
    if cfg.attention_bias:
        q, k, v = q + lp["bq"], k + lp["bk"], v + lp["bv"]
    q, k = split_heads(q, hq), split_heads(k, hk)
    if cfg.qk_norm:  # Qwen3: RMSNorm over head_dim, pre-RoPE
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v.reshape(b, s, hk, dh)


def _act(cfg: ModelConfig, gate: jax.Array) -> jax.Array:
    """Gate activation shared by every MLP path: SiLU (Llama) or
    tanh-GELU (Gemma GeGLU)."""
    return (jax.nn.gelu(gate, approximate=True)
            if cfg.hidden_activation == "gelu_tanh" else jax.nn.silu(gate))


def _dense_mlp(cfg: ModelConfig, lp: dict, x: jax.Array) -> jax.Array:
    """Gated MLP: act(x·Wg) * (x·Wu) · Wd."""
    return matmul(
        _act(cfg, matmul(x, lp["w_gate"])) * matmul(x, lp["w_up"]),
        lp["w_down"],
    )


def _moe_router(cfg: ModelConfig, lp: dict, xf: jax.Array):
    """Shared routing for both dispatch paths: top-k expert ids + weights.
    xf: [T, Dm] → (weights [T,k] f32, topi [T,k] int32)."""
    router_logits = (xf @ lp["router"]).astype(jnp.float32)  # [T,E]
    topv, topi = jax.lax.top_k(router_logits, cfg.num_experts_per_tok)
    if cfg.norm_topk_prob:
        # renormalized top-k == softmax over the top-k logits
        weights = jax.nn.softmax(topv, axis=-1)
    else:
        # Qwen3-MoE norm_topk_prob=False: full-softmax probs of the top-k
        probs_all = jax.nn.softmax(router_logits, axis=-1)
        weights = jnp.take_along_axis(probs_all, topi, axis=-1)
    return weights, topi


_EXPERT_KEYS = ("w_gate", "w_up", "w_down")
_LANES = 128  # minor-dimension tile of a TPU array


def experts_in_place(layers: dict, tp: int) -> bool:
    """Whether ``forward`` closes over the stacked expert arrays of
    ``layers`` (the layer-indexed ``grouped_expert_dispatch``) or lets
    the scan slice them — a static function of the leaves' type and the
    mesh, made before tracing, like ``attention_impl``'s.

    QTensor leaves are sliced: dequantising a closed-over stack would
    materialise every layer.  Under ``tp`` > 1 a device holds F/tp of
    each expert; where that is not a multiple of the 128 lanes (Qwen3's
    768 over tp=4) the TPU keeps ``w_gate``/``w_up`` with Dm minor, and
    the custom call's row-major operand then costs a re-layout of the
    WHOLE stack every step (compiled for a described v5e: temp 538 MB
    at depth 2, growing with L) where the sliced form re-lays one layer
    at a time."""
    if any(isinstance(layers[k], QTensor) for k in _EXPERT_KEYS):
        return False
    ffn = layers["w_gate"].shape[-1]
    return tp == 1 or ffn % tp == 0 and (ffn // tp) % _LANES == 0


def _moe_mlp(cfg: ModelConfig, lp: dict, x: jax.Array,
             layer=None) -> jax.Array:
    """``lp`` holds this layer's expert arrays — or, with ``layer``, the
    stacked [L, E, ...] ones, of which ``layer`` is this one's index."""
    import os

    if os.environ.get("DYNAMO_MOE_DENSE"):
        if layer is not None:
            lp = {**lp, **{k: lp[k][layer] for k in _EXPERT_KEYS}}
        return _moe_mlp_dense(cfg, lp, x)
    return _moe_mlp_grouped(cfg, lp, x, layer)


def grouped_expert_dispatch(xf, weights, topi, num_experts,
                            w_gate, w_up, w_down, act, layer=None,
                            held=None):
    """The grouped-MoE core, shared across model families (Llama-family
    MoE here, DeepSeekMoE in models/deepseek.py): sort token→expert
    assignments by expert, run each projection as ONE ``lax.ragged_dot``
    (XLA's grouped matmul), then weighted unsort-sum back per token.
    ``xf`` [T,Dm]; ``weights``/``topi`` [T,k]; ``w_*`` dense [E,Dm,F] /
    [E,F,Dm]; ``act`` maps the gate activation.

    With ``layer`` (a traced int32 scalar) the ``w_*`` are the whole
    stacked [L,E,Dm,F] / [L,E,F,Dm] arrays, viewed as L·E groups of which
    only ``[layer·E, (layer+1)·E)`` have rows.  On the TPU ``ragged_dot``
    is a Mosaic custom call that takes whole buffers, so a layer sliced
    out of the stack first is a copy of E·Dm·F weights per projection;
    this form reads them where they lie, and the kernel's grid visits
    only (group, row-tile) pairs that have rows.

    With ``held`` = (first, count) the ``w_*`` hold only experts
    ``first .. first+count-1`` of the ``num_experts`` the router chose
    among (one chip's share of an expert-parallel layer).  Assignments to
    the others sort last, belong to no group, and add nothing: the result
    is the part of the layer's sum that the held experts give.

    Where weights are the bound — on a TPU, few rows an expert by the
    static shapes (``grouped_matmul_impl``: T·k over the experts the router
    chooses among) — the three products are the Pallas kernel
    ``ops/pallas/grouped_matmul.py``, which streams each touched expert's
    weights once from where they lie: one algorithm whose best form changes
    with a shape the trace knows.  Above that, off the TPU, and under a
    mesh (GSPMD partitions ``ragged_dot`` on F; a Mosaic call it cannot)
    ``lax.ragged_dot`` stays."""
    from dynamo_tpu.ops.pallas import grouped_matmul as gmm
    from dynamo_tpu.ops.pallas.registry import grouped_matmul_row_tile

    t, d = xf.shape
    k = topi.shape[1]
    flat_e = topi.reshape(t * k)
    kernel = gmm.grouped_matmul_impl(
        t * k, num_experts, d, w_gate.shape[-1], xf.dtype, w_gate.dtype)
    here = None
    if held is not None:
        first, num_experts = held
        here = (flat_e >= first) & (flat_e < first + num_experts)
        flat_e = jnp.where(here, flat_e - first, num_experts)
    order = jnp.argsort(flat_e)          # stable: ties keep token order
    token_idx = order // k               # source token of each sorted row
    xs = xf[token_idx]                   # [T*k, Dm] gather
    group_sizes = jnp.bincount(flat_e, length=num_experts).astype(jnp.int32)
    if here is not None:
        # an id of ``num_experts`` (held elsewhere) is counted in no group
        group_sizes = jnp.bincount(
            flat_e, length=num_experts + 1)[:num_experts].astype(jnp.int32)
    if layer is not None:
        groups = w_gate.shape[0] * num_experts
        if not kernel:
            group_sizes = jax.lax.dynamic_update_slice(
                jnp.zeros(groups, jnp.int32), group_sizes,
                (layer * num_experts,))
        w_gate, w_up, w_down = (
            w.reshape(groups, *w.shape[2:]) for w in (w_gate, w_up, w_down))
    if kernel:
        # the plan is over this layer's experts; the kernel is told where
        # they begin in the stack
        tm = grouped_matmul_row_tile(t * k, max(d, w_gate.shape[-1]))
        plan = gmm.grouped_matmul_plan(group_sizes, t * k, tm)
        first_group = 0 if layer is None else layer * num_experts
        dot = lambda x, *ws: gmm.grouped_expert_matmul(
            x, ws, plan, first_group, tm=tm)
    else:
        dot = lambda x, *ws: [
            jax.lax.ragged_dot(x, w, group_sizes) for w in ws]
    gate, up = dot(xs, w_gate, w_up)     # one read of xs, two streams
    out, = dot(act(gate) * up, w_down)   # [T*k, Dm]
    out = out * weights.reshape(t * k)[order, None].astype(out.dtype)
    if here is not None:
        # rows past the last group are whatever the grouped dot left there
        out = jnp.where(here[order, None], out, 0)
    # unsort (inverse permutation) then reduce the k slots of each token;
    # gather+reshape-sum keeps the combine deterministic (no scatter-add)
    return out[jnp.argsort(order)].reshape(t, k, d).sum(axis=1)


# what a counting model's expert layer adds to its row of the cache's
# ``moe_counts``, by the key of the engine's count (obs/metric_names.py) each
# column is read back into: a model whose cache carries ``moe_counts`` says
# in ``moe_count_keys`` what its columns are, in order
EXPERT_COUNT_KEYS = ("moe_router_picks_total", "moe_held_picks_total",
                     "moe_expert_layer_calls_total",
                     "moe_experts_touched_total")
EXPERT_COUNTS = len(EXPERT_COUNT_KEYS)


def experts_touched(topi, first: int, count: int):
    """int32 scalar: how many of the experts ``first .. first+count-1`` have
    at least one of the picks ``topi`` [T, k] — the experts whose weights a
    ``grouped_expert_dispatch`` of these picks reads.  (The dispatch's own
    count of rows a held expert, so that XLA computes it once for both: a
    compare of every pick with every expert cost the TPU compiler 12
    CPU-seconds a Solar chunk program.)"""
    flat = topi.reshape(-1)
    here = (flat >= first) & (flat < first + count)
    sizes = jnp.bincount(jnp.where(here, flat - first, count),
                         length=count + 1)[:count]
    return (sizes > 0).sum(dtype=jnp.int32)


def _moe_mlp_grouped(cfg: ModelConfig, lp: dict, x: jax.Array,
                     layer=None) -> jax.Array:
    """Grouped MoE dispatch: sort token→expert assignments by expert, run
    ONE ragged (grouped) matmul per projection, unsort, weighted-sum per
    token.  Intermediates are [T·k, F] — E/k× smaller than the dense
    path's [T, E, F] — and FLOPs are exactly the k experts each token
    routed to (the dense path computes all E).

    TPU mapping: ``lax.ragged_dot`` is XLA's grouped matmul and tiles onto
    the MXU; under the mesh the expert FFN dim F is sharded over "model"
    (partition_specs), which GSPMD partitions directly — compute and
    weight memory split evenly across devices REGARDLESS of routing skew
    (device-EP would idle devices whose experts receive no tokens).
    Replaces the reference's inherited vLLM fused-MoE CUDA kernels
    (container/deps/vllm patch, grouped_topk region) with the XLA-native
    equivalent."""
    b, s, d = x.shape
    xf = x.reshape(b * s, d)
    with jax.named_scope("moe_router"):
        weights, topi = _moe_router(cfg, lp, xf)
    with jax.named_scope("moe_experts"):
        out = grouped_expert_dispatch(
            xf, weights, topi, cfg.num_experts,
            # quantized experts (always this layer's slice) dequantise
            # here.  Whether the convert fuses into the grouped dot's
            # operand load, HBM reads staying int8, is not measured; on
            # the TPU the dot is a custom call, whose operands are whole
            # buffers.
            dequantize(lp["w_gate"], x.dtype),
            dequantize(lp["w_up"], x.dtype),
            dequantize(lp["w_down"], x.dtype),
            lambda g: _act(cfg, g), layer=layer,
        )
    return out.reshape(b, s, d)


def _moe_mlp_dense(cfg: ModelConfig, lp: dict, x: jax.Array) -> jax.Array:
    """Dense-dispatch MoE oracle: each expert computes all tokens, weighted
    by its (top-k-normalised) router probability.  O(E/k) wasted FLOPs and
    [B,S,E,F] intermediates — kept as the parity oracle for the grouped
    path (DYNAMO_MOE_DENSE=1) because it contains no permutation logic."""
    b, s, d = x.shape
    weights, topi = _moe_router(cfg, lp, x.reshape(b * s, d))
    weights = weights.reshape(b, s, -1)
    topi = topi.reshape(b, s, -1)
    onehot = jax.nn.one_hot(topi, cfg.num_experts, dtype=jnp.float32)  # [B,S,k,E]
    gate_probs = jnp.einsum("bske,bsk->bse", onehot, weights)  # [B,S,E]
    w_up = dequantize(lp["w_up"], x.dtype)
    w_gate = dequantize(lp["w_gate"], x.dtype)
    w_down = dequantize(lp["w_down"], x.dtype)
    up = jnp.einsum("bsd,edf->bsef", x, w_up)
    gate = jnp.einsum("bsd,edf->bsef", x, w_gate)
    act = _act(cfg, gate) * up
    out = jnp.einsum("bsef,efd->bsed", act, w_down)
    return jnp.einsum("bsed,bse->bsd", out, gate_probs.astype(out.dtype))

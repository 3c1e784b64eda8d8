"""A decoder whose layers are of two kinds: most carry a recurrent state of
fixed size behind a short causal convolution, every few attend over a paged
cache — grouped-query attention with no positional term over the K/V pool
(``attention`` "gqa"), or latent attention in absorbed form over one cached
row a token (``attention`` "mla": the cache's ``kv`` leaf gives way to a
``latent`` leaf in ops/latent_cache.py's dense layout).  Every layer ends in
routed experts, of which this chip holds a share, plus a shared expert — or,
in the ``dense_layers`` leading layers and where the model has no experts, in
one gated MLP with no router.
The recurrence is the configuration's (``recurrence``): the gated delta rule
with a decay per key channel (``solar_open2``, ``ling_hybrid_mla``:
ops/linear_state.py), the state-space recurrence with a scalar decay a head
(``granitemoehybrid``'s Mamba-2 layers, ops/ssm_state.py) or the selective
scan with a decay a channel and state index (``jamba``'s Mamba-1 layers,
ops/selective_state.py).  docs/linear_state.md has the equations of all
three.

Per layer (pre-norm residual blocks; a mixer's and an expert layer's output
times ``residual_multiplier``):

  * **Delta-rule layer.**  q̂, k̂, v̂ = W x; a depth-wise convolution of
    ``short_conv_kernel_size`` over time on each, then SiLU; q and k L2-normed
    a head (q also by d^-1/2); decay g = -exp(A_log) · softplus(a + b_dt) a
    key channel or, in its lower-bound form (``decay_lower_bound`` b < 0),
    g = b · sigmoid(exp(A_log) · (a + b_dt)) in (b, 0); a = W_f↑ W_f↓ x, or
    W_f x full-rank where ``gate_rank`` is 0; step beta = ``beta_scale`` ·
    sigmoid(W_β x), in (0, 2) or (0, 1); the recurrence
    (``delta_rule_step`` for one token a row, ``delta_rule_scan`` for a
    prefill chunk); RMSNorm a head and a sigmoid gate of the decay's rank;
    W_o.
  * **State-space layer.**  z ‖ xBC ‖ dt = W_in x; one depth-wise convolution
    with a bias over x‖B‖C, then SiLU; Δ = softplus(dt + b_dt), decay
    exp(Δ·A) a head; the recurrence (``ssd_step`` / ``ssd_scan``); RMSNorm
    over the whole width of y ⊙ SiLU(z); W_out.
  * **Selective layer.**  x̃ ‖ z = W_in x; a depth-wise convolution with a
    bias over x̃, then SiLU; δ ‖ B ‖ C = W_x x̂, each through an RMS norm of
    its own; Δ = softplus(W_dt δ + b_dt) a channel, decay exp(Δ·A) a channel
    and state index; the recurrence (``selective_step`` /
    ``selective_scan``); (y + D ⊙ x̂) ⊙ SiLU(z); W_out.  No heads, no norm
    on the output.
  * **GQA layer.**  softmax(q kᵀ · scale) v through the K/V pool and the
    Pallas kernels every dense model here uses (ops/paged_attention.py), no
    rope, no q/k norm; scale d^-1/2 or ``attention_multiplier``;
    o ⊙ sigmoid(W_gate x) where the model has the gate; W_o.
  * **MLA layer.**  The projections and the absorbed form of
    models/glm_dsa.py (``latent_projections``: W_q direct where
    ``q_lora_rank`` is None, ĉ = RMSNorm(c), rope on q_pe and k_pe), the row
    ĉ ‖ rope(k_pe) written once into ``latent``, causal softmax over every
    cached row at scale (nope + rope)^-1/2 (``latent_cache.dense_attention``:
    ``mla_dense_decode`` / ``mla_dense_prefill`` on the TPU), the attended
    latent back through kv_b's V half, o_h ⊙ sigmoid(W_gate x)_h a head
    where ``head_gate``; W_o.
  * **Experts**: as models/glm_dsa.py — the router scores all
    ``router_experts``, this chip computes the part its own give.  With
    ``n_routed_experts`` 0 the layer's feed-forward is W_down(SiLU(W_gate u)
    ⊙ W_up u) and nothing else: no router, no shared expert; so is that of
    the first ``dense_layers`` layers of a model with experts, at
    ``intermediate_size``.  With ``n_group`` > 1 the choice is V3's
    group-limited one (``moe_route``).

Parameters are stacked per kind — the mixer's, and ``_dense`` behind it where
the layer ends in the MLP: parameters of another shape, a group and a scan of
their own — and each run of consecutive layers of one kind is one
``lax.scan``.

**The state is held per engine slot**, not per block: the cache is a dict of
``kv`` or ``latent`` (the pool, over the attending layers only; the engine
reads the leaf's name from ``pool_leaf``), ``state``, ``conv``,
``state_pos`` and ``moe_counts``, all under the one block table.  ``forward`` is told which slot each row of
the dispatch sits in (``seq_slots``; None: row i is slot i, a decode over the
slot array) and owns these rules: a row whose first position is 0 starts from
zeros; a token with ``slot_idx < 0`` is an identity step; a row with no real
token leaves its slot bit for bit as it was; ``state_pos[slot]`` becomes the
position after the row's last real token, and a row that continues at
another position than that is counted (``moe_counts[0, 0, 6]``).

One chip only, and nothing that moves blocks knows the state: the engine
refuses those paths at start-up (``private_cache_layout``,
``recurrent_state``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.deepseek import moe_route, routing_groups
from dynamo_tpu.models.glm_dsa import (
    ROUTER_BIAS_STD,
    dense_attention_impls,
    latent_params,
    latent_projections,
    latent_values,
)
from dynamo_tpu.models.llama import (
    EXPERT_COUNT_KEYS,
    EXPERT_COUNTS,
    experts_touched,
    grouped_expert_dispatch,
    rms_norm,
    rope_inv_freq,
    split_heads,
)
from dynamo_tpu.ops import (
    latent_cache,
    linear_state,
    selective_state,
    ssm_state,
)
from dynamo_tpu.ops.pallas.linear_state import state_update
from dynamo_tpu.ops.pallas.selective_state import (
    state_scan as selective_state_scan,
    state_update as selective_state_update,
)
from dynamo_tpu.ops.pallas.ssm_state import state_update as ssm_state_update
from dynamo_tpu.ops.paged_attention import (
    paged_attention_layer,
    prefill_attention,
    rows_by_length,
    write_kv_cache_layer,
)

Params = Any

__all__ = ["HybridLinearConfig", "HybridLinearModel", "DECAY_PROJ_STD",
           "STATE_COUNT_KEYS", "slot_rows", "decode_rows_by_length",
           "paged_gqa"]

QK_NORM_EPS = 1e-6
# standard deviation of what the seeded low-rank decay projection adds to
# b_dt.  With fan-in-scaled weights it is 1 and, worse, softplus of a
# zero-centred number is ~0.7: alpha = exp(-0.7 · 1..16) forgets in three
# tokens, and a state that forgets cannot show a broken chunk carry.  At 0.5
# the decay still moves with the token (a factor e^±0.5 on softplus) and its
# median stays where b_dt puts it
DECAY_PROJ_STD = 0.5
# a forward adds its three counts (real tokens x linear layers, sequences
# started from zeros, position mismatches) to ``moe_counts[0, 0, 4:7]``,
# behind the expert layers' ``EXPERT_COUNTS``; named like those
STATE_COUNT_KEYS = ("state_tokens_total", "state_resets_total",
                    "state_position_mismatches_total")
STATE_COUNTS = len(STATE_COUNT_KEYS)


@dataclass
class HybridLinearConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int                 # GQA query heads
    num_kv_heads: int
    head_dim: int
    linear_heads: int
    linear_head_dim: int           # key and value width a head
    conv_kernel: int
    gate_rank: int                 # rank of the decay and output gates
    gqa_layers: tuple              # indices of the attending layers
    moe_intermediate_size: int
    n_routed_experts: int          # experts held HERE
    router_experts: int            # experts the router chooses among
    expert_first: int
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    dtype: str = "bfloat16"
    # what the layers that do not attend run: "delta" (the gated delta rule,
    # a state of linear_head_dim x linear_head_dim a head), "ssd" (the
    # state-space recurrence, linear_head_dim x state_dim a head, B and C
    # shared by the heads of one of ``ssm_groups``, chunks of ``ssm_chunk``)
    # or "selective" (the selective scan: state_dim numbers a channel, the
    # channels as ``linear_heads`` rows of ``linear_head_dim`` = 128 lanes,
    # ``gate_rank`` the rank of the step's bottleneck)
    recurrence: str = "delta"
    state_dim: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 256
    gqa_gate: bool = True
    attention_multiplier: float | None = None   # softmax scale; None: d^-1/2
    shared_intermediate_size: int | None = None # None: n_shared x an expert's
    tie_word_embeddings: bool = False
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # the delta rule's switches: the decay's lower bound b < 0 (None: the
    # softplus form), what multiplies sigmoid(W_β x); ``gate_rank`` 0 makes
    # both of its gates full-rank
    decay_lower_bound: float | None = None
    beta_scale: float = 2.0
    # the leading layers that end in a dense MLP, and its width
    dense_layers: int = 0
    intermediate_size: int = 0
    n_group: int = 1               # routing groups (``moe_route``)
    topk_group: int = 1
    # what the attending layers are: "gqa" over the K/V pool, or "mla" over
    # one latent row a token (``head_dim`` is then the row, r + rope, and
    # ``num_kv_heads`` 1: what the engine sizes a token's cache by)
    attention: str = "gqa"
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    kv_lora_rank: int = 0
    q_lora_rank: int | None = None
    rope_theta: float = 10000.0
    head_gate: bool = False        # MLA: o_h ⊙ sigmoid(W_gate x)_h
    # how ``init_params`` draws a delta-rule layer's value path: with the
    # mean SiLU gives every v kept out of the residual stream (its docstring).
    # The rule of every delta-rule reader from PR 66 on; ``solar_open2``
    # keeps the draw its accepted cell was measured with.  A property of the
    # draw, fixed before a cell is measured: not a knob to meet a spread
    seed_without_common_mode: bool = False

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def linear_layers(self) -> int:
        return self.num_layers - len(self.gqa_layers)

    @property
    def ssm_width(self) -> int:
        return self.linear_heads * self.linear_head_dim

    @property
    def conv_width(self) -> int:
        """What the convolution runs over: q̂ ‖ k̂ ‖ v̂ of one token,
        x ‖ B ‖ C, or x̃ alone."""
        if self.recurrence == "ssd":
            return self.ssm_width + 2 * self.ssm_groups * self.state_dim
        if self.recurrence == "selective":
            return self.ssm_width
        return 3 * self.linear_heads * self.linear_head_dim

    @property
    def state_shape(self) -> tuple:
        """One slot's state in one recurrent layer: (H, d, d) of the delta
        rule, (H, P, N) of the state-space layers, (N, rows, lanes) of the
        selective ones (N leading, the channels filling rows of lanes)."""
        d = self.linear_head_dim
        if self.recurrence == "selective":
            return (self.state_dim, self.linear_heads, d)
        return (self.linear_heads, d,
                self.state_dim if self.recurrence == "ssd" else d)

    @property
    def shared_width(self) -> int:
        return (self.shared_intermediate_size
                or self.moe_intermediate_size * self.n_shared_experts)

    @classmethod
    def from_hf_config(cls, cfg: dict, dtype: str = "bfloat16"
                       ) -> "HybridLinearConfig":
        """The published ``solar_open2``, ``granitemoehybrid``, ``jamba`` or
        ``ling_hybrid_mla`` keys -> HybridLinearConfig.  Raises, by name, on what this port does not
        compute.  ``expert_parallel`` (not a published key) says which share
        of a layer's experts this chip holds, as for models/glm_dsa.py."""
        g = cfg.get
        if g("model_type") == "granitemoehybrid":
            return cls._from_granite(cfg, dtype)
        if g("model_type") == "jamba":
            return cls._from_jamba(cfg, dtype)
        if g("model_type") == "ling_hybrid_mla":
            return cls._from_ling(cfg, dtype)
        if g("model_type") != "solar_open2":
            raise NotImplementedError(f"model_type {g('model_type')!r}")
        lin = g("linear_attn_config") or {}
        n = int(g("num_hidden_layers"))
        gqa = tuple(int(i) for i in g("gqa_layers") or ())
        period = int(g("gqa_interval", 3)) + 1
        if gqa != tuple(range(0, n, period)):
            raise NotImplementedError(
                f"gqa_layers {list(gqa)} disagree with gqa_interval "
                f"{period - 1} over {n} layers (every {period}th from 0)")
        if bool(g("kda_use_full_proj", False)):
            raise NotImplementedError(
                "kda_use_full_proj=True (full-rank decay projection)")
        if not bool(g("kda_allow_neg_eigval", True)):
            raise NotImplementedError(
                "kda_allow_neg_eigval=False (beta in (0, 1))")
        if lin.get("num_kv_heads") not in (None, lin.get("num_heads")):
            raise NotImplementedError(
                "linear_attn_config.num_kv_heads "
                f"{lin.get('num_kv_heads')} != num_heads "
                f"{lin.get('num_heads')} (grouped linear heads)")
        if bool(g("use_rope", False)):
            raise NotImplementedError("use_rope=True (rotary GQA layers)")
        if not bool(g("use_gqa_gate", True)):
            raise NotImplementedError("use_gqa_gate=False")
        if int(g("first_k_dense_replace", 0)):
            raise NotImplementedError("first_k_dense_replace > 0")
        if g("rope_scaling") is not None:
            raise NotImplementedError(f"rope_scaling {g('rope_scaling')!r}")
        if int(g("n_group", 1) or 1) != 1 or int(g("topk_group", 1) or 1) != 1:
            raise NotImplementedError("group-limited expert choice")
        if g("scoring_func", "sigmoid") != "sigmoid":
            raise NotImplementedError(f"scoring_func {g('scoring_func')!r}")
        if g("topk_method", "noaux_tc") != "noaux_tc":
            raise NotImplementedError(f"topk_method {g('topk_method')!r}")
        held, total, first = _held_experts(cfg, "n_routed_experts")
        d = int(lin["head_dim"])
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=int(g("hidden_size")),
            num_layers=n, num_heads=int(g("num_attention_heads")),
            num_kv_heads=int(g("num_key_value_heads")),
            head_dim=int(g("head_dim")),
            linear_heads=int(lin["num_heads"]), linear_head_dim=d,
            conv_kernel=int(lin["short_conv_kernel_size"]),
            gate_rank=d, gqa_layers=gqa,
            moe_intermediate_size=int(g("moe_intermediate_size")),
            n_routed_experts=held, router_experts=total, expert_first=first,
            num_experts_per_tok=int(g("num_experts_per_tok")),
            n_shared_experts=int(g("n_shared_experts", 1)),
            routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(g("norm_topk_prob", True)),
            rms_norm_eps=float(g("rms_norm_eps", 1e-5)),
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype,
            tie_word_embeddings=bool(g("tie_word_embeddings", False)),
        )

    @classmethod
    def _from_ling(cls, cfg: dict, dtype: str) -> "HybridLinearConfig":
        """``ling_hybrid_mla`` (Ling-3.0-flash's language model; the name is
        this repository's, the catalog's row carries none): published layer
        L attends — MLA — iff (L + 1) % ``layer_group_size`` == 0 and is a
        delta-rule (KDA) layer otherwise, with full-rank gates and the
        decay's lower-bound form; layers below ``first_k_dense_replace`` end
        in a dense MLP of ``intermediate_size``, the others in ``num_experts``
        sigmoid-routed experts chosen inside the ``topk_group`` best of
        ``n_group`` groups.  ``published_layers`` (not a published key) names
        the published index of each of the file's layers where the file is a
        cut of the stack; the per-layer lists are read by it."""
        g = cfg.get
        for key in ("use_nGPT", "scale_router_input", "value_norm",
                    "up_proj_norm", "mtp_use_kda", "use_mla_nope",
                    "use_kda_lora"):
            if bool(g(key, False)):
                raise NotImplementedError(f"{key}=True")
        for key in ("no_kda_lora", "kda_safe_gate", "use_qk_norm",
                    "linear_silu", "moe_router_enable_expert_bias"):
            if not bool(g(key, True)):
                raise NotImplementedError(f"{key}=False")
        if g("score_function", "sigmoid") != "sigmoid":
            raise NotImplementedError(
                f"score_function {g('score_function')!r}")
        if g("gated_attention_proj_granularity_type",
             "head_wise") != "head_wise":
            raise NotImplementedError(
                "gated_attention_proj_granularity_type "
                f"{g('gated_attention_proj_granularity_type')!r}")
        if g("rope_scaling") is not None:
            raise NotImplementedError(f"rope_scaling {g('rope_scaling')!r}")
        if int(g("group_norm_size", 1)) != 1:
            raise NotImplementedError(
                f"group_norm_size {g('group_norm_size')} (several heads a "
                "norm group)")
        hq, d = int(g("num_attention_heads")), int(g("head_dim"))
        if int(g("num_kv_heads_for_linear_attn", 0) or 0) not in (0, hq):
            raise NotImplementedError(
                "num_kv_heads_for_linear_attn "
                f"{g('num_kv_heads_for_linear_attn')} (grouped linear heads)")
        rope = int(g("qk_rope_head_dim"))
        if int(g("rotary_dim", rope)) != rope or round(
                float(g("partial_rotary_factor", rope / d)) * d) != rope:
            raise NotImplementedError(
                f"rotary_dim {g('rotary_dim')} / partial_rotary_factor "
                f"{g('partial_rotary_factor')} beside qk_rope_head_dim {rope} "
                "(a rotary term outside the MLA layers' rope part)")
        bound = float(g("kda_lower_bound", -5.0))
        if not bound < 0:
            raise ValueError(f"kda_lower_bound {bound} is not below 0")
        n, period = int(g("num_hidden_layers")), int(g("layer_group_size"))
        published = [int(i) for i in g("published_layers") or range(n)]
        if len(published) != n or sorted(set(published)) != published:
            raise ValueError(
                f"published_layers {published} do not name {n} layers in "
                "ascending order")
        for key in ("expert_swiglu_limit_list",
                    "share_expert_swiglu_limit_list"):
            limits = list(g(key) or ())
            on = [i for i in published if i < len(limits) and limits[i]]
            if on:
                raise NotImplementedError(
                    f"{key} is non-zero in layers {on} (a clamp inside the "
                    "experts' SwiGLU)")
        dense = sum(i < int(g("first_k_dense_replace", 0)) for i in published)
        held, total, first = _held_experts(cfg, "num_experts")
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=int(g("hidden_size")),
            num_layers=n, num_heads=hq, num_kv_heads=1,
            head_dim=int(g("kv_lora_rank")) + rope,
            linear_heads=hq, linear_head_dim=d,
            conv_kernel=int(g("short_conv_kernel_size")), gate_rank=0,
            gqa_layers=tuple(at for at, i in enumerate(published)
                             if (i + 1) % period == 0),
            moe_intermediate_size=int(g("moe_intermediate_size")),
            n_routed_experts=held, router_experts=total, expert_first=first,
            num_experts_per_tok=int(g("num_experts_per_tok")),
            n_shared_experts=1,
            routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(g("norm_topk_prob", True)),
            rms_norm_eps=float(g("rms_norm_eps", 1e-6)),
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype, gqa_gate=False,
            shared_intermediate_size=int(
                g("moe_shared_expert_intermediate_size")),
            tie_word_embeddings=bool(g("tie_word_embeddings", False)),
            decay_lower_bound=bound, beta_scale=1.0,
            dense_layers=dense, intermediate_size=int(g("intermediate_size")),
            attention="mla",
            qk_nope_head_dim=int(g("qk_nope_head_dim")),
            qk_rope_head_dim=rope, v_head_dim=int(g("v_head_dim")),
            kv_lora_rank=int(g("kv_lora_rank")),
            q_lora_rank=(None if g("q_lora_rank") is None
                         else int(g("q_lora_rank"))),
            rope_theta=float(g("rope_theta", 10000.0)), head_gate=True,
            seed_without_common_mode=True,
            **routing_groups(cfg, total),
        )

    @classmethod
    def _from_granite(cls, cfg: dict, dtype: str) -> "HybridLinearConfig":
        """``granitemoehybrid``: ``layer_types`` says which layers attend,
        ``mamba_*`` sizes the others, ``intermediate_size`` is one expert's
        width and ``shared_intermediate_size`` the shared MLP's."""
        g = cfg.get
        n = int(g("num_hidden_layers"))
        kinds = list(g("layer_types") or ())
        if len(kinds) != n or set(kinds) - {"mamba", "attention"}:
            raise NotImplementedError(
                f"layer_types {kinds} over {n} layers (one of 'mamba' | "
                "'attention' a layer)")
        if g("position_embedding_type", "nope") != "nope":
            raise NotImplementedError(
                f"position_embedding_type {g('position_embedding_type')!r} "
                "(rotary attention layers)")
        for key in ("attention_bias", "mamba_proj_bias"):
            if bool(g(key, False)):
                raise NotImplementedError(f"{key}=True")
        if not bool(g("mamba_conv_bias", True)):
            raise NotImplementedError("mamba_conv_bias=False")
        if g("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {g('hidden_act')!r}")
        if g("normalization_function", "rmsnorm") != "rmsnorm":
            raise NotImplementedError(
                f"normalization_function {g('normalization_function')!r}")
        dm = int(g("hidden_size"))
        heads, p = int(g("mamba_n_heads")), int(g("mamba_d_head"))
        if heads * p != int(g("mamba_expand")) * dm:
            raise ValueError(
                f"mamba_n_heads {heads} x mamba_d_head {p} is not "
                f"mamba_expand {g('mamba_expand')} x hidden_size {dm}")
        groups = int(g("mamba_n_groups", 1))
        if heads % groups:
            raise ValueError(f"{heads} heads in {groups} groups")
        held, total, first = _held_experts(cfg, "num_local_experts")
        hq = int(g("num_attention_heads"))
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=dm, num_layers=n,
            num_heads=hq, num_kv_heads=int(g("num_key_value_heads")),
            head_dim=int(g("head_dim") or dm // hq),
            linear_heads=heads, linear_head_dim=p,
            conv_kernel=int(g("mamba_d_conv")), gate_rank=0,
            gqa_layers=tuple(i for i, k in enumerate(kinds)
                             if k == "attention"),
            moe_intermediate_size=int(g("intermediate_size")),
            n_routed_experts=held, router_experts=total, expert_first=first,
            num_experts_per_tok=int(g("num_experts_per_tok")),
            n_shared_experts=1, routed_scaling_factor=1.0,
            # softmax over the chosen logits = softmax over all of them,
            # renormalised over the chosen
            norm_topk_prob=True, scoring_func="softmax", topk_method="greedy",
            rms_norm_eps=float(g("rms_norm_eps", 1e-5)),
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype, recurrence="ssd",
            state_dim=int(g("mamba_d_state")), ssm_groups=groups,
            ssm_chunk=int(g("mamba_chunk_size", 256)), gqa_gate=False,
            attention_multiplier=float(g("attention_multiplier")),
            shared_intermediate_size=int(g("shared_intermediate_size")),
            tie_word_embeddings=bool(g("tie_word_embeddings", False)),
            embedding_multiplier=float(g("embedding_multiplier", 1.0)),
            residual_multiplier=float(g("residual_multiplier", 1.0)),
            logits_scaling=float(g("logits_scaling", 1.0)),
        )


    @classmethod
    def _from_jamba(cls, cfg: dict, dtype: str) -> "HybridLinearConfig":
        """``jamba``: layer i attends iff i % ``attn_layer_period`` ==
        ``attn_layer_offset`` (``JambaConfig.layers_block_type``), ``mamba_*``
        sizes the others, and with ``num_experts`` 1 every layer's
        feed-forward is one gated MLP of ``intermediate_size``
        (``layers_num_experts``)."""
        g = cfg.get
        if int(g("num_experts", 16)) > 1:
            raise NotImplementedError(
                f"num_experts {g('num_experts', 16)} (Jamba's sparse "
                "mixture every expert_layer_period-th layer; this port "
                "serves the router-less MLP of num_experts 1)")
        if g("sliding_window") is not None:
            raise NotImplementedError(
                f"sliding_window {g('sliding_window')!r}")
        if bool(g("mamba_proj_bias", False)):
            raise NotImplementedError("mamba_proj_bias=True")
        if not bool(g("mamba_conv_bias", True)):
            raise NotImplementedError("mamba_conv_bias=False")
        if g("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {g('hidden_act')!r}")
        n, dm = int(g("num_hidden_layers")), int(g("hidden_size"))
        period, offset = (int(g("attn_layer_period", 8)),
                          int(g("attn_layer_offset", 4)))
        if not 0 <= offset < period:
            raise ValueError(
                f"attn_layer_offset {offset} is not below attn_layer_period "
                f"{period}")
        inner = int(g("mamba_expand", 2)) * dm
        lanes = 128 if inner % 128 == 0 else inner
        rank = g("mamba_dt_rank", "auto")
        hq = int(g("num_attention_heads"))
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=dm, num_layers=n,
            num_heads=hq, num_kv_heads=int(g("num_key_value_heads") or hq),
            head_dim=int(g("head_dim") or dm // hq),
            linear_heads=inner // lanes, linear_head_dim=lanes,
            conv_kernel=int(g("mamba_d_conv", 4)),
            gate_rank=-(-dm // 16) if rank == "auto" else int(rank),
            gqa_layers=tuple(i for i in range(n) if i % period == offset),
            moe_intermediate_size=int(g("intermediate_size")),
            n_routed_experts=0, router_experts=0, expert_first=0,
            num_experts_per_tok=0, n_shared_experts=0,
            routed_scaling_factor=1.0, norm_topk_prob=True,
            rms_norm_eps=float(g("rms_norm_eps", 1e-6)),
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype, recurrence="selective",
            state_dim=int(g("mamba_d_state", 16)), gqa_gate=False,
            tie_word_embeddings=bool(g("tie_word_embeddings", False)),
        )


def _held_experts(cfg: dict, key: str) -> tuple[int, int, int]:
    """(experts held here, experts the router chooses among, the first held)
    from the file's count under ``key`` and its ``expert_parallel`` block."""
    ep = cfg.get("expert_parallel") or {}
    held = int(cfg[key])
    total = int(ep.get("router_experts", held))
    first = int(ep.get("first_expert", 0))
    if not 0 <= first <= total - held:
        raise ValueError(
            f"experts {first}..{first + held - 1} are not among the "
            f"router's {total}")
    return held, total, first


def slot_rows(pos, positions, slot_idx, seq_slots, layers: int):
    """The slot contract's part of a forward (docs/linear_state.md), for any
    model that keeps something per engine slot.  ``pos`` is the cache's
    ``state_pos`` [slots]; ``positions`` / ``slot_idx`` [B, S] the
    dispatch's; ``seq_slots`` [B] or None (row i is slot i) ->
    (rows = (seq_slots, fresh [B], alive [B], n_real [B], valid [B, S]), the
    new ``state_pos``, and the three counts of ``STATE_COUNT_KEYS``: real
    tokens x ``layers``, sequences started from zeros, rows that went on at
    another position than their slot's)."""
    valid = slot_idx >= 0
    n_real = valid.sum(axis=1, dtype=jnp.int32)
    alive = n_real > 0
    first = positions[:, 0]
    fresh = alive & (first == 0)
    held = pos if seq_slots is None else pos[seq_slots]
    after = jnp.where(alive, first + n_real, held)
    state_pos = after if seq_slots is None else pos.at[seq_slots].set(after)
    counted = jnp.stack([
        n_real.sum(dtype=jnp.int32) * layers,
        fresh.sum(dtype=jnp.int32),
        (alive & ~fresh & (first != held)).sum(dtype=jnp.int32)])
    return (seq_slots, fresh, alive, n_real, valid), state_pos, counted


def decode_rows_by_length(block_tables, seq_lens, positions):
    """A decode step's rows as the kernel takes them, longest first
    (``rows_by_length``), made once before the layer scan: (order, inverse,
    tables, lens, positions in that order)."""
    order, inverse = rows_by_length(seq_lens)
    return (order, inverse, block_tables[order], seq_lens[order],
            positions[order])


def paged_gqa(q, k, v, kv, ci, positions, block_tables, seq_lens, slot_idx,
              prefix_blocks, by_length, sm_scale):
    """Write this dispatch's k, v [B, S, Hk, D] into row ``ci`` of the pool
    and attend: (attention [B, S, H, D], the pool).  A prefill chunk through
    ``prefill_attention``, a decode step's rows longest first
    (``by_length``), the state staying in slot order."""
    s = q.shape[1]
    fast = prefix_blocks is not None and s > 1
    kv = write_kv_cache_layer(kv, ci, k, v, slot_idx, block_aligned=fast)
    if fast:
        attn = prefill_attention(
            q, k, v, kv, ci, block_tables, seq_lens, positions[:, 0],
            prefix_blocks, sm_scale=sm_scale)
    elif by_length is not None:
        order, inverse, tables, lens, at = by_length
        attn = paged_attention_layer(
            q[order], kv, ci, tables, lens, at, sm_scale=sm_scale)[inverse]
    else:
        attn = paged_attention_layer(
            q, kv, ci, block_tables, seq_lens, positions, sm_scale=sm_scale)
    return attn, kv


@dataclass(frozen=True)
class _Run:
    """Consecutive layers of one kind: a scan."""
    kind: str        # the mixer, "gqa" | "mla" | "linear", and "_dense"
                     # behind it where the layers end in the dense MLP
    start: int       # first index within the kind's stacks
    count: int
    layer0: int      # first layer index (row of ``moe_counts``)


class HybridLinearModel:
    """Engine-facing functional model (same protocol as LlamaModel)."""

    private_cache_layout = True
    # a state per engine slot beside the pool: the engine hands ``forward``
    # the slots of a prefill dispatch's rows, keeps prefix reuse off, and
    # refuses what packs several sequences into one row axis
    recurrent_state = True
    pool_leaf = "kv"
    moe_count_keys = EXPERT_COUNT_KEYS + STATE_COUNT_KEYS
    supports_ragged_prefill = False
    supports_unified_dispatch = False
    supports_seq_parallel = False
    # ``prefix_blocks`` goes to ``prefill_attention`` and nowhere else
    prefix_blocks_sizes_forward = False

    def __init__(self, config: HybridLinearConfig, state_dtype=jnp.float32):
        """``state_dtype``: what ``state`` is *stored* in between dispatches
        (the arithmetic is float32 either way).  float32 is the model; bf16
        is the negative control of the check
        (scripts/hybrid_linear_longctx_check.py)."""
        self.config = config
        self.state_dtype = state_dtype
        if config.attention == "mla":
            self.sm_scale = float(
                (config.qk_nope_head_dim + config.qk_rope_head_dim) ** -0.5)
            self.inv_freq = rope_inv_freq(config.qk_rope_head_dim,
                                          config.rope_theta)
            # what the engine asks a model with attention kernels and a
            # cache leaf of its own (as GlmDsaModel): the leaf held by
            # block, the start-up line's kernels, the rows a decode fetches,
            # and a context gather sized by ``prefix_blocks``
            from dynamo_tpu.ops.pallas.mla_dense_attention import (
                decode_rows_fetched,
            )
            self.pool_leaf = "latent"
            self.attention_impls = dense_attention_impls
            self.decode_rows_fetched = decode_rows_fetched
            self.prefix_blocks_sizes_forward = True
        else:
            self.sm_scale = float(config.head_dim ** -0.5
                                  if config.attention_multiplier is None
                                  else config.attention_multiplier)
        runs, seen = [], {}
        for li in range(config.num_layers):
            kind = config.attention if li in config.gqa_layers else "linear"
            if config.n_routed_experts and li < config.dense_layers:
                kind += "_dense"
            at = seen.get(kind, 0)
            if runs and runs[-1].kind == kind:
                last = runs[-1]
                runs[-1] = _Run(kind, last.start, last.count + 1, last.layer0)
            else:
                runs.append(_Run(kind, at, 1, li))
            seen[kind] = at + 1
        self.runs = tuple(runs)
        self.group_sizes = seen
        # a layer's row of its mixer's part of the cache (``kv`` / ``latent``,
        # or ``state`` / ``conv``) is its index within its kind, behind the
        # rows of the leading dense layers with the same mixer
        self.cache_shift = {
            kind: 0 if kind.endswith("_dense") else seen.get(kind + "_dense", 0)
            for kind in seen}
        self._draw = jax.jit(self._draw_params)

    # ------------------------------------------------------------------ init
    def init_params(self, rng: jax.Array) -> Params:
        """Seeded weights: normal / sqrt(fan-in), norms 1, and the decay's
        own (A_log = ln U(1, 16) a head; b_dt the inverse softplus of a
        log-uniform step in [0.001, 0.1] a channel — a head for the
        state-space layers — the family's initialisation; what the input
        adds to b_dt scaled by ``DECAY_PROJ_STD``; under the delta rule's
        lower-bound gate b_dt = logit(step / |b|) / exp(A_log) and the
        projection scaled by ``DECAY_PROJ_STD`` / exp(A_log) a head, so that
        the decay's median is the same step's and moves with the token by
        the same factor; with ``seed_without_common_mode`` the value path's
        convolution taps have unit norm a channel and W_o zero mean over a
        head's channels — SiLU gives every v the same positive mean, which
        the state sums coherently and W_o would hand every token as one
        common vector: 30% of a normed hidden state at these widths, so
        every token's router favoured the same experts, 64 rows touched a
        third of the experts held where an even router touches 63%, and by
        how much (±11% a layer) was the seed's (PERF.md §6, PR 66); the
        selective layers'
        A_log = ln(1..N) a channel and W_dt uniform in ±rank^-1/2, Mamba's
        published initialisation).  Keys are drawn in a
        fixed order: a new parameter goes after the ones that are there.  One program: made
        array by array, the draws are some forty compilations (200 s of a
        first start on the chip)."""
        return self._draw(rng)

    def _draw_params(self, rng: jax.Array) -> Params:
        cfg = self.config
        dt = cfg.jax_dtype
        dm = cfg.hidden_size
        h, hk, dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
        lh, ld, r = cfg.linear_heads, cfg.linear_head_dim, cfg.gate_rank
        keys = iter(jax.random.split(rng, 64))

        def dense(shape, fan_in, scale=1.0, dtype=dt):
            return (scale * jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)

        def step_size(shape):
            """A decay step log-uniform in [0.001, 0.1]."""
            return jnp.exp(jax.random.uniform(
                next(keys), shape, jnp.float32,
                math.log(0.001), math.log(0.1)))

        def decay_step(shape):
            """Its inverse softplus: softplus^-1(s) = ln(e^s - 1)."""
            return jnp.log(jnp.expm1(step_size(shape)))

        def a_log(n: int):
            return jnp.log(jax.random.uniform(
                next(keys), (n, lh), jnp.float32, 1.0, 16.0))

        def experts(n: int) -> dict:
            e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
            fs = cfg.shared_width
            out = {"mlp_norm": jnp.ones((n, dm), dt),
                   "router": dense((n, dm, cfg.router_experts), dm)}
            if cfg.topk_method == "noaux_tc":      # the correction bias
                out["router_bias"] = ROUTER_BIAS_STD * jax.random.normal(
                    next(keys), (n, cfg.router_experts), jnp.float32)
            out.update(
                w_gate=dense((n, e, dm, f), dm),
                w_up=dense((n, e, dm, f), dm),
                w_down=dense((n, e, f, dm), f),
                shared_gate=dense((n, dm, fs), dm),
                shared_up=dense((n, dm, fs), dm),
                shared_down=dense((n, fs, dm), fs))
            return out

        def mlp(n: int, f: int) -> dict:
            """One gated MLP: the feed-forward of a model without experts,
            and of the leading dense layers of one with them."""
            return {"mlp_norm": jnp.ones((n, dm), dt),
                    "mlp_gate": dense((n, dm, f), dm),
                    "mlp_up": dense((n, dm, f), dm),
                    "mlp_down": dense((n, f, dm), f)}

        def feed_forward(n: int, leading: bool = False) -> dict:
            if leading:
                return mlp(n, cfg.intermediate_size)
            if cfg.n_routed_experts:
                return experts(n)
            return mlp(n, cfg.moe_intermediate_size)

        def gqa(n: int) -> dict:
            out = {"attn_norm": jnp.ones((n, dm), dt),
                   "wq": dense((n, dm, h * dh), dm),
                   "wk": dense((n, dm, hk * dh), dm),
                   "wv": dense((n, dm, hk * dh), dm)}
            if cfg.gqa_gate:
                out["w_gate_attn"] = dense((n, dm, h * dh), dm)
            out["wo"] = dense((n, h * dh, dm), h * dh)
            return out

        def mla(n: int) -> dict:
            out = {"attn_norm": jnp.ones((n, dm), dt),
                   **latent_params(cfg, n, dense, dt)}
            if cfg.head_gate:
                out["w_gate_heads"] = dense((n, dm, h), dm)
            out["wo"] = dense((n, h * cfg.v_head_dim, dm), h * cfg.v_head_dim)
            return out

        def linear(n: int) -> dict:
            width, bound = lh * ld, cfg.decay_lower_bound
            step = step_size((n, width))
            out = {"attn_norm": jnp.ones((n, dm), dt),
                   "wq": dense((n, dm, width), dm),
                   "wk": dense((n, dm, width), dm),
                   "wv": dense((n, dm, width), dm),
                   "conv_w": dense((n, 3 * width, cfg.conv_kernel),
                                   cfg.conv_kernel, dtype=jnp.float32)}
            if cfg.seed_without_common_mode:
                # every value channel's taps at unit norm: one E[SiLU] for all
                taps = out["conv_w"][:, 2 * width:]
                out["conv_w"] = out["conv_w"].at[:, 2 * width:].set(
                    taps * jax.lax.rsqrt(
                        jnp.sum(taps * taps, axis=-1, keepdims=True)))
            out["conv_w"] = out["conv_w"].astype(dt)
            # what the decay's projection adds to b_dt, drawn at standard
            # deviation 1 here and scaled below, once A is drawn
            if r:
                out["decay_down"] = dense((n, dm, r), dm)
                last = "decay_up"
                out[last] = dense((n, r, width), r, dtype=jnp.float32)
            else:
                last = "w_decay"
                out[last] = dense((n, dm, width), dm, dtype=jnp.float32)
            out["a_log"] = a_log(n)
            if bound is None:
                scale = jnp.float32(DECAY_PROJ_STD)
                out["dt_bias"] = jnp.log(jnp.expm1(step))
            else:
                # g = b·sigmoid(A·(a + b_dt)): b_dt puts the median decay at
                # the drawn step, and A multiplies what the token adds
                a_head = jnp.repeat(jnp.exp(out["a_log"]), ld, axis=-1)
                scale = (DECAY_PROJ_STD / a_head)[:, None, :]
                ratio = step / abs(bound)
                out["dt_bias"] = jnp.log(ratio / (1.0 - ratio)) / a_head
            out[last] = (out[last] * scale).astype(dt)
            out["w_beta"] = dense((n, dm, lh), dm)
            out["out_norm"] = jnp.ones((n, ld), dt)
            if r:
                out["gate_down"] = dense((n, dm, r), dm)
                out["gate_up"] = dense((n, r, width), r)
            else:
                out["w_out_gate"] = dense((n, dm, width), dm)
            out["wo"] = dense((n, width, dm), width, dtype=jnp.float32)
            if cfg.seed_without_common_mode:
                # ... and W_o blind to what is constant over a head's channels
                heads = out["wo"].reshape(n, lh, ld, dm)
                out["wo"] = (heads - heads.mean(axis=2, keepdims=True)
                             ).reshape(n, width, dm)
            out["wo"] = out["wo"].astype(dt)
            return out

        def conv_init(shape):
            bound = cfg.conv_kernel ** -0.5
            return jax.random.uniform(next(keys), shape, jnp.float32,
                                      -bound, bound).astype(dt)

        def ssd(n: int) -> dict:
            inner, w = cfg.ssm_width, cfg.conv_width
            dt_bias = decay_step((n, lh))
            # z ‖ xBC ‖ dt; the dt columns scaled as the delta rule's
            # low-rank decay projection is, and for its reason
            cols = jnp.concatenate([jnp.ones((inner + w,), jnp.float32),
                                    jnp.full((lh,), DECAY_PROJ_STD)])
            w_in = (jax.random.normal(next(keys), (n, dm, inner + w + lh),
                                      jnp.float32)
                    * cols / math.sqrt(dm)).astype(dt)
            return {
                "attn_norm": jnp.ones((n, dm), dt),
                "w_in": w_in,
                # Mamba-2's: a depth-wise Conv1d's default, U(±K^-1/2)
                "conv_w": conv_init((n, w, cfg.conv_kernel)),
                "conv_b": conv_init((n, w)),
                "a_log": a_log(n),
                "dt_bias": dt_bias,
                "d_skip": jnp.ones((n, lh), jnp.float32),
                "out_norm": jnp.ones((n, inner), dt),
                "wo": dense((n, inner, dm), inner),
            }

        def selective(n: int) -> dict:
            inner, ns = cfg.ssm_width, cfg.state_dim
            return {
                "attn_norm": jnp.ones((n, dm), dt),
                "w_in": dense((n, dm, 2 * inner), dm),          # x̃ ‖ z
                "conv_w": conv_init((n, inner, cfg.conv_kernel)),
                "conv_b": conv_init((n, inner)),
                "w_x": dense((n, inner, r + 2 * ns), inner),    # δ ‖ B ‖ C
                "dt_norm": jnp.ones((n, r), dt),
                "b_norm": jnp.ones((n, ns), dt),
                "c_norm": jnp.ones((n, ns), dt),
                "w_dt": jax.random.uniform(
                    next(keys), (n, r, inner), jnp.float32,
                    -r ** -0.5, r ** -0.5).astype(dt),
                "dt_bias": decay_step((n, inner)),
                # [N, channels]: the published A_log [channels, N] turned,
                # as the state lies
                "a_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, ns + 1, dtype=jnp.float32))[:, None],
                    (n, ns, inner)),
                "d_skip": jnp.ones((n, inner), jnp.float32),
                "wo": dense((n, inner, dm), inner),
            }

        make = {"gqa": gqa, "mla": mla,
                "linear": {"ssd": ssd, "selective": selective,
                           "delta": linear}[cfg.recurrence]}

        def group(kind: str, n: int) -> dict:
            mixer, _, leading = kind.partition("_")
            return {**make[mixer](n), **feed_forward(n, bool(leading))}

        out = {
            "embed": dense((cfg.vocab_size, dm), dm),
            "groups": {kind: group(kind, n)
                       for kind, n in sorted(self.group_sizes.items())},
            "final_norm": jnp.ones((dm,), dt),
        }
        if not cfg.tie_word_embeddings:
            out["lm_head"] = dense((dm, cfg.vocab_size), dm)
        return out

    def partition_specs(self) -> Params:
        raise NotImplementedError(
            "HybridLinearModel serves one chip's share of an expert-parallel "
            "deployment on one chip; it has no partition specs (neither the "
            "exchange of an expert-parallel layer nor a recurrent state "
            "sharded over heads is built)")

    def cache_spec(self, quant: bool = False):
        if quant:
            raise NotImplementedError("int8 K/V beside a recurrent state")
        return {self.pool_leaf: P(), "state": P(), "conv": P(),
                "state_pos": P(), "moe_counts": P()}

    # --------------------------------------------------------------- kv cache
    def init_kv_cache(self, num_blocks: int, block_size: int, dtype=None,
                      slots: int | None = None):
        """``kv``: the K/V pool in LlamaModel's layout over the attending
        layers only, [L_gqa, N, 2, Bs, Hk·D], what the engine counts a
        token's cache bytes by — or, where those layers are latent ones,
        ``latent`` [L_mla, N, Bs, Wd]: the row ĉ ‖ rope(k_pe) in
        ops/latent_cache.py's dense layout; ``state``
        [L_lin, slots, *state_shape] float32 (H, d, d of the delta rule;
        H, P, N of the state-space layers; N, rows, lanes of the selective
        ones), ``conv`` [L_lin, slots, K-1, conv_width] and
        ``state_pos`` [slots] (ops/linear_state.py), indexed by the engine's
        slot; ``moe_counts`` int32 [L, 1, 7]: what the expert
        layers counted (as models/glm_dsa.py) and, in row 0, what the linear
        layers did — real tokens × layers advanced, sequences started from
        zeros, rows that continued at another position than their slot's."""
        cfg = self.config
        if dtype is not None and jnp.dtype(dtype) != jnp.dtype(cfg.jax_dtype):
            raise NotImplementedError(f"K/V cache dtype {dtype!r}")
        if slots is None:
            raise ValueError(
                "a recurrent state is held per engine slot: init_kv_cache "
                "needs slots= (EngineCore passes max_batch_size)")
        if cfg.attention == "mla":
            pool = latent_cache.init_dense_cache(
                len(cfg.gqa_layers), num_blocks, block_size, cfg.head_dim,
                cfg.jax_dtype)
        else:
            pool = {"kv": jnp.zeros(
                (len(cfg.gqa_layers), num_blocks, 2, block_size,
                 cfg.num_kv_heads * cfg.head_dim), cfg.jax_dtype)}
        return {
            **pool,
            **linear_state.init_state(
                cfg.linear_layers, slots, *cfg.state_shape, cfg.conv_width,
                cfg.conv_kernel, cfg.jax_dtype, self.state_dtype),
            "moe_counts": jnp.zeros(
                (cfg.num_layers, 1, len(self.moe_count_keys)), jnp.int32),
        }

    def state_bytes_per_slot(self) -> int:
        cfg = self.config
        per_layer = (math.prod(cfg.state_shape)
                     * jnp.dtype(self.state_dtype).itemsize
                     + (cfg.conv_kernel - 1) * cfg.conv_width
                     * jnp.dtype(cfg.jax_dtype).itemsize)
        return cfg.linear_layers * per_layer

    def state_update_impl(self) -> tuple[str, str]:
        """("pallas" | "xla", why) for a decode step's state update: the one
        place the choice is made, before tracing (as
        ``paged_attention.attention_impl``)."""
        if self.config.recurrence == "ssd":
            return ssm_state.step_impl(*self.config.state_shape,
                                       self.config.ssm_groups,
                                       self.state_dtype)
        if self.config.recurrence == "selective":
            return selective_state.step_impl(*self.config.state_shape,
                                             self.state_dtype)
        return linear_state.step_impl(*self.config.state_shape,
                                      self.state_dtype)

    def state_scan_impl(self) -> tuple[str, str]:
        """``state_update_impl`` for a prefill chunk's recurrence: a kernel
        for the selective scan alone, which has no matrix-product form of a
        chunk; the other two run their chunked XLA forms."""
        if self.config.recurrence != "selective":
            return "xla", f"the {self.config.recurrence} chunk is XLA's"
        return selective_state.scan_impl(*self.config.state_shape,
                                         self.state_dtype)

    def _updates_in_place(self, s: int, slots) -> bool:
        """A decode over the slot array updates the state where it lies, in
        its recurrence's kernel (ops/pallas/linear_state.py, ssm_state.py,
        selective_state.py), and so does a prefill chunk of the selective
        scan; any other prefill chunk, and any backend but the TPU, slices
        the state, runs the XLA form and sets it."""
        if s == 1:
            return slots is None and self.state_update_impl()[0] == "pallas"
        return self.state_scan_impl()[0] == "pallas"

    # ---------------------------------------------------------------- forward
    def _add(self, h, out):
        """The residual stream plus a mixer's or an expert layer's output."""
        m = self.config.residual_multiplier
        return h + (out if m == 1.0 else out * jnp.asarray(m, out.dtype))

    def _experts(self, group: dict, lp: dict, i, h, valid):
        """h + MoE(RMSNorm(h)) and the layer's four counts."""
        cfg = self.config
        b, s, d = h.shape
        xf = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps).reshape(b * s, d)
        with jax.named_scope("moe_router"):
            weights, topi = moe_route(cfg, lp["router"], xf,
                                      lp.get("router_bias"))
            real = valid.reshape(b * s, 1)
            here = ((topi >= cfg.expert_first)
                    & (topi < cfg.expert_first + cfg.n_routed_experts) & real)
            counted = jnp.stack([
                real.sum(dtype=jnp.int32) * cfg.num_experts_per_tok,
                here.sum(dtype=jnp.int32), jnp.int32(1),
                experts_touched(topi, cfg.expert_first,
                                cfg.n_routed_experts)])
        with jax.named_scope("moe_experts"):
            routed = grouped_expert_dispatch(
                xf, weights, topi, cfg.router_experts,
                group["w_gate"], group["w_up"], group["w_down"],
                jax.nn.silu, layer=i,
                held=(cfg.expert_first, cfg.n_routed_experts))
        shared = (jax.nn.silu(xf @ lp["shared_gate"])
                  * (xf @ lp["shared_up"])) @ lp["shared_down"]
        return self._add(h, (routed + shared).reshape(b, s, d)), counted

    def _mlp(self, lp: dict, h):
        """h + W_down(SiLU(W_gate u) ⊙ W_up u), u = RMSNorm(h): the
        feed-forward of a model without experts, and of the leading dense
        layers of one with them."""
        with jax.named_scope("dense_mlp"):
            x = rms_norm(h, lp["mlp_norm"], self.config.rms_norm_eps)
            return self._add(h, (jax.nn.silu(x @ lp["mlp_gate"])
                                 * (x @ lp["mlp_up"])) @ lp["mlp_down"])

    def _gqa(self, lp, ci, h, kv, positions, block_tables, seq_lens,
             slot_idx, prefix_blocks, by_length):
        cfg = self.config
        b, s, _ = h.shape
        with jax.named_scope("attn_proj"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            q = split_heads(x @ lp["wq"], cfg.num_heads)
            k = split_heads(x @ lp["wk"], cfg.num_kv_heads)
            v = (x @ lp["wv"]).reshape(b, s, cfg.num_kv_heads, cfg.head_dim)
            if cfg.gqa_gate:
                gate = jax.nn.sigmoid(
                    (x @ lp["w_gate_attn"]).astype(jnp.float32))
        with jax.named_scope("attn"):
            attn, kv = paged_gqa(q, k, v, kv, ci, positions, block_tables,
                                 seq_lens, slot_idx, prefix_blocks,
                                 by_length, self.sm_scale)
        with jax.named_scope("attn_out"):
            o = attn.reshape(b, s, -1)
            if cfg.gqa_gate:
                o = o.astype(jnp.float32) * gate
            h = self._add(h, o.astype(h.dtype) @ lp["wo"])
        return h, kv

    def _mla(self, lp, ci, h, latent, positions, block_tables, seq_lens,
             slot_idx, groups):
        """One latent-attention layer: the shared projections
        (``latent_projections``), the row written into row ``ci`` of
        ``latent``, every cached row attended (``block_tables`` cut to the
        context the call reads), the gate a head.  The whole mixer is under
        ``latent_attn``."""
        cfg = self.config
        b, s, _ = h.shape
        f32 = jnp.float32
        with jax.named_scope("latent_attn"):
            with jax.named_scope("attn_proj"):
                x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
                q_lat, row, kv_b, _ = latent_projections(
                    cfg, lp, x, positions, self.inv_freq)
                latent = latent_cache.write_dense(
                    latent, ci, row.reshape(b * s, -1),
                    slot_idx.reshape(b * s))
                # the softmax scale rides on the query: the dense kernels
                # know none
                q_lat = (q_lat.astype(f32) * self.sm_scale).astype(
                    q_lat.dtype)
                if cfg.head_gate:
                    gate = jax.nn.sigmoid(
                        (x @ lp["w_gate_heads"]).astype(f32))[..., None]
            with jax.named_scope("attn"):
                out = latent_cache.dense_attention(
                    q_lat, latent, ci, block_tables, positions, seq_lens,
                    dv=cfg.kv_lora_rank, groups=groups)
            with jax.named_scope("attn_out"):
                o = latent_values(cfg, out, kv_b, h.dtype)
                if cfg.head_gate:
                    o = (o.astype(f32) * gate).astype(h.dtype)
                h = self._add(h, o.reshape(b, s, -1) @ lp["wo"])
        return h, latent

    def _ssd(self, lp, si, h, state, conv, rows):
        """One state-space layer; arguments as ``_linear``.  The state's own
        read-update-write is under ``ssm_state``, the mixer between its
        projections under ``ssm``."""
        cfg = self.config
        b, s, _ = h.shape
        heads, p, ns = cfg.state_shape
        gn = cfg.ssm_groups * ns
        slots, fresh, alive, n_real, valid = rows
        f32 = jnp.float32
        with jax.named_scope("attn_proj"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            z, xbc, dt = jnp.split(
                x @ lp["w_in"],
                (cfg.ssm_width, cfg.ssm_width + cfg.conv_width), axis=-1)
        in_place = self._updates_in_place(s, slots)
        with jax.named_scope("attn"), jax.named_scope("ssm"):
            at = si if slots is None else (si, slots)    # row i is slot i
            old_c = conv[at]
            old_s = None if in_place else state[at]
            tail = jnp.where(fresh[:, None, None], 0, old_c)
            y, new_c = linear_state.short_conv(xbc, lp["conv_w"], tail,
                                               n_real, lp["conv_b"])
            xs, bm, cm = jnp.split(
                jax.nn.silu(y), (cfg.ssm_width, cfg.ssm_width + gn), axis=-1)
            xs = xs.reshape(b, s, heads, p)
            bm = bm.reshape(b, s, cfg.ssm_groups, ns)
            cm = cm.reshape(b, s, cfg.ssm_groups, ns)
            step = jax.nn.softplus(dt.astype(f32) + lp["dt_bias"].astype(f32))
            step = jnp.where(valid[..., None], step, 0.0)    # padding
            a_head = -jnp.exp(lp["a_log"].astype(f32))
            with jax.named_scope("ssm_state"):
                if in_place:
                    # the zero start and the dead row's rule are the kernel's
                    o, state = ssm_state_update(
                        state, si, xs[:, 0], step[:, 0], a_head, bm[:, 0],
                        cm[:, 0], lp["d_skip"], fresh, alive)
                    o = o[:, None]
                else:
                    s0 = jnp.where(fresh[:, None, None, None], 0,
                                   old_s.astype(f32))
                    if s == 1:
                        o, new_s = ssm_state.ssd_step(
                            xs[:, 0], step[:, 0], a_head, bm[:, 0], cm[:, 0],
                            lp["d_skip"], s0)
                        o = o[:, None]
                    else:
                        o, new_s = ssm_state.ssd_scan(
                            xs, step, a_head, bm, cm, lp["d_skip"], s0,
                            cfg.ssm_chunk)
                    # a row with no real token keeps its slot bit for bit
                    new_s = jnp.where(alive[:, None, None, None],
                                      new_s.astype(state.dtype), old_s)
                    state = state.at[at].set(new_s)
            conv = conv.at[at].set(
                jnp.where(alive[:, None, None], new_c, old_c))
            # the gate inside the norm, one group over the whole width
            o = o.reshape(b, s, cfg.ssm_width) * jax.nn.silu(z.astype(f32))
            o = rms_norm(o, lp["out_norm"], cfg.rms_norm_eps).astype(h.dtype)
        with jax.named_scope("attn_out"):
            h = self._add(h, o @ lp["wo"])
        return h, state, conv

    def _linear(self, lp, si, h, state, conv, rows):
        """One delta-rule layer, the whole mixer under ``delta_rule``."""
        with jax.named_scope("delta_rule"):
            return self._delta_rule(lp, si, h, state, conv, rows)

    def _delta_rule(self, lp, si, h, state, conv, rows):
        """One linear layer on ``h`` [B, S, Dm]; ``state`` / ``conv`` are the
        whole leaves, ``si`` this layer's row of them.  ``rows`` = (slots or
        None, fresh [B], alive [B], n_real [B], valid [B, S])."""
        cfg = self.config
        b, s, _ = h.shape
        lh, ld = cfg.linear_heads, cfg.linear_head_dim
        slots, fresh, alive, n_real, valid = rows
        f32 = jnp.float32
        with jax.named_scope("attn_proj"):
            x = rms_norm(h, lp["attn_norm"], cfg.rms_norm_eps)
            qkv = jnp.concatenate(
                [x @ lp["wq"], x @ lp["wk"], x @ lp["wv"]], axis=-1)
            full = not cfg.gate_rank
            a = (x @ lp["w_decay"] if full
                 else (x @ lp["decay_down"]) @ lp["decay_up"])
            beta_logit = x @ lp["w_beta"]
            out_gate = (x @ lp["w_out_gate"] if full
                        else (x @ lp["gate_down"]) @ lp["gate_up"])
        in_place = self._updates_in_place(s, slots)
        with jax.named_scope("attn"), jax.named_scope("linear"):
            at = si if slots is None else (si, slots)    # row i is slot i
            old_c = conv[at]
            old_s = None if in_place else state[at]
            zero = fresh[:, None, None]
            tail = jnp.where(zero, 0, old_c)
            y, new_c = linear_state.short_conv(qkv, lp["conv_w"], tail,
                                               n_real)
            y = jax.nn.silu(y).reshape(b, s, 3, lh, ld)
            q, k, v = y[:, :, 0], y[:, :, 1], y[:, :, 2]

            def unit(t):
                return t * jax.lax.rsqrt(
                    jnp.sum(t * t, axis=-1, keepdims=True) + QK_NORM_EPS)

            q, k = unit(q) * ld ** -0.5, unit(k)
            a_head = jnp.exp(lp["a_log"].astype(f32))[:, None]
            if cfg.decay_lower_bound is None:
                g = -a_head * jax.nn.softplus(
                    a.astype(f32).reshape(b, s, lh, ld)
                    + lp["dt_bias"].astype(f32).reshape(lh, ld))
            else:
                g = cfg.decay_lower_bound * jax.nn.sigmoid(a_head * (
                    a.astype(f32).reshape(b, s, lh, ld)
                    + lp["dt_bias"].astype(f32).reshape(lh, ld)))
            beta = cfg.beta_scale * jax.nn.sigmoid(beta_logit.astype(f32))
            # padding: an identity step
            g = jnp.where(valid[..., None, None], g, 0.0)
            beta = jnp.where(valid[..., None], beta, 0.0)
            with jax.named_scope("linear_state"):
                if in_place:
                    # the zero start and the dead row's rule are the kernel's
                    o, state = state_update(
                        state, si, q[:, 0], k[:, 0], v[:, 0], g[:, 0],
                        beta[:, 0], fresh, alive)
                    o = o[:, None]
                else:
                    s0 = jnp.where(zero[..., None], 0, old_s.astype(f32))
                    if s == 1:
                        o, new_s = linear_state.delta_rule_step(
                            q[:, 0], k[:, 0], v[:, 0], g[:, 0], beta[:, 0],
                            s0)
                        o = o[:, None]
                    else:
                        o, new_s = linear_state.delta_rule_scan(
                            q, k, v, g, beta, s0)
                    # a row with no real token keeps its slot bit for bit
                    new_s = jnp.where(alive[:, None, None, None],
                                      new_s.astype(state.dtype), old_s)
            new_c = jnp.where(alive[:, None, None], new_c, old_c)
            if not in_place:
                state = state.at[at].set(new_s)
            conv = conv.at[at].set(new_c)
            if in_place:
                # the tail is written here, before the output projection:
                # left to float behind the experts, XLA carries the whole
                # 57 MB leaf through the layer scan in VMEM and moves it out
                # and back under every layer's first projection (+0.12 ms)
                conv, o = jax.lax.optimization_barrier((conv, o))
            o = rms_norm(o, lp["out_norm"], cfg.rms_norm_eps)
            o = (o * jax.nn.sigmoid(
                out_gate.astype(f32).reshape(b, s, lh, ld))).astype(h.dtype)
        with jax.named_scope("attn_out"):
            h = self._add(h, o.reshape(b, s, lh * ld) @ lp["wo"])
        return h, state, conv

    def _selective(self, lp, si, h, state, conv, rows):
        """One selective state-space layer; arguments as ``_linear``.  The
        whole mixer is under ``selective``; what a slot keeps (the
        convolution's tail and the state) is read, advanced and written
        under ``selective_step`` (one token a row) or ``selective_scan`` (a
        chunk): two program classes, two scopes."""
        cfg = self.config
        b, s, _ = h.shape
        ns, r, lanes = cfg.state_shape
        inner, rank = cfg.ssm_width, cfg.gate_rank
        slots, fresh, alive, n_real, valid = rows
        f32 = jnp.float32
        eps = cfg.rms_norm_eps
        with jax.named_scope("selective"):
            with jax.named_scope("attn_proj"):
                x = rms_norm(h, lp["attn_norm"], eps)
                xin, z = jnp.split(x @ lp["w_in"], 2, axis=-1)
            in_place = self._updates_in_place(s, slots)
            with jax.named_scope("attn"):
                at = si if slots is None else (si, slots)   # row i is slot i
                # what a slot keeps — the tail here, the state below — is
                # read and written under one scope, the mixer's products
                # between the two outside it
                kept = "selective_step" if s == 1 else "selective_scan"
                with jax.named_scope(kept):
                    old_c = conv[at]
                    tail = jnp.where(fresh[:, None, None], 0, old_c)
                    y, new_c = linear_state.short_conv(
                        xin, lp["conv_w"], tail, n_real, lp["conv_b"])
                    xs = jax.nn.silu(y).astype(h.dtype)
                delta, bm, cm = jnp.split(
                    xs @ lp["w_x"], (rank, rank + ns), axis=-1)
                delta = rms_norm(delta, lp["dt_norm"], eps)
                bm = rms_norm(bm, lp["b_norm"], eps)
                cm = rms_norm(cm, lp["c_norm"], eps)
                step = jax.nn.softplus(
                    jnp.matmul(delta, lp["w_dt"], preferred_element_type=f32)
                    + lp["dt_bias"].astype(f32))
                step = jnp.where(valid[..., None], step, 0.0)   # padding
                a = -jnp.exp(lp["a_log"].astype(f32)).reshape(ns, r, lanes)
                xf = xs.astype(f32).reshape(b, s, r, lanes)
                step = step.reshape(b, s, r, lanes)
                with jax.named_scope(kept):
                    if in_place and s == 1:
                        # the zero start and the dead row's rule are the
                        # kernel's
                        o, state = selective_state_update(
                            state, si, xf[:, 0], step[:, 0], a, bm[:, 0],
                            cm[:, 0], fresh, alive)
                        o = o[:, None]
                    elif in_place:
                        # a dead row's steps are all zero: an identity
                        o, state = selective_state_scan(
                            state, si,
                            jnp.arange(b) if slots is None else slots,
                            xf, step, a, bm, cm, fresh)
                    else:
                        old_s = state[at]
                        s0 = jnp.where(fresh[:, None, None, None], 0,
                                       old_s.astype(f32))
                        if s == 1:
                            o, new_s = selective_state.selective_step(
                                xf[:, 0], step[:, 0], a, bm[:, 0], cm[:, 0],
                                s0)
                            o = o[:, None]
                        else:
                            o, new_s = selective_state.selective_scan(
                                xf, step, a, bm, cm, s0)
                        # a row with no real token keeps its slot bit for bit
                        new_s = jnp.where(alive[:, None, None, None],
                                          new_s.astype(state.dtype), old_s)
                        state = state.at[at].set(new_s)
                    conv = conv.at[at].set(
                        jnp.where(alive[:, None, None], new_c, old_c))
                o = (o + lp["d_skip"].astype(f32).reshape(r, lanes) * xf
                     ).reshape(b, s, inner)
                o = (o * jax.nn.silu(z.astype(f32))).astype(h.dtype)
            with jax.named_scope("attn_out"):
                h = self._add(h, o @ lp["wo"])
        return h, state, conv

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
            cfg.linear_layers)
        valid = rows[-1]
        latent = cfg.attention == "mla"
        by_length = groups = None
        if latent:
            # the context the call reads: the static ``prefix_blocks``
            # cached blocks and its own (None: the table)
            bs = cache["latent"].shape[2]
            if prefix_blocks is not None:
                block_tables = block_tables[
                    :, :prefix_blocks + -(-s // bs)]
            if s == 1:
                groups = latent_cache.dense_decode_groups(
                    block_tables, positions, seq_lens, bs)
        elif s == 1:
            by_length = decode_rows_by_length(block_tables, seq_lens,
                                              positions)
        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(cfg.jax_dtype)
            if cfg.embedding_multiplier != 1.0:
                hidden = hidden * jnp.asarray(cfg.embedding_multiplier,
                                              hidden.dtype)

        kv, state, conv = (cache[self.pool_leaf], cache["state"],
                           cache["conv"])
        counts = cache["moe_counts"].at[0, 0, EXPERT_COUNTS:].add(counted)
        expert_keys = ("w_gate", "w_up", "w_down")
        recur = {"ssd": self._ssd, "selective": self._selective,
                 "delta": self._linear}[cfg.recurrence]

        def layer_step(kind: str):
            group = params["groups"][kind]
            sliced = {k: v for k, v in group.items() if k not in expert_keys}
            mixer, _, leading = kind.partition("_")
            routed = bool(cfg.n_routed_experts) and not leading
            shift = self.cache_shift[kind]

            def step(carry, at):
                h, kv, state, conv, counts = carry
                i, li = at
                ci = i + shift if shift else i
                lp = jax.tree.map(lambda a: a[i], sliced)
                if mixer == "gqa":
                    h, kv = self._gqa(lp, ci, h, kv, positions, block_tables,
                                      seq_lens, slot_idx, prefix_blocks,
                                      by_length)
                elif mixer == "mla":
                    h, kv = self._mla(lp, ci, h, kv, positions, block_tables,
                                      seq_lens, slot_idx, groups)
                else:
                    h, state, conv = recur(lp, ci, h, state, conv, rows)
                with jax.named_scope("mlp"):
                    if routed:
                        h, picked = self._experts(group, lp, i, h, valid)
                        counts = counts.at[li, 0, :EXPERT_COUNTS].add(picked)
                    else:
                        h = self._mlp(lp, h)
                return (h, kv, state, conv, counts), None
            return step

        # one step function a kind: the runs of a kind (G | L L L | G | L L L)
        # are scans of the same length over the same function, which
        # ``lax.scan`` then traces once between them
        steps = {kind: layer_step(kind)
                 for kind in {run.kind for run in self.runs}}
        for run in self.runs:
            n = jnp.arange(run.count, dtype=jnp.int32)
            (hidden, kv, state, conv, counts), _ = jax.lax.scan(
                steps[run.kind], (hidden, kv, state, conv, counts),
                (run.start + n, run.layer0 + n))
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return hidden, {self.pool_leaf: kv, "state": state, "conv": conv,
                        "state_pos": state_pos, "moe_counts": counts}

    def compute_logits(self, params, hidden):
        cfg = self.config
        with jax.named_scope("logits"):
            w = (params["embed"].T if cfg.tie_word_embeddings
                 else params["lm_head"])
            logits = jnp.matmul(hidden.astype(w.dtype), w,
                                preferred_element_type=jnp.float32)
            if cfg.logits_scaling != 1.0:
                logits = logits / cfg.logits_scaling
            return logits

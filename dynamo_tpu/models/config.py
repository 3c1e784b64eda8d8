"""Model architecture configuration (Llama family + MoE extensions).

Loadable from a HuggingFace ``config.json`` so checkpoints drop in directly
(reference analogue: ModelDeploymentCard builds from HF repo contents,
lib/llm/src/model_card/create.rs).
"""

from __future__ import annotations

import json
from dataclasses import dataclass, field
from pathlib import Path
from typing import Optional

import jax.numpy as jnp

# Llama-family architectures the unified decoder serves (reference parity:
# vLLM's model zoo; these cover the reference's example deployments —
# Llama/R1-Distill, Mistral, Mixtral MoE, Qwen2/3, Phi3, Gemma 1/2).
SUPPORTED_ARCHITECTURES = {
    "LlamaForCausalLM",
    "MistralForCausalLM",
    "MixtralForCausalLM",
    "Qwen2ForCausalLM",
    "Qwen3ForCausalLM",
    "Qwen3MoeForCausalLM",
    "Phi3ForCausalLM",
    "GemmaForCausalLM",
    "Gemma2ForCausalLM",
    "OuroForCausalLM",
    "MellumForCausalLM",
}

# architectures served by a model class of their own, not by the unified
# decoder this file configures: architecture -> (model_type, model class);
# models/loader.py and cli._load_any_checkpoint route a checkpoint directory by it
OTHER_ARCHITECTURES = {
    "JambaForCausalLM": (
        "jamba", "dynamo_tpu.models.hybrid_linear:HybridLinearModel"),
}

# kinds of attention layer a ``layer_types`` list may name
LAYER_KINDS = ("sliding_attention", "full_attention")
# rope_type of one kind's ``rope_parameters`` -> how LlamaModel builds it
ROPE_KINDS = ("default", "linear", "llama3", "yarn")


def layer_period(layer_types) -> tuple:
    """The repeating unit of a layer pattern: the shortest prefix P with
    ``layer_types[i] == P[i mod len(P)]`` for every layer.  The stack must be
    a whole number of them (``LlamaModel`` scans periods, each period's layers
    unrolled in its body): a last period cut short is refused."""
    types = tuple(layer_types)
    p = next(p for p in range(1, len(types) + 1)
             if all(t == types[i % p] for i, t in enumerate(types)))
    if len(types) % p:
        raise ValueError(
            f"layer_types: {len(types)} layers are not a whole number of the "
            f"period {types[:p]} (a ragged last period)")
    return types[:p]


@dataclass
class ModelConfig:
    """What ``LlamaModel`` needs to know of an architecture.

    Layers of more than one kind: a stack whose layers differ only in their
    attention's window and rope (``layer_types`` + ``rope_parameters``: window
    layers served as windows, by the windowed Pallas kernels on the TPU,
    beside full layers with a rope of their own, YaRN included) is one
    configuration of this class, because the stacked layer parameters keep
    one shape.  Still refused or served in full: a stack that mixes dense and
    expert MLPs (refused: two parameter shapes), Gemma2's and Qwen2/3's
    window interleaves (served with full attention and a warning, see
    ``sliding_window``), YaRN as a uniform ``rope_scaling`` (refused)."""

    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None  # default hidden_size // num_heads
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    # Qwen2-style QKV projection bias (o_proj stays bias-free)
    attention_bias: bool = False
    # Qwen3-style per-head RMSNorm on q and k (over head_dim, before RoPE)
    qk_norm: bool = False
    # Sliding-window size: attention masks keys older than `window`
    # positions — EXACT HF semantics (query p sees key j iff 0 <= p - j <
    # window).  With ``layer_types`` None every layer has it (Mistral/Phi3);
    # otherwise the layers of kind "sliding_attention" have it and the
    # "full_attention" ones do not.  The attention dispatch applies it only
    # when the static context bound can exceed the window
    # (ops/paged_attention.py): a deployment whose max_model_len fits inside
    # it traces full attention, which is exact there.  Two interleaves are
    # still NOT served as windows: from_hf_config nulls this field, with a
    # warning, for Gemma2 (local/global by layer parity) and for a Qwen2 /
    # Qwen3 config with max_window_layers != 0 (no configuration of the
    # benchmark is theirs).
    sliding_window: Optional[int] = None
    # The kind of every layer ("sliding_attention" | "full_attention"), a
    # whole number of equal periods (``layer_period``); None = one kind, the
    # stack as it always was.  ``rope_parameters`` then holds one rope per
    # kind, in HF's per-kind form ({"rope_type", "rope_theta", and for YaRN
    # "factor", "original_max_position_embeddings", "beta_fast",
    # "beta_slow", "attention_factor"}): LlamaModel turns q and k of a layer
    # by its kind's frequencies and multiplies cos and sin by its
    # attention factor.
    layer_types: Optional[tuple] = None
    rope_parameters: Optional[dict] = None
    # MoE (Mixtral-style); num_experts == 0 → dense MLP
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # renormalize top-k router probs (Mixtral always; Qwen3-MoE flag)
    norm_topk_prob: bool = True
    # --- Gemma-family deltas (all default to the Llama behavior) ---
    # MLP activation on the gate branch: "silu" (Llama) or "gelu_tanh"
    # (Gemma GeGLU)
    hidden_activation: str = "silu"
    # RMSNorm multiplies by (1 + weight): Gemma stores zero-centred scales
    rmsnorm_unit_offset: bool = False
    # multiply embeddings by sqrt(hidden_size) after lookup
    scale_embeddings: bool = False
    # Gemma2 sandwich norms: extra post-attention / post-MLP RMSNorms
    post_norms: bool = False
    # rope_scaling (HF config.json): {"rope_type": "llama3"|"linear", ...}
    # — Llama-3.1+ checkpoints REQUIRE llama3 frequency scaling; ignoring
    # it would silently corrupt long-context behavior
    rope_scaling: Optional[dict] = None
    # attention sm_scale = query_pre_attn_scalar**-0.5 (None = head_dim)
    query_pre_attn_scalar: Optional[float] = None
    # tanh softcaps: scores (Gemma2 attn_logit_softcapping) and final logits
    attn_logit_softcap: Optional[float] = None
    final_logit_softcap: Optional[float] = None
    # --- looped decoder (Ouro: HF total_ut_steps / early_exit_threshold) ---
    # The layer stack runs ``ut_steps`` times over the same weights; pass t
    # keeps a K/V cache of its own (cache layer t * num_layers + l), the
    # final norm closes every pass, and an exit gate chooses, per token,
    # which pass's state goes to the output head: the first pass at which
    # the cumulated exit probability reaches ``early_exit_threshold``,
    # else the last (at 1.0 always the last).  1 = an ordinary decoder.
    ut_steps: int = 1
    early_exit_threshold: float = 1.0
    # runtime
    dtype: str = "bfloat16"

    def __post_init__(self):
        if self.head_dim is None:
            self.head_dim = self.hidden_size // self.num_heads
        if self.layer_types is not None:
            self.layer_types = tuple(self.layer_types)
            if len(self.layer_types) != self.num_layers:
                raise ValueError(
                    f"layer_types names {len(self.layer_types)} layers, "
                    f"num_layers is {self.num_layers}")
            layer_period(self.layer_types)      # refuses a ragged period
            for kind in set(self.layer_types):
                if kind not in LAYER_KINDS:
                    raise ValueError(
                        f"layer_types: unknown kind {kind!r} "
                        f"(known: {LAYER_KINDS})")
                rope = (self.rope_parameters or {}).get(kind)
                if rope is None:
                    raise ValueError(
                        f"rope_parameters has no rope for layers of kind "
                        f"{kind!r}")
                rope_type = rope.get("rope_type", "default")
                if rope_type not in ROPE_KINDS:
                    raise ValueError(
                        f"rope_parameters[{kind!r}]: unknown rope kind "
                        f"{rope_type!r} (known: {ROPE_KINDS})")

    @property
    def jax_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def period(self) -> Optional[tuple]:
        """The kinds of one period of ``layer_types``; None for a stack of
        one kind."""
        return None if self.layer_types is None else layer_period(
            self.layer_types)

    @property
    def window_layers(self) -> int:
        """Layers whose attention is masked by ``sliding_window``."""
        if self.sliding_window is None:
            return 0
        if self.layer_types is None:
            return self.num_layers
        return self.layer_types.count("sliding_attention")

    @classmethod
    def tiny(cls, **kw) -> "ModelConfig":
        """A toy config for tests (fast CPU compile, exercises GQA)."""
        defaults = dict(
            vocab_size=256,
            hidden_size=64,
            intermediate_size=128,
            num_layers=2,
            num_heads=4,
            num_kv_heads=2,
            max_position_embeddings=512,
            dtype="float32",
        )
        defaults.update(kw)
        return cls(**defaults)

    @classmethod
    def from_hf_config(cls, path_or_dict, dtype: str = "bfloat16") -> "ModelConfig":
        """Build from a HuggingFace config.json (file, dir, or dict)."""
        if isinstance(path_or_dict, (str, Path)):
            p = Path(path_or_dict)
            if p.is_dir():
                p = p / "config.json"
            cfg = json.loads(p.read_text())
        else:
            cfg = dict(path_or_dict)
        archs = cfg.get("architectures") or []
        arch = archs[0] if archs else "LlamaForCausalLM"
        if arch in OTHER_ARCHITECTURES:
            raise ValueError(
                f"{arch} is served by {OTHER_ARCHITECTURES[arch][1]}, not by "
                "the unified decoder's ModelConfig")
        if arch not in SUPPORTED_ARCHITECTURES:
            raise ValueError(
                f"unsupported architecture {arch!r}; supported: "
                f"{sorted(SUPPORTED_ARCHITECTURES)}"
            )
        if not archs and cfg.get("model_type") == "mellum":
            arch = "MellumForCausalLM"
        gemma = arch in ("GemmaForCausalLM", "Gemma2ForCausalLM")
        ouro = arch == "OuroForCausalLM"
        mellum = arch == "MellumForCausalLM"
        # the Qwen3-MoE family's keys and conventions: num_experts,
        # moe_intermediate_size, norm_topk_prob (default false), a per-head
        # RMSNorm on q and k that no key announces
        qwen3_moe = arch == "Qwen3MoeForCausalLM" or mellum
        if qwen3_moe and (
            cfg.get("decoder_sparse_step", 1) != 1 or cfg.get("mlp_only_layers")
        ):
            # partially-sparse stacks interleave dense and MoE layers; the
            # scan-over-layers decoder assumes a uniform layer type
            raise ValueError(
                "Qwen3-MoE with decoder_sparse_step != 1 or mlp_only_layers "
                "is not supported (non-uniform layer stack)"
            )
        rs = cfg.get("rope_scaling")
        if rs:
            kind = rs.get("rope_type") or rs.get("type")
            if kind not in ("llama3", "linear", "default", None):
                # longrope/dynamic are not implemented, and YaRN is served
                # only as one kind's rope of ``rope_parameters`` (below) —
                # be loud, a silently-unscaled rope corrupts every long
                # prompt
                raise ValueError(
                    f"rope_scaling type {kind!r} not supported "
                    "(supported: llama3, linear)"
                )
        layer_types = ropes = None
        if mellum:
            # two kinds of attention layer, a rope each (__post_init__
            # checks kinds, period and ropes); every layer routes
            layer_types = tuple(cfg["layer_types"])
            ropes = {kind: dict(rope)
                     for kind, rope in cfg["rope_parameters"].items()
                     if isinstance(rope, dict)}
            dense = sorted(set(cfg.get("mlp_layer_types") or ()) - {"sparse"})
            if dense:
                raise ValueError(
                    f"mlp_layer_types: layers of kind {dense} are not "
                    "supported (every layer must be 'sparse': the stacked "
                    "layer parameters have one shape)")
        act = cfg.get("hidden_activation") or cfg.get("hidden_act") or "silu"
        # original Gemma-1 configs say "gelu" but the canonical weights were
        # trained with tanh-approx GELU (transformers maps it the same way);
        # unknown activations must fail loudly, not silently run SiLU
        act_map = {
            "silu": "silu",
            "gelu": "gelu_tanh",
            "gelu_pytorch_tanh": "gelu_tanh",
            "gelu_tanh": "gelu_tanh",
        }
        if act not in act_map:
            raise ValueError(
                f"unsupported hidden activation {act!r} for {arch}; "
                f"supported: {sorted(act_map)}"
            )
        sliding = cfg.get("sliding_window")
        if sliding and mellum:
            if not cfg.get("use_sliding_window", True):
                sliding = None      # every layer attends in full
            elif cfg.get("max_window_layers", 0) != 0:
                # layer_types says which layers have the window; a second
                # rule by depth has no published reading for this family
                raise ValueError(
                    f"{arch} with max_window_layers="
                    f"{cfg['max_window_layers']}: only 0 (layer_types alone "
                    "decides which layers have the window) is supported")
        elif sliding and arch in ("Qwen2ForCausalLM", "Qwen3ForCausalLM",
                                  "Qwen3MoeForCausalLM"):
            if not cfg.get("use_sliding_window"):
                # HF Qwen configs carry sliding_window but gate it behind
                # use_sliding_window (default False) — honoring the number
                # without the gate would wrongly window full-attention models
                sliding = None
            elif cfg.get("max_window_layers", None) != 0:
                import logging

                # HF windows only layers >= max_window_layers; a uniform
                # window over the scan-over-layers decoder would corrupt
                # the full-attention lower layers — same treatment as
                # Gemma2's interleave: full attention + a loud warning.
                # An ABSENT key means the HF default, which is nonzero
                # (e.g. 28 for Qwen2) — also non-uniform, NOT a uniform
                # window over all layers (ADVICE r5)
                logging.getLogger("dynamo_tpu.models").warning(
                    "%s use_sliding_window with max_window_layers=%s "
                    "(non-uniform layer windows): served with full "
                    "attention — outputs match HF only for contexts "
                    "within the window", arch,
                    cfg.get("max_window_layers", "absent (HF default)"),
                )
                sliding = None
        if sliding and arch == "Gemma2ForCausalLM":
            import logging

            # Gemma2 interleaves LOCAL and GLOBAL layers; a uniform window
            # over the scan-over-layers decoder would corrupt the global
            # layers, so Gemma2 keeps full attention — exact for contexts
            # within the window, divergent beyond it
            logging.getLogger("dynamo_tpu.models").warning(
                "%s sliding_window=%d: interleaved local/global layers are "
                "served with full attention — outputs match HF only for "
                "contexts within the window", arch, sliding,
            )
            sliding = None
        return cls(
            vocab_size=cfg["vocab_size"],
            hidden_size=cfg["hidden_size"],
            # MoE experts use their own width (Qwen3-MoE moe_intermediate_size)
            intermediate_size=(
                cfg["moe_intermediate_size"] if qwen3_moe
                else cfg["intermediate_size"]
            ),
            num_layers=cfg["num_hidden_layers"],
            num_heads=cfg["num_attention_heads"],
            num_kv_heads=cfg.get("num_key_value_heads", cfg["num_attention_heads"]),
            head_dim=cfg.get("head_dim"),
            # one number for the code that knows one rope; a model with
            # rope_parameters turns by its kinds' own
            rope_theta=(ropes.get(layer_types[0], {}).get("rope_theta", 1e4)
                        if ropes else cfg.get("rope_theta", 10000.0)),
            rms_norm_eps=cfg.get("rms_norm_eps", 1e-5),
            max_position_embeddings=cfg.get("max_position_embeddings", 4096),
            # HF Gemma checkpoints tie embeddings and omit the flag
            tie_word_embeddings=cfg.get("tie_word_embeddings", gemma),
            # HF Qwen2 attention always carries QKV bias; Llama exposes an
            # explicit attention_bias flag (default False)
            attention_bias=cfg.get("attention_bias", arch == "Qwen2ForCausalLM"),
            qk_norm=arch in ("Qwen3ForCausalLM", "Qwen3MoeForCausalLM",
                             "MellumForCausalLM"),
            sliding_window=sliding,
            layer_types=layer_types,
            rope_parameters=ropes,
            num_experts=cfg.get("num_local_experts",
                                cfg.get("num_experts", 0) if qwen3_moe else 0),
            num_experts_per_tok=cfg.get("num_experts_per_tok", 2),
            # HF default differs by family: Mixtral always renormalizes,
            # Qwen3MoeConfig defaults the flag to False
            norm_topk_prob=bool(cfg.get("norm_topk_prob", not qwen3_moe)),
            rope_scaling=dict(rs) if rs else None,
            hidden_activation=act_map[act],
            rmsnorm_unit_offset=gemma,
            scale_embeddings=gemma,
            # Ouro's input_layernorm_2 / post_attention_layernorm_2 norm
            # the two residual branches, as Gemma2's sandwich norms do
            post_norms=arch == "Gemma2ForCausalLM" or ouro,
            query_pre_attn_scalar=cfg.get("query_pre_attn_scalar"),
            attn_logit_softcap=cfg.get("attn_logit_softcapping"),
            final_logit_softcap=cfg.get("final_logit_softcapping"),
            ut_steps=int(cfg.get("total_ut_steps", 1)) if ouro else 1,
            early_exit_threshold=float(cfg.get("early_exit_threshold", 1.0)),
            dtype=dtype,
        )

"""HuggingFace checkpoint → params pytree loader.

Maps the HF Llama/Mixtral weight naming onto the stacked-layer layout used
by LlamaModel (weights transposed to [in, out] and stacked on a leading L
axis for lax.scan).  Loads from a local HF model directory (safetensors) or
from an in-memory state_dict (tests use a tiny random transformers model).

Reference analogue: the reference never loads weights itself (vLLM does);
its closest piece is ModelDeploymentCard creation from an HF repo
(lib/llm/src/model_card/create.rs).  Here loading is first-class.
"""

from __future__ import annotations

import json
from pathlib import Path
from typing import Any, Callable, Mapping

import jax.numpy as jnp
import numpy as np

from dynamo_tpu.models.config import ModelConfig

__all__ = ["load_params_from_state_dict", "load_params_from_dir", "load_model_dir",
           "is_jamba_dir", "load_jamba_dir", "jamba_params_from_state_dict"]


def _np(x) -> np.ndarray:
    if hasattr(x, "detach"):  # torch tensor
        x = x.detach().to("cpu").float().numpy()
    return np.asarray(x)


def load_params_from_state_dict(
    cfg: ModelConfig, state: Mapping[str, Any], dtype=None
) -> dict:
    """Convert an HF-style state dict (torch tensors or ndarrays) to params."""
    dt = dtype or cfg.jax_dtype
    L = cfg.num_layers

    def get(name: str) -> np.ndarray:
        return _np(state[name])

    def stack(fmt: str, transpose: bool = True) -> jnp.ndarray:
        ws = []
        for i in range(L):
            w = get(fmt.format(i=i))
            ws.append(w.T if transpose else w)
        return jnp.asarray(np.stack(ws), dtype=dt)

    # Phi3 fuses qkv_proj and gate_up_proj into single matrices
    fused_qkv = "model.layers.0.self_attn.qkv_proj.weight" in state
    fused_gate_up = "model.layers.0.mlp.gate_up_proj.weight" in state
    # Ouro names its two extra (sandwich) norms after the norm they follow
    ouro_norms = "model.layers.0.input_layernorm_2.weight" in state

    def stack_fused(fmt: str, sizes: list[int]) -> list[jnp.ndarray]:
        """One read of each layer's fused [sum(sizes), in] matrix, split
        into len(sizes) stacked parts (the lazy safetensors mapping
        re-reads the whole tensor per get(), so per-part reads would cost
        len(sizes)x the host I/O at load)."""
        parts: list[list[np.ndarray]] = [[] for _ in sizes]
        for i in range(L):
            w = get(fmt.format(i=i))
            off = 0
            for j, sz in enumerate(sizes):
                parts[j].append(w[off:off + sz].T)
                off += sz
        return [jnp.asarray(np.stack(p), dtype=dt) for p in parts]

    dh = cfg.head_dim
    if fused_qkv:
        wq, wk, wv = stack_fused(
            "model.layers.{i}.self_attn.qkv_proj.weight",
            [cfg.num_heads * dh, cfg.num_kv_heads * dh, cfg.num_kv_heads * dh],
        )
    else:
        wq = stack("model.layers.{i}.self_attn.q_proj.weight")
        wk = stack("model.layers.{i}.self_attn.k_proj.weight")
        wv = stack("model.layers.{i}.self_attn.v_proj.weight")
    layers = {
        "attn_norm": stack("model.layers.{i}.input_layernorm.weight", transpose=False),
        "wq": wq,
        "wk": wk,
        "wv": wv,
        "wo": stack("model.layers.{i}.self_attn.o_proj.weight"),
        # Gemma2 renames the pre-MLP norm and adds sandwich norms; in the
        # Llama family (and Ouro) post_attention_layernorm IS the pre-MLP
        # norm
        "mlp_norm": stack(
            "model.layers.{i}.pre_feedforward_layernorm.weight"
            if cfg.post_norms and not ouro_norms
            else "model.layers.{i}.post_attention_layernorm.weight",
            transpose=False,
        ),
    }
    if ouro_norms:
        # Ouro's sandwich: input_layernorm_2 norms the attention branch,
        # post_attention_layernorm_2 the FFN branch (names assumed from the
        # layer's equations: no checkpoint was at hand to read)
        layers.update(
            post_attn_norm=stack(
                "model.layers.{i}.input_layernorm_2.weight", transpose=False),
            post_mlp_norm=stack(
                "model.layers.{i}.post_attention_layernorm_2.weight",
                transpose=False),
        )
    elif cfg.post_norms:
        layers.update(
            post_attn_norm=stack(
                "model.layers.{i}.post_attention_layernorm.weight",
                transpose=False,
            ),
            post_mlp_norm=stack(
                "model.layers.{i}.post_feedforward_layernorm.weight",
                transpose=False,
            ),
        )
    if cfg.attention_bias:
        layers.update(
            bq=stack("model.layers.{i}.self_attn.q_proj.bias", transpose=False),
            bk=stack("model.layers.{i}.self_attn.k_proj.bias", transpose=False),
            bv=stack("model.layers.{i}.self_attn.v_proj.bias", transpose=False),
        )
    if cfg.qk_norm:  # Qwen3 per-head norms
        layers.update(
            q_norm=stack("model.layers.{i}.self_attn.q_norm.weight",
                         transpose=False),
            k_norm=stack("model.layers.{i}.self_attn.k_norm.weight",
                         transpose=False),
        )
    if cfg.is_moe:
        e = cfg.num_experts

        def stack_experts(fmt: str) -> jnp.ndarray:
            return jnp.asarray(
                np.stack(
                    [
                        np.stack([get(fmt.format(i=i, e=j)).T for j in range(e)])
                        for i in range(L)
                    ]
                ),
                dtype=dt,
            )

        if "model.layers.0.mlp.gate.weight" in state:  # Qwen3-MoE naming
            layers.update(
                router=stack("model.layers.{i}.mlp.gate.weight"),
                w_gate=stack_experts("model.layers.{i}.mlp.experts.{e}.gate_proj.weight"),
                w_down=stack_experts("model.layers.{i}.mlp.experts.{e}.down_proj.weight"),
                w_up=stack_experts("model.layers.{i}.mlp.experts.{e}.up_proj.weight"),
            )
        else:  # Mixtral naming
            layers.update(
                router=stack("model.layers.{i}.block_sparse_moe.gate.weight"),
                w_gate=stack_experts("model.layers.{i}.block_sparse_moe.experts.{e}.w1.weight"),
                w_down=stack_experts("model.layers.{i}.block_sparse_moe.experts.{e}.w2.weight"),
                w_up=stack_experts("model.layers.{i}.block_sparse_moe.experts.{e}.w3.weight"),
            )
    else:
        if fused_gate_up:
            w_gate, w_up = stack_fused(
                "model.layers.{i}.mlp.gate_up_proj.weight",
                [cfg.intermediate_size, cfg.intermediate_size],
            )
        else:
            w_gate = stack("model.layers.{i}.mlp.gate_proj.weight")
            w_up = stack("model.layers.{i}.mlp.up_proj.weight")
        layers.update(
            w_gate=w_gate,
            w_up=w_up,
            w_down=stack("model.layers.{i}.mlp.down_proj.weight"),
        )

    params = {
        "embed": jnp.asarray(get("model.embed_tokens.weight"), dtype=dt),
        "layers": layers,
        "final_norm": jnp.asarray(get("model.norm.weight"), dtype=dt),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = jnp.asarray(get("lm_head.weight").T, dtype=dt)
    if cfg.ut_steps > 1:  # looped decoder: the exit gate, Linear(Dm -> 1)
        params["exit_gate_w"] = jnp.asarray(
            get("model.early_exit_gate.weight").reshape(-1), dtype=dt)
        params["exit_gate_b"] = jnp.asarray(
            get("model.early_exit_gate.bias").reshape(()), dtype=dt)
    return params


class _LazySafetensors(Mapping):
    """Mapping over all *.safetensors files in a dir, loading tensors on
    demand so 70B checkpoints never fully materialise in host RAM at once."""

    def __init__(self, model_dir: Path):
        from safetensors import safe_open

        self._open: Callable = safe_open
        self._index: dict[str, Path] = {}
        files = sorted(model_dir.glob("*.safetensors"))
        if not files:
            raise FileNotFoundError(f"no safetensors files in {model_dir}")
        index_file = model_dir / "model.safetensors.index.json"
        if index_file.exists():
            weight_map = json.loads(index_file.read_text())["weight_map"]
            for name, fname in weight_map.items():
                self._index[name] = model_dir / fname
        else:
            for f in files:
                with safe_open(f, framework="np") as sf:
                    for name in sf.keys():
                        self._index[name] = f

    def __getitem__(self, name: str) -> np.ndarray:
        with self._open(self._index[name], framework="np") as sf:
            return sf.get_tensor(name)

    def __iter__(self):
        return iter(self._index)

    def __len__(self):
        return len(self._index)


def load_params_from_dir(cfg: ModelConfig, model_dir: str | Path, dtype=None) -> dict:
    return load_params_from_state_dict(cfg, _LazySafetensors(Path(model_dir)), dtype)


def load_model_dir(model_dir: str | Path, dtype: str = "bfloat16"):
    """Convenience: (ModelConfig, params) from a local HF model directory."""
    cfg = ModelConfig.from_hf_config(model_dir, dtype=dtype)
    return cfg, load_params_from_dir(cfg, model_dir)


def is_deepseek_dir(model_dir: str | Path) -> bool:
    """True when config.json declares a DeepSeek architecture (the MLA
    family loads through models/deepseek.py, not the unified decoder)."""
    import json as _json

    p = Path(model_dir) / "config.json"
    if not p.exists():
        return False
    try:
        archs = _json.loads(p.read_text()).get("architectures") or []
    except Exception:
        return False
    return any(str(a).startswith("Deepseek") for a in archs)


def load_deepseek_dir(model_dir: str | Path, dtype: str = "bfloat16"):
    """(DeepseekConfig, params) from a DeepSeek-V2 HF directory —
    safetensors stream lazily through the same shard mapping."""
    import json as _json

    from dynamo_tpu.models.deepseek import DeepseekConfig, convert_hf_state_dict

    cfg = DeepseekConfig.from_hf(
        _json.loads((Path(model_dir) / "config.json").read_text())
    )
    cfg.dtype = dtype
    return cfg, convert_hf_state_dict(_LazySafetensors(Path(model_dir)), cfg)


def is_jamba_dir(model_dir: str | Path) -> bool:
    """True when config.json declares ``JambaForCausalLM`` / ``jamba`` (the
    Mamba-1 hybrid loads through models/hybrid_linear.py, not the unified
    decoder)."""
    p = Path(model_dir) / "config.json"
    if not p.exists():
        return False
    try:
        cfg = json.loads(p.read_text())
    except Exception:
        return False
    from dynamo_tpu.models.config import OTHER_ARCHITECTURES

    model_type = OTHER_ARCHITECTURES["JambaForCausalLM"][0]
    return ("JambaForCausalLM" in (cfg.get("architectures") or [])
            or cfg.get("model_type") == model_type)


def jamba_params_from_state_dict(cfg, state: Mapping[str, Any]) -> dict:
    """The published ``JambaForCausalLM`` names (modeling_jamba.py) -> the
    stacked groups of models/hybrid_linear.py: ``gqa`` the attending layers,
    ``linear`` the Mamba layers, each over its layers in order, weights
    turned to ``x @ W``; ``A_log`` [I, N] turned to [N, I] as the state
    lies, and it, ``D`` and ``dt_proj.bias`` kept float32."""
    dt = cfg.jax_dtype

    def stack(layers, name: str, turn: bool = True, dtype=dt) -> jnp.ndarray:
        ws = [_np(state[f"model.layers.{i}.{name}"]) for i in layers]
        return jnp.asarray(np.stack([w.T if turn else w for w in ws]), dtype)

    def shared(layers) -> dict:
        return {
            "attn_norm": stack(layers, "input_layernorm.weight", False),
            "mlp_norm": stack(layers, "pre_ff_layernorm.weight", False),
            "mlp_gate": stack(layers, "feed_forward.gate_proj.weight"),
            "mlp_up": stack(layers, "feed_forward.up_proj.weight"),
            "mlp_down": stack(layers, "feed_forward.down_proj.weight"),
        }

    f32 = jnp.float32

    def gqa(layers) -> dict:
        return {
            "wq": stack(layers, "self_attn.q_proj.weight"),
            "wk": stack(layers, "self_attn.k_proj.weight"),
            "wv": stack(layers, "self_attn.v_proj.weight"),
            "wo": stack(layers, "self_attn.o_proj.weight"),
            **shared(layers)}

    def mamba(layers) -> dict:
        return {
            "w_in": stack(layers, "mamba.in_proj.weight"),
            "conv_w": stack(layers, "mamba.conv1d.weight", False)[:, :, 0],
            "conv_b": stack(layers, "mamba.conv1d.bias", False),
            "w_x": stack(layers, "mamba.x_proj.weight"),
            "dt_norm": stack(layers, "mamba.dt_layernorm.weight", False),
            "b_norm": stack(layers, "mamba.b_layernorm.weight", False),
            "c_norm": stack(layers, "mamba.c_layernorm.weight", False),
            "w_dt": stack(layers, "mamba.dt_proj.weight"),
            "dt_bias": stack(layers, "mamba.dt_proj.bias", False, f32),
            "a_log": stack(layers, "mamba.A_log", True, f32),
            "d_skip": stack(layers, "mamba.D", False, f32),
            "wo": stack(layers, "mamba.out_proj.weight"),
            **shared(layers)}

    kinds = {"gqa": (gqa, list(cfg.gqa_layers)),
             "linear": (mamba, [i for i in range(cfg.num_layers)
                                if i not in cfg.gqa_layers])}
    out = {
        "embed": jnp.asarray(_np(state["model.embed_tokens.weight"]), dt),
        "groups": {kind: make(layers)
                   for kind, (make, layers) in kinds.items() if layers},
        "final_norm": jnp.asarray(
            _np(state["model.final_layernorm.weight"]), dt),
    }
    if not cfg.tie_word_embeddings:
        out["lm_head"] = jnp.asarray(_np(state["lm_head.weight"]).T, dt)
    return out


def load_jamba_dir(model_dir: str | Path, dtype: str = "bfloat16"):
    """(HybridLinearConfig, params) from a ``jamba`` HF directory."""
    from dynamo_tpu.models.hybrid_linear import HybridLinearConfig

    cfg = HybridLinearConfig.from_hf_config(
        json.loads((Path(model_dir) / "config.json").read_text()), dtype=dtype)
    return cfg, jamba_params_from_state_dict(
        cfg, _LazySafetensors(Path(model_dir)))

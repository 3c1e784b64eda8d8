"""cellbench/reference/jamba_hybrid.py against ``transformers``'
``JambaForCausalLM`` (``use_mamba_kernels=False``: ``slow_forward``, eager
attention, float32) on the same seeded weights, carried over by the loader's
own map of the published names (models/loader.py): this ties the benchmark's
reference, and the loader, to the published code."""

import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import HybridLinearConfig
from dynamo_tpu.models.loader import jamba_params_from_state_dict
from hybrid_linear_tiny import tokens_of
from jamba_tiny import TINY, ref

torch = pytest.importorskip("torch")
# float32 on both sides, another order of the sums (a fused convolution, the
# head in one product): 2e-5 at logits of order 1, measured 3e-6
TOLERANCE = 2e-5


def published(cfg: dict, seed: int = 0):
    from transformers import JambaConfig, JambaForCausalLM

    torch.manual_seed(seed)
    hf_cfg = JambaConfig(**{k: v for k, v in cfg.items()
                            if k != "model_type"},
                         attn_implementation="eager")
    model = JambaForCausalLM(hf_cfg).eval().float()
    with torch.no_grad():
        # the published initialisation leaves dt_proj.bias and the inner
        # norms at values under which a wrong order of the three norms, or a
        # bias left out, would not show: draw them
        for name, p in model.named_parameters():
            if name.endswith(("layernorm.weight", "dt_proj.bias", ".D",
                              "conv1d.bias")):
                p.copy_(torch.randn_like(p) * 0.5 + (1.0 if "norm" in name else 0.0))
    return model


@pytest.mark.parametrize("heads", [
    {}, {"num_attention_heads": 20, "hidden_size": 160}],
    ids=["4-to-1", "20-to-1"])
def test_the_reference_is_the_published_model(heads):
    cfg = {**TINY, **heads}
    model = published(cfg)
    toks = tokens_of(48, 3)
    with torch.no_grad():
        logits = model(torch.tensor([toks])).logits[0]
    want = np.asarray(torch.log_softmax(logits, dim=-1))
    mcfg = HybridLinearConfig.from_hf_config(cfg, dtype="float32")
    params = jamba_params_from_state_dict(mcfg, model.state_dict())
    got = np.asarray(ref.make_forward(cfg)(
        params, jnp.asarray(toks, jnp.int32), jnp.arange(48)))
    assert np.abs(got - want).max() < TOLERANCE
    # the layers are where the published property puts them
    assert model.config.layers_block_type.count("attention") == 1
    assert model.config.layers_block_type.index("attention") == 7
    assert mcfg.gqa_layers == (7,)
    assert set(model.config.layers_num_experts) == {1}


def test_the_loaded_tree_is_the_seeded_tree_in_names_and_shapes():
    """What the loader builds from the published names has the leaves, shapes
    and types of ``init_params``: nothing is assumed about a name."""
    import jax

    from dynamo_tpu.models.hybrid_linear import HybridLinearModel

    model = published(TINY)
    mcfg = HybridLinearConfig.from_hf_config(TINY, dtype="float32")
    loaded = jamba_params_from_state_dict(mcfg, model.state_dict())
    seeded = jax.eval_shape(
        HybridLinearModel(mcfg).init_params, jax.random.PRNGKey(0))
    shape = lambda tree: jax.tree.map(lambda a: (a.shape, str(a.dtype)), tree)
    assert shape(loaded) == shape(seeded)
    # every published tensor went somewhere (the tied head is the embedding)
    used = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(loaded))
    assert used == sum(p.numel() for p in model.parameters())

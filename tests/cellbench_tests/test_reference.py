"""Both plain references against the repo's models at tiny size in float32."""

import jax
import jax.numpy as jnp
import pytest

from cellbench import server, spec
from roots import HERE, REPO


def system_logprobs(model, params, tokens):
    t = len(tokens)
    cache = model.init_kv_cache(8, 16)
    pos = jnp.arange(t, dtype=jnp.int32)[None]
    hidden, _ = model.forward(
        params, tokens[None], pos, cache, jnp.arange(4, dtype=jnp.int32)[None],
        jnp.array([t], jnp.int32), pos)
    return jax.nn.log_softmax(model.compute_logits(params, hidden)[0], -1)


@pytest.mark.parametrize("name", ["tiny-dense", "tiny-moe"])
def test_reference_matches_the_model(name):
    config = spec.read_json(HERE / "data" / f"{name}.json")
    model = server.resolve(config["model_class"])(server.model_config(config))
    params = model.init_params(server.seed_key(2**31 + 3))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (40,), 1, 256)
    ref = spec.load_module(REPO, "reference", config["reference"])
    at = jnp.arange(40)
    got = jax.jit(ref.make_forward(config))(params, tokens, at)
    want = system_logprobs(model, params, tokens)
    assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_references_import_nothing_from_the_models():
    for name in ("dense_gqa", "qwen3_moe"):
        text = (REPO / "cellbench" / "reference" / f"{name}.py").read_text()
        assert "dynamo_tpu" not in text.replace("dynamo-tpu", "")

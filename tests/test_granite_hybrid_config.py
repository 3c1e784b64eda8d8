"""What the seed and the configuration give the state-space layers: a decay
that remembers, tied embeddings behind ``logits_scaling``, and the tied
switch for the delta-rule model, which refused it by name until the model
class had a second user."""

import jax
import jax.numpy as jnp
import numpy as np

from granite_hybrid_tiny import TINY, build
import hybrid_linear_tiny as delta


def test_the_seeded_decay_remembers():
    """The per-token decay a = exp(Δ·A) has its median over tokens in
    [0.9, 0.999] for more than a quarter of the heads (a state that forgets
    in three tokens cannot show a broken chunk carry) and few heads forget
    at once, at the published widths of the decay's parameters: 128 heads."""
    cfg = dict(TINY, hidden_size=256, mamba_n_heads=128, mamba_d_head=4,
               num_hidden_layers=2, layer_types=["mamba", "attention"])
    model, params = build(cfg)
    lp = jax.tree.map(lambda a: a[0], params["groups"]["linear"])
    x = jax.random.normal(jax.random.PRNGKey(9), (256, 256), jnp.float32)
    dt = (x @ lp["w_in"])[:, -128:]
    assert 0.4 < float(jnp.std(dt)) < 0.6          # DECAY_PROJ_STD
    step = jax.nn.softplus(dt + lp["dt_bias"])
    a = np.exp(np.asarray(step * -jnp.exp(lp["a_log"])))
    median = np.median(a, axis=0)
    assert np.mean((median >= 0.9) & (median <= 0.999)) > 0.25
    assert np.mean(median < 0.5) < 0.2
    # the token moves the decay: it is a gate, not a constant
    assert np.std(np.log(a), axis=0).mean() > 0.01 * -np.mean(np.log(a))
    assert np.array_equal(np.asarray(lp["d_skip"]), np.ones(128, np.float32))
    assert 0.2 < float(jnp.std(lp["conv_b"])) < 0.35     # U(-1/2, 1/2)


def test_tied_logits_are_the_embedding_over_the_scaling():
    model, params = build()
    hidden = jax.random.normal(jax.random.PRNGKey(3), (2, 5, 64), jnp.float32)
    got = model.compute_logits(params, hidden)
    want = np.asarray(hidden) @ np.asarray(params["embed"]).T / 16
    assert got.shape == (2, 5, 128) and got.dtype == jnp.float32
    assert np.abs(np.asarray(got) - want).max() < 1e-5


def test_the_delta_rule_model_takes_tied_embeddings_too():
    """``tie_word_embeddings`` is the model class's switch, not a family's:
    the delta-rule toy with it set has no ``lm_head`` and answers as the
    untied toy whose head is its embedding turned round."""
    tied, tied_params = delta.build(dict(delta.TINY, tie_word_embeddings=True))
    assert "lm_head" not in tied_params
    untied, params = delta.build()
    params = dict(params, lm_head=params["embed"].T)
    hidden = jax.random.normal(jax.random.PRNGKey(4), (3, 64), jnp.float32)
    assert np.array_equal(
        np.asarray(tied.compute_logits(dict(tied_params,
                                            embed=params["embed"]), hidden)),
        np.asarray(untied.compute_logits(params, hidden)))

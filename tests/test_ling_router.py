"""The group-limited router (``models/deepseek.py::moe_route``, ``noaux_tc``
with ``n_group`` > 1) against ``transformers``' ``DeepseekV3TopkRouter`` and
the benchmark's reference, at the published geometry (512 experts, 8 groups,
4 kept, top-8), and the shares of an expert layer against the uncut layer."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.deepseek import moe_route
from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from ling_tiny import TINY, build, ref

PUBLISHED = dict(TINY, hidden_size=96, num_experts=128, num_experts_per_tok=8,
                 n_group=8, topk_group=4, expert_parallel={
                     "chips": 4, "router_experts": 512, "first_expert": 0})


def routed_weights(topi, weights, total: int) -> np.ndarray:
    """[T, E]: each token's weight on each expert, zero off its picks."""
    out = np.zeros((topi.shape[0], total), np.float32)
    np.put_along_axis(out, np.asarray(topi), np.asarray(weights), axis=1)
    return out


def test_the_grouped_router_is_transformers_deepseek_v3_router():
    """Same weights, same bias, same tokens: the same eight experts a token
    and the same weights as ``DeepseekV3TopkRouter`` (torch, CPU) — the bias
    large enough to move groups, so that the choice-only bias, the sum of a
    group's two best and the zero (not -inf) of a dropped group all count."""
    torch = pytest.importorskip("torch")
    from transformers import DeepseekV3Config
    from transformers.models.deepseek_v3.modeling_deepseek_v3 import (
        DeepseekV3TopkRouter,
    )

    cfg = HybridLinearConfig.from_hf_config(PUBLISHED, dtype="float32")
    assert (cfg.router_experts, cfg.n_group, cfg.topk_group,
            cfg.num_experts_per_tok, cfg.routed_scaling_factor) == (
        512, 8, 4, 8, 2.5)
    rng = np.random.default_rng(0)
    w = rng.normal(size=(96, 512)).astype(np.float32) / np.sqrt(96)
    bias = (0.05 * rng.normal(size=512)).astype(np.float32)
    x = rng.normal(size=(200, 96)).astype(np.float32)
    theirs = DeepseekV3TopkRouter(DeepseekV3Config(
        hidden_size=96, n_routed_experts=512, num_experts_per_tok=8,
        n_group=8, topk_group=4, norm_topk_prob=True,
        routed_scaling_factor=2.5))
    with torch.no_grad():
        theirs.weight.copy_(torch.from_numpy(w.T.copy()))
        theirs.e_score_correction_bias.copy_(torch.from_numpy(bias))
        t_idx, t_w = theirs(torch.from_numpy(x))
    weights, topi = moe_route(cfg, jnp.asarray(w), jnp.asarray(x),
                              jnp.asarray(bias))
    ours = routed_weights(topi, weights, 512)
    want = routed_weights(t_idx.numpy(), t_w.numpy(), 512)
    assert ((ours > 0) == (want > 0)).all()
    np.testing.assert_allclose(ours, want, rtol=1e-5, atol=1e-7)
    # every pick lies in one of a token's four kept groups
    assert all(len(set(row // 64)) <= 4 for row in np.asarray(topi))
    # ... and without the groups the choice would differ: they bind
    flat = HybridLinearConfig.from_hf_config(
        dict(PUBLISHED, n_group=1, topk_group=1), dtype="float32")
    _, free = moe_route(flat, jnp.asarray(w), jnp.asarray(x),
                        jnp.asarray(bias))
    assert (np.sort(np.asarray(free)) != np.sort(np.asarray(topi))).any()


def test_the_reference_routes_as_the_program_does():
    """``ling_hybrid_mla.gates`` (its own lines, no import from the program)
    gives the weights ``moe_route`` gives, on the model's seeded router."""
    model, params = build(PUBLISHED)
    lp = jax.tree.map(lambda a: a[1], params["groups"]["linear"])
    x = jax.random.normal(jax.random.PRNGKey(3), (64, 96), jnp.float32)
    weights, topi = moe_route(model.config, lp["router"], x,
                              lp["router_bias"])
    with jax.default_matmul_precision("highest"):
        theirs = np.asarray(ref.gates(x, lp, PUBLISHED))
    np.testing.assert_allclose(routed_weights(topi, weights, 512), theirs,
                               rtol=1e-5, atol=1e-7)


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of two routing groups each (groups 0-1, 2-3, 4-5, 6-7 of
    eight): the parts their held experts give, with the shared expert
    counted once, are the layer over all the experts — in the reference and
    in the program's own ``_experts``."""
    base = dict(TINY, num_experts_per_tok=4, n_group=8, topk_group=4)
    whole_cfg = dict(base, num_experts=32, expert_parallel={
        "chips": 1, "router_experts": 32, "first_expert": 0})
    model, params = build(whole_cfg)
    group = params["groups"]["linear"]
    lp = jax.tree.map(lambda a: a[1], group)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    routed_all, shared_all = ref.make_layer(whole_cfg)(lp, x[0])
    total = np.zeros_like(np.asarray(routed_all))
    picks = 0
    for first in (0, 8, 16, 24):
        cfg = dict(base, num_experts=8, expert_parallel={
            "chips": 4, "router_experts": 32, "first_expert": first})
        share = jax.tree.map(lambda a: a, lp)
        stacks = {k: group[k][:, first:first + 8]
                  for k in ("w_gate", "w_up", "w_down")}
        share.update({k: v[1] for k, v in stacks.items()})
        routed, shared = ref.make_layer(cfg)(share, x[0])
        assert np.abs(shared - shared_all).max() == 0
        total += np.asarray(routed)
        part = HybridLinearModel(
            HybridLinearConfig.from_hf_config(cfg, dtype="float32"))
        y, counted = part._experts({**group, **stacks}, share, 1, x, valid)
        normed = ref.rms_norm(x[0], lp["mlp_norm"], TINY["rms_norm_eps"])
        r2, s2 = ref.make_layer(cfg)(share, normed)
        assert np.abs(np.asarray(y[0] - x[0]) - np.asarray(r2 + s2)).max() < 1e-4
        assert int(counted[0]) == 24 * 4
        picks += int(counted[1])
    assert picks == 24 * 4                  # every pick fell on one share
    assert np.abs(total - np.asarray(routed_all)).max() < 1e-4


def test_the_seeded_value_path_hands_the_router_no_common_direction():
    """SiLU gives every v the same positive mean; the state sums it
    coherently and W_o would hand it to every token as one vector, which
    every token's router then reads alike: the experts held are touched by
    the seed's luck, and the step's time follows the seed.  With
    ``seed_without_common_mode`` (the ``ling_hybrid_mla`` reader sets it) the
    value taps have unit norm a channel and W_o zero mean over a head's
    channels: what 64 independent sequences' mixers put out has no common
    part beyond sampling (1/64); without it several times that is common
    (8% at the toy's 16 channels a head, 41% at the published 128)."""
    import dataclasses

    from dynamo_tpu.ops import linear_state

    model, params = build()
    cfg = model.config
    assert cfg.seed_without_common_mode
    plain = HybridLinearModel(dataclasses.replace(
        cfg, seed_without_common_mode=False))
    b, s, dm = 64, 24, cfg.hidden_size
    h = jax.random.normal(jax.random.PRNGKey(11), (b, s, dm), jnp.float32)
    ones = jnp.ones((b,), bool)
    rows = (None, ones, ones, jnp.full((b,), s, jnp.int32),
            jnp.ones((b, s), bool))
    shares = {}
    for name, m, p in (("balanced", model, params),
                       ("plain", plain,
                        plain.init_params(jax.random.PRNGKey(0)))):
        lp = jax.tree.map(lambda a: a[0], p["groups"]["linear"])
        kept = linear_state.init_state(
            1, b, *cfg.state_shape, cfg.conv_width, cfg.conv_kernel,
            cfg.jax_dtype, jnp.float32)
        out, _, _ = m._linear(lp, 0, h, kept["state"], kept["conv"], rows)
        y = np.asarray(out - h)[:, -1]                  # one row a sequence
        mean = y.mean(axis=0)
        shares[name] = float(mean @ mean / np.mean((y * y).sum(axis=1)))
        wo = np.asarray(lp["wo"]).reshape(4, 16, dm)
        taps = np.asarray(lp["conv_w"])[2 * 64:]
        if name == "balanced":
            assert np.abs(wo.mean(axis=1)).max() < 1e-6
            np.testing.assert_allclose((taps * taps).sum(-1), 1.0, rtol=1e-5)
        else:
            assert np.abs(wo.mean(axis=1)).max() > 1e-3
    assert shares["balanced"] < 0.03 and shares["plain"] > 3 * shares["balanced"], shares


def test_the_witness_counts_picks_and_forces_them_faithfully(monkeypatch):
    """scripts/ling_router_witness.py on the long-answer script's toy in
    float32: the picks ``moe_route`` hands over are the reference's at every
    position of prompt and answer, and the reference given those picks back
    computes what it computed with its own — forcing re-weights, nothing
    else."""
    import dynamo_tpu.models.hybrid_linear as hybrid
    from cellbench import server
    from scripts import ling_router_witness as witness

    monkeypatch.setattr(hybrid, "moe_route", hybrid.moe_route)  # put back after
    witness.hand_over_picks()
    cfg = witness.TINY_LING
    model = server.resolve(cfg["model_class"])(server.model_config(cfg))
    params = model.init_params(jax.random.PRNGKey(3))
    prompt = [int(t) for t in np.random.default_rng(3).integers(1, 512, 83)]
    fed, logp, picks = witness.greedy(
        model, witness.program(model), params, prompt, 5,
        cfg["serve"]["prefill_chunk_tokens"], cfg["serve"]["block_size"])
    assert len(fed) == 83 + 4 and logp.shape == (87, 512)
    assert picks.shape == (3, 87, 2)            # expert layers, positions, top-k
    padded = np.zeros(128, np.int32)
    padded[:87] = fed
    reference = witness.make_reference(cfg)
    free, own = reference(params, jnp.asarray(padded), None)
    chosen = np.zeros((3, 128, 16), np.float32)
    np.put_along_axis(chosen[:, :87], picks, 1.0, axis=-1)
    assert np.array_equal(np.asarray(own)[:, :87], chosen[:, :87] > 0)
    assert np.abs(np.asarray(free)[:87] - logp).max() < 2e-3
    forced, _ = reference(params, jnp.asarray(padded), jnp.asarray(
        np.asarray(own, np.float32)))
    assert np.abs(np.asarray(forced)[:87] - np.asarray(free)[:87]).max() < 1e-5

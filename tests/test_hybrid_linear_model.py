"""models/hybrid_linear.py against the benchmark's plain reference
(cellbench/reference/hybrid_linear.py) by direct calls of ``forward``: prefill
in chunks then decode, padding, the shares of the expert layer, what
``from_hf_config`` refuses, and how the seeded decay is spread."""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.hybrid_linear import (HybridLinearConfig,
                                             HybridLinearModel)
from hybrid_linear_tiny import (BS, NB, ROOT, ROUNDING, SLOTS, TINY, build,
                                ref, tokens_of, want)

WIDTH = 16          # blocks a row's table holds


def logp(model, params, hidden):
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(params, hidden), axis=-1))


def chunk(model, params, cache, tokens, a, b, slot, first_block, pad_to=None):
    """Tokens [a, b) of one sequence in engine slot ``slot``, as the engine
    lays a prefill chunk out (padded to ``pad_to`` with slot -1)."""
    n = pad_to or (b - a)
    bt = np.zeros((1, WIDTH), np.int32)
    bt[0, :] = first_block + np.arange(WIDTH)
    tok = np.zeros((1, n), np.int32)
    pos = np.zeros((1, n), np.int32)
    slots = np.full((1, n), -1, np.int32)
    tok[0, :b - a] = tokens[a:b]
    pos[0, :b - a] = np.arange(a, b)
    slots[0, :b - a] = bt[0, np.arange(a, b) // BS] * BS + np.arange(a, b) % BS
    pb = a // BS
    pb = 0 if pb == 0 else 1 << (pb - 1).bit_length()
    h, cache = model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray([b], jnp.int32), jnp.asarray(slots),
        prefix_blocks=min(pb, WIDTH),
        seq_slots=jnp.asarray([slot], jnp.int32))
    return logp(model, params, h[0, :b - a]), cache


def decode(model, params, cache, rows):
    """One decode step over the slot array: ``rows`` maps slot -> (tokens so
    far, first block, next token); the other slots are idle."""
    bt = np.zeros((SLOTS, WIDTH), np.int32)
    tok = np.zeros((SLOTS, 1), np.int32)
    pos = np.zeros((SLOTS, 1), np.int32)
    slot = np.full((SLOTS, 1), -1, np.int32)
    lens = np.zeros(SLOTS, np.int32)
    for i, (n, first_block, nxt) in rows.items():
        bt[i] = first_block + np.arange(WIDTH)
        tok[i, 0], pos[i, 0], lens[i] = nxt, n, n + 1
        slot[i, 0] = bt[i, n // BS] * BS + n % BS
    h, cache = model.forward(
        params, jnp.asarray(tok), jnp.asarray(pos), cache, jnp.asarray(bt),
        jnp.asarray(lens), jnp.asarray(slot))
    return logp(model, params, h[:, 0]), cache


def fresh_cache(model):
    return model.init_kv_cache(NB, BS, slots=SLOTS)


def test_prefill_in_chunks_then_decode_is_the_reference():
    """75 tokens in chunks of 32, 32 and 11 (the last padded to 16) in slot
    2, then five decode steps while slot 0 decodes another sequence: every
    position's log-probabilities against the reference's full forward.
    float32 on both sides: what is left is the order of the sums."""
    model, params = build()
    toks, other = tokens_of(80, 1), tokens_of(26, 2)
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, other, 0, 20, 0, 20)
    got = []
    for a, b, pad in ((0, 32, None), (32, 64, None), (64, 75, 16)):
        lp, cache = chunk(model, params, cache, toks, a, b, 2, 1, pad)
        got.append(lp)
    for n in range(75, 80):
        lp, cache = decode(model, params, cache, {
            2: (n, 1, toks[n]), 0: (n - 55, 20, other[n - 55])})
        got.append(lp[2:3])
        want_other = want(params, other[:n - 54], [n - 55])
        assert np.abs(lp[0] - want_other[0]).max() < ROUNDING
    got = np.concatenate(got)
    assert np.abs(got - want(params, toks, np.arange(80))).max() < ROUNDING
    counts = np.asarray(cache["moe_counts"])
    assert counts[0, 0, 4] == 6 * (20 + 75 + 2 * 5)        # tokens x layers
    assert counts[0, 0, 5] == 2 and counts[0, 0, 6] == 0   # resets, mismatches
    assert list(np.asarray(cache["state_pos"])) == [25, 0, 80, 0]


def test_padding_and_idle_rows_change_nothing():
    """A chunk padded to twice its length gives the same rows and leaves the
    same state; a decode step leaves the slots with no row bit for bit, and
    a sequence continued at another position than its state's is counted."""
    model, params = build()
    toks = tokens_of(40, 3)
    plain_lp, plain = chunk(model, params, fresh_cache(model), toks, 0, 24, 1, 1)
    padded_lp, padded = chunk(model, params, fresh_cache(model), toks, 0, 24,
                              1, 1, pad_to=64)
    assert np.abs(plain_lp - padded_lp).max() < 1e-4    # the sums' order
    for leaf in ("state", "conv", "state_pos"):
        assert np.abs(np.asarray(plain[leaf], np.float32)
                      - np.asarray(padded[leaf], np.float32)).max() < 1e-4
    _, after = decode(model, params, plain, {3: (5, 30, 7)})
    for leaf in ("state", "conv"):
        assert np.array_equal(np.asarray(after[leaf])[:, 1],
                              np.asarray(plain[leaf])[:, 1])
    assert list(np.asarray(after["state_pos"])) == [0, 24, 0, 6]
    # slot 3 went on at position 5 with a state that stood at 0
    assert np.asarray(after["moe_counts"])[0, 0, 6] == 1


def test_the_shares_of_an_expert_layer_add_up_to_the_uncut_layer():
    """Four chips of two experts each: the parts their held experts give,
    with the shared expert counted once, are the layer over all eight — in
    the reference and in the program's own ``_experts``."""
    whole_cfg = dict(TINY, n_routed_experts=8, expert_parallel={
        "chips": 1, "router_experts": 8, "first_expert": 0})
    model, params = build(whole_cfg)
    group = params["groups"]["linear"]
    lp = jax.tree.map(lambda a: a[1], group)
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 24, 64), jnp.float32)
    valid = jnp.ones((1, 24), bool)
    routed_all, shared_all = ref.make_layer(whole_cfg)(lp, x[0])
    total = np.zeros_like(np.asarray(routed_all))
    for first in (0, 2, 4, 6):
        cfg = dict(TINY, expert_parallel={
            "chips": 4, "router_experts": 8, "first_expert": first})
        share = jax.tree.map(lambda a: a, lp)
        stacks = {k: group[k][:, first:first + 2]
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
        assert int(counted[0]) == 24 * 2 and 0 <= int(counted[1]) <= 48
    assert np.abs(total - np.asarray(routed_all)).max() < 1e-4


@pytest.mark.parametrize("change,words", [
    ({"scoring_func": "softmax"}, "scoring_func"),
    ({"linear_attn_config": {**TINY["linear_attn_config"], "num_kv_heads": 2}},
     "num_kv_heads"),
    ({"use_rope": True}, "use_rope"),
    ({"topk_method": "greedy"}, "topk_method"),
    ({"use_gqa_gate": False}, "use_gqa_gate"),
    ({"rope_scaling": {"type": "yarn", "factor": 4}}, "rope_scaling"),
    ({"gqa_layers": [0, 5]}, "gqa_interval"),
    ({"gqa_interval": 1}, "gqa_interval"),
    ({"model_type": "ling_hybrid_mla", "use_nGPT": True}, "use_nGPT"),
    # what ``ling_hybrid_mla``'s reader computes and this one still refuses:
    # cellbench/reference/hybrid_linear.py does not know the switches
    ({"kda_use_full_proj": True}, "kda_use_full_proj"),
    ({"kda_allow_neg_eigval": False}, "kda_allow_neg_eigval"),
    ({"first_k_dense_replace": 1}, "first_k_dense_replace"),
    ({"n_group": 4, "topk_group": 2}, "group-limited"),
    ({"model_type": "glm_moe_dsa"}, "model_type"),
])
def test_from_hf_config_refuses_by_name_what_it_does_not_compute(change, words):
    with pytest.raises(NotImplementedError, match=words):
        HybridLinearConfig.from_hf_config({**TINY, **change})


def test_the_configuration_file_is_the_published_model_cut_as_stated():
    """``attention_layers`` against ``gqa_layers``, the parameter count of the
    cut from the program's own shapes (3.90 B = 7.80 GB), the state and the
    pool it asks for (1.61 GB each), every published key as the catalog has
    it but the four reduced."""
    cfg = json.loads((ROOT / "cellbench/configs/solar-open2-ep16.json").read_text())
    assert cfg["attention_layers"] == len(cfg["gqa_layers"]) == 2
    assert cfg["reduced"] == ["num_hidden_layers", "gqa_layers",
                              "n_routed_experts", "vocab_size"]
    published = {"hidden_size": 4096, "num_attention_heads": 64, "head_dim": 128,
                 "num_key_value_heads": 8, "moe_intermediate_size": 1280,
                 "intermediate_size": 10240, "num_experts_per_tok": 8,
                 "n_shared_experts": 1, "first_k_dense_replace": 0,
                 "gqa_interval": 3, "use_rope": False, "use_gqa_gate": True,
                 "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
                 "linear_attn_config": {"short_conv_kernel_size": 4,
                                        "head_dim": 128, "num_heads": 64,
                                        "num_kv_heads": None}}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (8, 20, 24576)
    assert cfg["expert_parallel"] == {"chips": 16, "router_experts": 320,
                                      "first_expert": 0}
    mc = HybridLinearConfig.from_hf_config(cfg)
    model = HybridLinearModel(mc)
    shapes = jax.eval_shape(model.init_params, jax.random.key(0))
    n = sum(a.size for a in jax.tree.leaves(shapes))
    expert = 3 * 4096 * 1280
    outside = expert + 4096 * 320 + 320 + 4096          # shared, router, bias, norm
    linear = (4 * 4096 * 8192 + 24576 * 4 + 2 * (4096 * 128 + 128 * 8192)
              + 4096 * 64 + 64 + 8192 + 128 + 4096)
    gqa = 3 * 4096 * 8192 + 2 * 4096 * 1024 + 4096
    assert n == (6 * linear + 2 * gqa + 8 * (outside + 20 * expert)
                 + 2 * 24576 * 4096 + 4096)
    assert 7.79e9 < 2 * n < 7.81e9
    serve = cfg["serve"]
    cache = jax.eval_shape(lambda: model.init_kv_cache(
        serve["num_blocks"], serve["block_size"], slots=serve["max_batch_size"]))
    assert cache["kv"].shape == (2, serve["num_blocks"], 2, 32, 1024)
    assert cache["state"].shape == (6, 64, 64, 128, 128)
    assert cache["state"].dtype == jnp.float32
    assert cache["conv"].shape == (6, 64, 3, 24576)
    assert model.state_bytes_per_slot() == 6 * (4 * 2**20 + 3 * 24576 * 2)
    # the traffic's worst case: 64 x (2,048 + 1,024) tokens
    assert serve["num_blocks"] * 32 >= 64 * 3072
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(cache))
    assert 0.6 < (2 * n + held) / 16.9e9 < 0.75


def test_the_seeded_decay_remembers():
    """The per-token decay alpha has its median over tokens in [0.9, 0.99]
    for at least a quarter of the key channels (a state that forgets in
    three tokens cannot show a broken chunk carry), at the published widths
    of the decay's parameters: 64 heads x 128 channels, rank 128."""
    cfg = dict(TINY, hidden_size=256, linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}, num_hidden_layers=4, gqa_layers=[0])
    model, params = build(cfg)
    lp = jax.tree.map(lambda a: a[0], params["groups"]["linear"])
    x = jax.random.normal(jax.random.PRNGKey(9), (256, 256), jnp.float32)
    a = (x @ lp["decay_down"]) @ lp["decay_up"] + lp["dt_bias"]
    g = -jnp.exp(lp["a_log"])[:, None] * jax.nn.softplus(a.reshape(256, 64, 128))
    alpha = np.median(np.exp(np.asarray(g)), axis=0).reshape(-1)
    share = np.mean((alpha >= 0.9) & (alpha <= 0.99))
    assert share >= 0.25, share
    assert np.mean(alpha < 0.5) < 0.2          # and few forget at once
    # the token moves the decay: it is a gate, not a constant
    assert np.std(np.asarray(g), axis=0).mean() > 0.01 * -np.mean(np.asarray(g))


def test_a_decode_through_the_state_kernel_is_the_decode_through_xla(
        monkeypatch):
    """The decode branch of ``_linear`` that the TPU takes (the state updated
    where it lies by ops/pallas/linear_state.py, here interpreted), against
    the slice / ``delta_rule_step`` / set form on the same cache: the live
    rows' log-probabilities, state and ``conv``, the idle slots bit for bit,
    ``state_pos`` and the counts equal.  Heads of 128 x 128: what the kernel tiles."""
    import functools

    from dynamo_tpu.models import hybrid_linear
    from dynamo_tpu.ops.pallas.linear_state import state_update

    cfg = dict(TINY, num_hidden_layers=4, gqa_layers=[0], linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 8,
        "num_kv_heads": None})
    model, params = build(cfg)
    assert model.state_update_impl() == ("xla", "backend is cpu")
    toks = tokens_of(30, 4)
    cache = fresh_cache(model)
    _, cache = chunk(model, params, cache, toks, 0, 24, 2, 1)
    # slot 1 holds what a finished request left; slot 3 starts at position 0
    cache["state"] = cache["state"].at[:, 1].set(7.0)
    rows = {2: (24, 1, toks[24]), 3: (0, 30, toks[0])}
    want_lp, want_cache = decode(model, params, cache, rows)
    monkeypatch.setattr(model, "state_update_impl", lambda: ("pallas", "test"))
    monkeypatch.setattr(hybrid_linear, "state_update",
                        functools.partial(state_update, interpret=True))
    got_lp, got_cache = decode(model, params, jax.tree.map(jnp.array, cache),
                               rows)
    assert np.abs(got_lp[[2, 3]] - want_lp[[2, 3]]).max() < 1e-4
    got_s, want_s = (np.asarray(c["state"]) for c in (got_cache, want_cache))
    assert np.abs(got_s[:, [2, 3]] - want_s[:, [2, 3]]).max() < 1e-5
    assert np.array_equal(got_s[:, [0, 1]], np.asarray(cache["state"])[:, [0, 1]])
    # a later layer's inputs carry the earlier layers' rounding
    assert np.abs(np.asarray(got_cache["conv"])
                  - np.asarray(want_cache["conv"])).max() < 1e-4
    for leaf in ("state_pos", "moe_counts"):
        assert np.array_equal(np.asarray(got_cache[leaf]),
                              np.asarray(want_cache[leaf]))

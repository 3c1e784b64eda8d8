"""A looped decoder through ``LlamaModel`` (``ModelConfig.ut_steps`` > 1):
the layer stack run T times over shared weights, a K/V cache of its own for
every pass (cache layer t*L + l), the final norm after every pass, the exit
gate — against the plain float32 reference of the benchmark
(cellbench/reference/ouro_loop.py).  T 3 and L 2, so that a pass and a layer
cannot be confused."""

import hashlib
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

ROOT = Path(__file__).resolve().parent.parent
BS, NB, T, L = 8, 40, 3, 2


def _reference():
    spec = importlib.util.spec_from_file_location(
        "_ouro_reference", ROOT / "cellbench/reference/ouro_loop.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()

TINY = dict(
    architectures=["OuroForCausalLM"], model_type="ouro", vocab_size=128,
    hidden_size=64, intermediate_size=96, num_hidden_layers=L,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    hidden_act="silu", rms_norm_eps=1e-6, rope_theta=10000.0,
    max_position_embeddings=512, tie_word_embeddings=False,
    total_ut_steps=T, early_exit_threshold=1)


def _model(hf: dict = TINY, seed: int = 0):
    """The model with every norm and the gate's bias drawn at random: with
    the seeded ones (all 1, bias 0) two norms could change places unseen."""
    model = LlamaModel(ModelConfig.from_hf_config(hf, dtype="float32"))
    params = model.init_params(jax.random.PRNGKey(seed))
    key = jax.random.PRNGKey(seed + 100)
    for name in ("attn_norm", "post_attn_norm", "mlp_norm", "post_mlp_norm"):
        key, sub = jax.random.split(key)
        params["layers"][name] = 1.0 + 0.3 * jax.random.normal(
            sub, params["layers"][name].shape)
    key, sub = jax.random.split(key)
    params["final_norm"] = 1.0 + 0.3 * jax.random.normal(
        sub, params["final_norm"].shape)
    if model.config.ut_steps > 1:
        params["exit_gate_b"] = jnp.float32(0.2)
    return model, params


def _tokens(n: int, seed: int = 0) -> np.ndarray:
    return np.random.default_rng(seed).integers(1, 128, n)


def _want(hf, params, tokens, at):
    padded = np.zeros(-(-len(tokens) // 32) * 32, np.int32)
    padded[:len(tokens)] = tokens
    return np.asarray(ref.make_forward(hf)(
        params, jnp.asarray(padded), jnp.asarray(at)))


def _logp(model, params, hidden):
    return np.asarray(jax.nn.log_softmax(
        model.compute_logits(params, hidden), axis=-1))


def _prefill(model, params, cache, tokens, table, chunks, width=8):
    """Prefill ``tokens`` in (start, end) chunks as the engine does: one
    prompt a dispatch, block-aligned chunks, a power-of-two prefix bucket.
    Returns the log-probabilities of every row and the cache."""
    bt = np.zeros((1, width), np.int32)
    bt[0, :len(table)] = table
    out = []
    for a, b in chunks:
        pos = np.arange(a, b, dtype=np.int32)[None]
        slots = bt[0, pos // BS] * BS + pos % BS
        pb = a // BS
        pb = 0 if pb == 0 else 1 << (pb - 1).bit_length()
        h, cache = model.forward(
            params, jnp.asarray(tokens[None, a:b], jnp.int32),
            jnp.asarray(pos), cache, jnp.asarray(bt),
            jnp.asarray([b], jnp.int32), jnp.asarray(slots),
            prefix_blocks=min(pb, width))
        out.append(_logp(model, params, h[0]))
    return np.concatenate(out), cache


def _decode(model, params, cache, rows, steps, width=8):
    """Decode ``steps`` tokens for several rows at once through the paged
    cache: ``rows`` = [(tokens so far incl. the ones to feed, table)].
    Row r feeds tokens[r][n_r], tokens[r][n_r + 1], ... (teacher forced)
    where n_r is its prefilled length.  Returns [rows, steps, V]."""
    b = len(rows)
    bt = np.zeros((b, width), np.int32)
    for r, (_, table, _) in enumerate(rows):
        bt[r, :len(table)] = table
    out = []
    for j in range(steps):
        pos = np.asarray([[n + j] for _, _, n in rows], np.int32)
        tok = np.asarray([[toks[n + j]] for toks, _, n in rows], np.int32)
        slots = bt[np.arange(b)[:, None], pos // BS] * BS + pos % BS
        h, cache = model.forward(
            params, jnp.asarray(tok), jnp.asarray(pos), cache,
            jnp.asarray(bt), jnp.asarray(pos[:, 0] + 1), jnp.asarray(slots))
        out.append(_logp(model, params, h[:, 0]))
    return np.stack(out, axis=1), cache


def _tables(lengths):
    tables, first = [], 1
    for n in lengths:
        blocks = -(-n // BS) + 1
        tables.append(np.arange(first, first + blocks, dtype=np.int32))
        first += blocks
    return tables


# --------------------------------------------- (a) system against reference --
@pytest.mark.parametrize("threshold", [1, 0.5], ids=["last-pass", "gate-0.5"])
def test_chunked_prefill_then_batched_decode_match_the_reference(threshold):
    """Three prompts prefilled in chunks, then five tokens decoded for all
    three at once through the paged cache: every position's logits."""
    hf = dict(TINY, early_exit_threshold=threshold)
    model, params = _model(hf)
    assert model.config.ut_steps == T and model.cache_layers == T * L
    cache = model.init_kv_cache(NB, BS)
    assert cache.shape[0] == T * L
    prompts = [(40, [(0, 16), (16, 32), (32, 40)]), (24, [(0, 24)]),
               (17, [(0, 16), (16, 17)])]
    steps = 5
    seqs = [_tokens(n + steps, seed=i) for i, (n, _) in enumerate(prompts)]
    tables = _tables([n + steps for n, _ in prompts])
    got_prefill = []
    for toks, table, (n, chunks) in zip(seqs, tables, prompts):
        got, cache = _prefill(model, params, cache, toks[:n], table, chunks)
        got_prefill.append(got)
    got_decode, cache = _decode(
        model, params, cache,
        [(toks, table, n) for toks, table, (n, _) in zip(seqs, tables, prompts)],
        steps)
    exits = set()
    for r, (toks, (n, _)) in enumerate(zip(seqs, prompts)):
        want = _want(hf, params, toks, np.arange(n + steps))
        np.testing.assert_allclose(got_prefill[r], want[:n], atol=2e-4)
        np.testing.assert_allclose(got_decode[r], want[n:], atol=2e-4)
        padded = np.zeros(64, np.int32)
        padded[:len(toks)] = toks
        with jax.default_matmul_precision("highest"):
            _, gates = ref.passes(params, jnp.asarray(padded), hf)
        exits |= set(np.asarray(
            ref.exit_pass(gates[:, :len(toks)], threshold)).tolist())
    # (b) at 1 the last pass feeds the head; at 0.5 tokens leave at every pass
    assert exits == ({T - 1} if threshold == 1 else set(range(T)))


@pytest.mark.parametrize("broken", ["a pass dropped", "no norm closing a pass",
                                    "one cache for all passes"])
def test_the_comparison_would_catch(broken, monkeypatch):
    """Against a program that drops a pass, leaves out the norm that closes
    a pass (and feeds the next), or lets the passes share one K/V cache,
    the reference's logits part by far more than (a)'s tolerance."""
    import dynamo_tpu.models.llama as llama

    model, params = _model()
    n, steps = 24, 4
    toks = _tokens(n + steps, seed=3)
    want = _want(TINY, params, toks, np.arange(n + steps))
    if broken == "a pass dropped":
        model = LlamaModel(ModelConfig.from_hf_config(
            dict(TINY, total_ut_steps=T - 1), dtype="float32"))
    elif broken == "no norm closing a pass":
        real = llama.rms_norm

        def skipping(x, weight, eps, unit_offset=False):
            return x if weight is params["final_norm"] else real(
                x, weight, eps, unit_offset)

        monkeypatch.setattr(llama, "rms_norm", skipping)
    else:
        shared = lambda li: (T - 1) * L + li % L
        for name, at in (("write_kv_cache_layer", 1), ("paged_attention_layer", 2),
                         ("prefill_attention", 4)):
            real = getattr(llama, name)

            def wrapped(*a, _real=real, _at=at, **kw):
                a = list(a)
                a[_at] = shared(a[_at])
                return _real(*a, **kw)

            monkeypatch.setattr(llama, name, wrapped)
    cache = model.init_kv_cache(NB, BS)
    table = _tables([n + steps])[0]
    got_p, cache = _prefill(model, params, cache, toks[:n], table, [(0, 16), (16, 24)])
    got_d, _ = _decode(model, params, cache, [(toks, table, n)], steps)
    worst = max(np.abs(got_p - want[:n]).max(), np.abs(got_d[0] - want[n:]).max())
    assert worst > 0.05, worst


# ------------------------------------------- (c) a cache of its own a pass --
def test_pass_t_writes_cache_layers_of_its_own_and_reads_no_others():
    model, params = _model()
    n = 24
    toks = _tokens(n + 1, seed=5)
    table = _tables([n + 1])[0]
    _, cache = _prefill(model, params, model.init_kv_cache(NB, BS), toks[:n],
                        table, [(0, 16), (16, 24)])
    cache = np.asarray(cache)
    used = cache[:, table[:3]]                       # [T*L, 3, 2, Bs, HkD]
    # every pass of every layer wrote its own rows, all different
    assert all(np.abs(used[i]).max() > 0 for i in range(T * L))
    for i in range(T * L):
        for j in range(i):
            assert np.abs(used[i] - used[j]).max() > 1e-3, (i, j)
    # pass 0 is an ordinary walk from the embeddings: its cache layers are
    # the cache of the same weights run once
    once = LlamaModel(ModelConfig.from_hf_config(
        dict(TINY, total_ut_steps=1), dtype="float32"))
    p1 = {k: v for k, v in params.items() if not k.startswith("exit_gate")}
    _, c1 = _prefill(once, p1, once.init_kv_cache(NB, BS), toks[:n], table,
                     [(0, 16), (16, 24)])
    np.testing.assert_allclose(cache[:L], np.asarray(c1), atol=1e-5)
    # Perturb pass 1's cache layers and decode one token.  What the decode
    # writes for pass 0, and for pass 1's first layer (projected before any
    # attention of pass 1), is untouched: only pass 1's attention read the
    # perturbed rows, and what it moved shows from pass 1's second layer on.
    slot = table[n // BS] * BS + n % BS
    noisy = cache.copy()
    noisy[L:2 * L, table[:3]] += 0.5
    rows = [(toks, table, n)]
    _, clean_after = _decode(model, params, jnp.asarray(cache), rows, 1)
    _, noisy_after = _decode(model, params, jnp.asarray(noisy), rows, 1)
    new = lambda c: np.asarray(c).reshape(T * L, NB * 2 * BS // (2 * BS), 2, BS, -1)[
        :, slot // BS, :, slot % BS]
    moved = np.abs(new(clean_after) - new(noisy_after)).max(axis=(1, 2))
    assert (moved[:L + 1] == 0).all(), moved
    assert (moved[L + 1:] > 1e-4).all(), moved


# ------------------------------------------------- (d) ut_steps 1: no loop --
# sha256 of ``jax.jit(forward).lower(...).as_text()`` of the Mistral toy
# (ModelConfig.tiny(), GQA 4/2) on the parent commit dfaebb3, decode (S 1) and
# a prefill chunk (S 16, one prefix block): a looped decoder's support adds no
# operation to a model that does not loop.  After a change to forward() that
# is meant to change every model's program, print the new digests with
# ``PYTHONPATH=. python tests/test_looped_layers.py``.  (PR 40 meant to: a
# decode-shaped step orders its rows by length before the layer scan, so S 1
# is that tree's; the prefill chunk is still dfaebb3's.)
PARENT_HLO = {
    1: "a9d7aa4ae03fccd28a49b376edbb95c770b0929098829bcaecb4e8134789af53",
    16: "958f9d0609b930c03080528ce49c2d2217c212c6a9dfdeacc556dd9f22e2153c",
}


def _toy_hlo(s: int) -> str:
    model = LlamaModel(ModelConfig.tiny(num_kv_heads=2))
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: model.init_kv_cache(8, 16))
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    kw = {} if s == 1 else {"prefix_blocks": 1}
    fn = lambda p, c, *a: model.forward(p, a[0], a[1], c, a[2], a[3], a[4], **kw)
    return jax.jit(fn).lower(
        params, cache, sds(2, s), sds(2, s), sds(2, 4), sds(2), sds(2, s)).as_text()


@pytest.mark.parametrize("s", sorted(PARENT_HLO))
def test_a_model_that_does_not_loop_lowers_to_the_program_it_had(s):
    text = _toy_hlo(s)
    assert text.count("stablehlo.while") == 1          # the layer scan alone
    assert hashlib.sha256(text.encode()).hexdigest() == PARENT_HLO[s]


def test_a_looped_model_lowers_to_one_loop_round_the_layer_scan():
    model = LlamaModel(ModelConfig.from_hf_config(TINY, dtype="float32"))
    params = jax.eval_shape(lambda: model.init_params(jax.random.key(0)))
    cache = jax.eval_shape(lambda: model.init_kv_cache(8, 16))
    sds = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)
    text = jax.jit(lambda p, c, *a: model.forward(
        p, a[0], a[1], c, a[2], a[3], a[4])).lower(
            params, cache, sds(2, 1), sds(2, 1), sds(2, 4), sds(2), sds(2, 1)).as_text()
    assert text.count("stablehlo.while") == 2


# ------------------------------------------------------- config and loader --
def test_hf_config_maps_onto_the_looped_decoder():
    cfg = ModelConfig.from_hf_config(TINY)
    assert (cfg.ut_steps, cfg.early_exit_threshold) == (T, 1.0)
    assert cfg.post_norms and not cfg.qk_norm and not cfg.attention_bias
    assert cfg.num_kv_heads == cfg.num_heads == 4 and cfg.sliding_window is None
    # the two keys belong to the architecture that defines them
    other = ModelConfig.from_hf_config(
        dict(TINY, architectures=["MistralForCausalLM"]))
    assert other.ut_steps == 1 and not other.post_norms
    with pytest.raises(ValueError, match="ut_steps"):
        LlamaModel(ModelConfig.tiny(ut_steps=0))


def test_loader_maps_the_four_norms_and_the_gate():
    from dynamo_tpu.models.loader import load_params_from_state_dict

    cfg = ModelConfig.from_hf_config(TINY, dtype="float32")
    rng = np.random.default_rng(0)
    dm, f, v = cfg.hidden_size, cfg.intermediate_size, cfg.vocab_size
    state = {"model.embed_tokens.weight": rng.normal(size=(v, dm)),
             "model.norm.weight": rng.normal(size=dm),
             "lm_head.weight": rng.normal(size=(v, dm)),
             "model.early_exit_gate.weight": rng.normal(size=(1, dm)),
             "model.early_exit_gate.bias": rng.normal(size=(1,))}
    for i in range(L):
        pre = f"model.layers.{i}."
        for name in ("q_proj", "k_proj", "v_proj", "o_proj"):
            state[pre + f"self_attn.{name}.weight"] = rng.normal(size=(dm, dm))
        for name, shape in (("gate_proj", (f, dm)), ("up_proj", (f, dm)),
                            ("down_proj", (dm, f))):
            state[pre + f"mlp.{name}.weight"] = rng.normal(size=shape)
        for name in ("input_layernorm", "input_layernorm_2",
                     "post_attention_layernorm", "post_attention_layernorm_2"):
            state[pre + name + ".weight"] = rng.normal(size=dm)
    params = load_params_from_state_dict(cfg, state)
    want = jax.eval_shape(lambda: LlamaModel(cfg).init_params(jax.random.key(0)))
    assert jax.tree.map(lambda a: a.shape, params) == jax.tree.map(
        lambda a: a.shape, want)
    for ours, theirs in (("attn_norm", "input_layernorm"),
                         ("post_attn_norm", "input_layernorm_2"),
                         ("mlp_norm", "post_attention_layernorm"),
                         ("post_mlp_norm", "post_attention_layernorm_2")):
        np.testing.assert_allclose(
            params["layers"][ours][1], state[f"model.layers.1.{theirs}.weight"],
            rtol=1e-6)
    np.testing.assert_allclose(
        params["exit_gate_w"], state["model.early_exit_gate.weight"][0], rtol=1e-6)
    # loaded weights serve: the reference agrees with the model on them
    model = LlamaModel(cfg)
    toks = _tokens(16, seed=9)
    got, _ = _prefill(model, params, model.init_kv_cache(NB, BS), toks,
                      _tables([16])[0], [(0, 16)])
    np.testing.assert_allclose(got, _want(TINY, params, toks, np.arange(16)),
                               atol=5e-3, rtol=1e-3)


# ------------------------------------------------------------------ engine --
def _engine(model, params, **kw):
    from dynamo_tpu.engine import EngineConfig, EngineCore

    return EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=BS, num_blocks=NB,
        prefill_chunk_tokens=16, **kw), eos_token_ids=[])


def _submit(core, rid, prompt, n):
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions

    outs = []
    core.submit(EngineRequest(rid, list(prompt), SamplingOptions(temperature=0.0),
                              StopConditions(max_tokens=n), outs.append))
    return outs


def test_engine_serves_a_looped_model_and_counts_its_passes():
    """EngineCore, the block manager and dispatch-ahead as they are: greedy
    tokens are the reference's argmax, and the counters say 3 passes a
    token over 6 cache layers."""
    model, params = _model()
    core = _engine(model, params)
    prompts = [_tokens(n, seed=20 + i).tolist() for i, n in enumerate((21, 9, 34))]
    outs = [_submit(core, f"r{i}", p, 6) for i, p in enumerate(prompts)]
    while core.step():
        pass
    for emitted, p in zip(outs, prompts):
        out = [t for o in emitted for t in o.token_ids]
        assert len(out) == 6
        want = _want(TINY, params, np.asarray(p + out), np.arange(len(p) - 1, len(p) + 5))
        # the sampled token is within rounding of the reference's best
        best = want.max(axis=-1)
        assert (best - want[np.arange(6), out] < 1e-3).all()
    m = core.metrics()
    assert m["cache_layers"] == T * L
    assert m["kv_bytes_per_token"] == 2 * 4 * 16 * 4 * T * L
    assert m["loop_tokens_total"] >= sum(map(len, prompts)) + 3 * 5
    assert m["loop_passes_total"] == T * m["loop_tokens_total"]
    assert m["ahead_dispatches_total"] > 0        # through _settle


def test_a_model_that_does_not_loop_counts_one_pass_a_token():
    model = LlamaModel(ModelConfig.tiny())
    core = _engine(model, model.init_params(jax.random.PRNGKey(0)))
    _submit(core, "r", _tokens(20).tolist(), 4)
    while core.step():
        pass
    m = core.metrics()
    assert m["cache_layers"] == 2
    assert m["loop_passes_total"] == m["loop_tokens_total"] >= 20 + 3


def test_seq_parallel_prefill_is_refused_for_a_looped_model():
    model, params = _model()
    assert not model.supports_seq_parallel
    assert LlamaModel(ModelConfig.tiny()).supports_seq_parallel
    with pytest.raises(NotImplementedError, match="looped"):
        model.forward_seq_parallel(params, jnp.zeros((1, 8), jnp.int32),
                                   jnp.zeros((1, 8), jnp.int32), mesh=None)


if __name__ == "__main__":
    print({s: hashlib.sha256(_toy_hlo(s).encode()).hexdigest()
           for s in sorted(PARENT_HLO)})

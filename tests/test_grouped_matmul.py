"""The experts' grouped matmul (ops/pallas/grouped_matmul.py) against
``jax.lax.ragged_dot``, in interpret mode; the static rule that picks it; and
the counter that says what it streamed (``core.moe_experts_touched_total``).
Times and rates come only from ``benchmarks/probe_kernels.py experts`` on the
chip; that it compiles for a v5e at the cells' widths, and that the served
programs hold it, is tests/test_tpu_compile.py's.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dynamo_tpu.ops.pallas import grouped_matmul as gmm
from dynamo_tpu.ops.pallas import registry as reg

# (m, group sizes, K, N, row tile, slice of N, groups of w, first group)
_ONES = [1] * 24
_DECODE = list(np.random.default_rng(3).multinomial(40, [1 / 20] * 20))
_CHUNK = list(np.random.default_rng(4).multinomial(4096, [1 / 16] * 16))
CASES = {
    "an-empty-group-between-two-full-ones": (32, [16, 0, 16], 128, 128, 8, 128, 3, 0),
    "every-group-one-row": (24, _ONES, 128, 128, 8, 128, 24, 0),
    "a-group-longer-than-a-row-tile": (48, [3, 40, 5], 128, 256, 16, 128, 3, 0),
    "offsets-off-the-tiling": (16, [3, 5, 7, 0, 0, 1], 128, 128, 8, 128, 6, 0),
    "rows-of-no-group": (40, [3, 5, 7, 0, 0, 1], 128, 256, 8, 128, 6, 0),
    "no-group-has-a-row": (32, [0, 0, 0], 128, 128, 8, 128, 3, 0),
    "stacked-first-layer": (40, [3, 5, 7, 0, 0, 1], 128, 256, 8, 256, 18, 0),
    "stacked-last-layer": (40, [3, 5, 7, 0, 0, 1], 128, 256, 8, 256, 18, 12),
    "a-ragged-last-row-tile": (136, [3, 5, 70, 0, 0, 1], 128, 256, None, 128, 6, 0),
    "decode-32-rows": (32, [2, 0, 9, 1, 4], 256, 384, None, 128, 5, 0),
    "decode-512-rows": (512, _DECODE, 256, 384, None, 384, 40, 20),
    "chunk-4096-rows": (4096, _CHUNK, 128, 256, None, 256, 16, 0),
}


def _inputs(m, sizes, k, n, groups, dtype, seed=50):
    rng = np.random.default_rng(seed)
    xs = rng.normal(size=(m, k)).astype(np.float32)
    xs[int(np.sum(sizes)):] = np.nan      # rows of no group: a canary
    w = rng.normal(size=(groups, k, n)).astype(np.float32) * k ** -0.5
    return (jnp.asarray(xs, dtype), jnp.asarray(w, dtype),
            jnp.asarray(sizes, jnp.int32))


@pytest.mark.parametrize("case", list(CASES))
def test_kernel_matches_ragged_dot(case):
    """float32 operands (interpret mode multiplies them exactly): every row
    of a group is ``ragged_dot``'s to the sums' order, every other row an
    exact zero whatever the canary held."""
    m, sizes, k, n, tm, tn, groups, first = CASES[case]
    xs, w, gs = _inputs(m, sizes, k, n, groups, jnp.float32)
    tm = tm or reg.grouped_matmul_row_tile(m, k)
    plan = gmm.grouped_matmul_plan(gs, m, tm)
    assert plan[0].shape == (reg.grouped_matmul_pairs(m, len(sizes), tm),)
    got, = gmm.grouped_expert_matmul(
        xs, (w,), plan, first, tm=tm, tn=tn, interpret=True)
    got = np.asarray(got)
    want = np.asarray(reg.grouped_matmul_reference(xs, w, gs, first))
    total = int(np.sum(sizes))
    assert np.isfinite(got).all()
    assert (got[total:] == 0).all()
    np.testing.assert_allclose(got[:total], want[:total], rtol=0, atol=2e-4)


def test_kernel_rounds_bfloat16_once():
    """bf16 in, float32 sums, bf16 out: the result is the float32 product of
    the bf16 operands rounded once."""
    m, sizes, k, n, _, _, groups, first = CASES["decode-512-rows"]
    xs, w, gs = _inputs(m, sizes, k, n, groups, jnp.bfloat16)
    tm = reg.grouped_matmul_row_tile(m, k)
    plan = gmm.grouped_matmul_plan(gs, m, tm)
    got, = gmm.grouped_expert_matmul(xs, (w,), plan, first, tm=tm,
                                     interpret=True)
    assert got.dtype == jnp.bfloat16 and got.shape == (m, n)
    want = reg.grouped_matmul_reference(xs, w, gs, first).astype(jnp.bfloat16)
    total = int(np.sum(sizes))
    # float32 sums in another order can land on the other side of a rounding
    diff = np.abs(np.asarray(got, np.float32) - np.asarray(want, np.float32))
    assert diff[:total].max() <= 2 ** -7 * np.abs(np.asarray(want, np.float32)).max()
    assert (np.asarray(got, np.float32)[total:] == 0).all()


def test_two_stacks_in_one_call_are_two_calls():
    """Gate and up share the rows and the plan: one call over both stacks
    gives each stack's own result, bit for bit."""
    m, sizes, k, n, _, _, groups, first = CASES["decode-512-rows"]
    xs, w, gs = _inputs(m, sizes, k, n, groups, jnp.bfloat16)
    w2 = jnp.flip(w, axis=0)
    plan = gmm.grouped_matmul_plan(gs, m, 128)
    both = gmm.grouped_expert_matmul(xs, (w, w2), plan, first, tm=128,
                                     tn=128, interpret=True)
    assert len(both) == 2
    for got, stack in zip(both, (w, w2)):
        alone, = gmm.grouped_expert_matmul(xs, (stack,), plan, first, tm=128,
                                           tn=128, interpret=True)
        assert np.array_equal(np.asarray(got, np.float32),
                              np.asarray(alone, np.float32))


def test_plan_lists_the_pairs_that_have_rows():
    """Groups of 3, 5, 7 and 1 rows over tiles of 8 of 40 rows: tile 0 meets
    groups 0 and 1, tile 1 groups 2 and 5 (rows 8-14 and 15), tiles 2-4 are
    past the last group and get one empty pair each under its index."""
    plan = gmm.grouped_matmul_plan(
        jnp.asarray([3, 5, 7, 0, 0, 1], jnp.int32), 40, 8)
    groups, tiles, row_tiles, lo, hi, count = (np.asarray(x) for x in plan)
    assert count[0] == 7 and len(groups) == 5 + 5
    assert list(groups[:7]) == [0, 1, 2, 5, 5, 5, 5]
    assert list(tiles[:7]) == [0, 0, 1, 1, 2, 3, 4]
    assert list(row_tiles[:7]) == [0, 0, 1, 1, 1, 1, 1]   # these fetch nothing
    assert list(zip(lo[:7], hi[:7])) == [
        (0, 3), (3, 8), (0, 7), (7, 8), (0, 0), (0, 0), (0, 0)]
    # surplus steps name the last pair's blocks again
    assert (groups[7:] == 5).all() and (tiles[7:] == 4).all()


@pytest.mark.parametrize("k,n,stacks,rows,want", [
    (2048, 768, 2, 128, 768),   # Qwen3 gate + up: both whole, 3.1 MB each
    (768, 2048, 1, 128, 2048),
    (4096, 1280, 2, 128, 256),  # Solar-Open2: a fifth of 10.5 MB, twice
    (4096, 1280, 1, 128, 640),  # ... half of it where one streams alone
    (1280, 4096, 1, 128, 2048),
    (4096, 2048, 2, 128, 256),  # Mistral-Small-4: an eighth of 16.8 MB
    (2048, 4096, 1, 128, 1024),
    (6144, 2048, 2, 64, 256),   # GLM-5.2: an eighth of 25.2 MB, 64 rows
    (2048, 6144, 1, 64, 1024),
    (2048, 2048, 2, 128, 512),  # ZAYA1: a quarter of 8.4 MB, twice (N 4,096)
    (2048, 2048, 1, 128, 1024),
])
def test_tiling_sizes_a_block_by_its_bytes(k, n, stacks, rows, want):
    tm = reg.grouped_matmul_row_tile(512, max(k, n))
    tn = reg.grouped_matmul_tiling(tm, k, n, weights=stacks)
    assert (tm, tn) == (rows, want) and n % tn == 0
    assert tm * max(k, n) * 2 <= reg.GROUPED_MATMUL_ROW_TILE_BYTES
    assert k * tn * 2 <= reg.GROUPED_MATMUL_BLOCK_BYTES
    assert reg.grouped_matmul_vmem_bytes(tm, tn, k, weights=stacks) \
        <= reg.SCOPED_VMEM_BYTES


def test_the_rule_takes_the_kernel_only_where_weights_bound(monkeypatch):
    bf16 = jnp.bfloat16
    rule = lambda m, e, x=bf16, w=bf16: gmm.grouped_matmul_impl(
        m, e, 2048, 768, x, w)
    assert not rule(256, 128)                       # the CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cap = reg.GROUPED_MATMUL_MAX_ROWS_PER_GROUP
    assert rule(256, 128) is True                   # a decode step
    assert rule(128 * cap, 128)
    assert not rule(128 * cap + 1, 128)             # rows enough for XLA's
    assert not rule(256, 128, jnp.float32, jnp.float32)
    assert not gmm.grouped_matmul_impl(256, 128, 2048, 96, bf16, bf16)
    monkeypatch.setenv("DYNAMO_DISABLE_PALLAS", "1")
    assert not rule(256, 128)
    monkeypatch.delenv("DYNAMO_DISABLE_PALLAS")
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:2]).reshape(1, 2),
                             ("data", "model"))
    with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):
        assert not rule(256, 128)                   # GSPMD's ragged_dot


def test_dispatch_through_the_kernel_is_the_dispatch_through_ragged_dot(
        monkeypatch):
    """``grouped_expert_dispatch`` with the rule steered to the kernel (run
    in interpret mode) against itself on ``ragged_dot``: the stacked form, a
    share of the experts held, bf16."""
    from dynamo_tpu.models import llama

    rng = np.random.default_rng(7)
    t, k, d, f, layers, held, router = 24, 2, 128, 256, 3, 4, 16
    bf16 = jnp.bfloat16
    xf = jnp.asarray(rng.normal(size=(t, d)), bf16)
    topi = jnp.asarray(np.stack([rng.permutation(router)[:k]
                                 for _ in range(t)]), jnp.int32)
    weights = jnp.asarray(rng.random(size=(t, k)), jnp.float32)
    w_gate, w_up = (jnp.asarray(rng.normal(size=(layers, held, d, f)) * 0.1,
                                bf16) for _ in range(2))
    w_down = jnp.asarray(rng.normal(size=(layers, held, f, d)) * 0.1, bf16)

    def dispatch():
        return np.asarray(llama.grouped_expert_dispatch(
            xf, weights, topi, router, w_gate, w_up, w_down, jax.nn.silu,
            layer=jnp.int32(2), held=(4, held)), np.float32)

    want = dispatch()
    monkeypatch.setattr(gmm, "grouped_matmul_impl", lambda *a: True)
    kernel = gmm.grouped_expert_matmul
    monkeypatch.setattr(gmm, "grouped_expert_matmul",
                        lambda *a, **kw: kernel(*a, interpret=True, **kw))
    got = dispatch()
    assert np.abs(want).max() > 0.1
    np.testing.assert_allclose(got, want, rtol=0, atol=2e-2)


# ----------------------------------------------- moe_experts_touched_total


def _picks_recorded(monkeypatch):
    """Every ``moe_route`` of models/glm_dsa.py hands its picks to the host."""
    from dynamo_tpu.models import glm_dsa

    seen: list = []
    route = glm_dsa.moe_route

    def spy(*a, **kw):
        weights, topi = route(*a, **kw)
        jax.debug.callback(lambda x: seen.append(np.asarray(x)), topi)
        return weights, topi

    monkeypatch.setattr(glm_dsa, "moe_route", spy)
    return seen


def test_experts_touched_counts_the_held_experts_with_a_row(monkeypatch):
    """A prefill chunk and a decode step of the tiny Mistral-Small-4 model
    (3 expert layers, experts 2-3 of 8 held, top-2): the cache's fourth count
    is the number of (layer, held expert) pairs some token picked — padding
    and idle rows too, whose rows the grouped matmul also computes — by a
    NumPy count of the router's picks."""
    from test_mistral4_mla import BS, NB, build, decode, prefill, table, tokens_of

    seen = _picks_recorded(monkeypatch)
    model, params = build()

    def touched_by_numpy():
        jax.effects_barrier()
        n = sum(len(np.intersect1d(np.unique(p), [2, 3])) for p in seen)
        seen.clear()
        return n

    toks = tokens_of(20, seed=2)
    _, cache = prefill(model, params, model.init_kv_cache(NB, BS), toks,
                       table(1, 20), [(0, 20)])
    counts = np.asarray(cache["moe_counts"])[:, 0]
    assert counts.shape == (3, 4)
    after_prefill = counts[:, 3].sum()
    assert after_prefill == touched_by_numpy() and 0 < after_prefill <= 6
    _, cache = decode(model, params, cache, [(toks, table(1, 20), 5)])
    counts = np.asarray(cache["moe_counts"])[:, 0]
    # one live row and three idle ones, which all pick the same experts
    assert counts[:, 3].sum() - after_prefill == touched_by_numpy()
    assert (counts[:, 2] == 2).all()


def test_experts_touched_reaches_metrics_and_the_exposition():
    from dynamo_tpu.engine import EngineConfig, EngineCore
    from dynamo_tpu.engine.counters import engine_totals
    from dynamo_tpu.engine.request import EngineRequest
    from dynamo_tpu.llm.http.metrics import Metrics
    from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
    from dynamo_tpu.obs.metric_names import EngineMetric as EM
    from test_mistral4_mla import BS, NB, build, tokens_of

    model, params = build()
    core = EngineCore(model, params, EngineConfig(
        max_batch_size=4, max_model_len=128, block_size=BS, num_blocks=NB,
        prefill_chunk_tokens=32), eos_token_ids=[])
    before = engine_totals().moe_experts_touched_total
    core.submit(EngineRequest(
        request_id="r", prompt=[int(t) for t in tokens_of(20, seed=2)],
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=4, ignore_eos=True),
        emit=lambda o: None))
    while core.step():
        pass
    m = core.metrics()
    on_device = int(np.asarray(core.cache["moe_counts"])[:, 0, 3].sum())
    assert m["moe_experts_touched_total"] == on_device > 0
    # at most the 2 held experts a layer and call
    assert m["moe_experts_touched_total"] <= 2 * m["moe_expert_layer_calls_total"]
    assert (engine_totals().moe_experts_touched_total - before
            == m["moe_experts_touched_total"])
    assert f"{EM.MOE_EXPERTS_TOUCHED_TOTAL} " in Metrics().render()

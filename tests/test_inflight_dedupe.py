"""Reserved-block registry: concurrent identical prompts run ONE prefill.

VERDICT r2 ask #5 (ref lib/llm/src/kv/reserved.rs:66, reuse.rs:16-50):
uncommitted allocations register their chain hashes; later allocations
join those blocks and wait for the owner's commit instead of recomputing.
"""

import jax
import numpy as np

from dynamo_tpu.engine.config import EngineConfig
from dynamo_tpu.engine.core import EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.kv.block_manager import KvBlockManager
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel

BS = 16


# --------------------------------------------------------- manager semantics
def test_reserve_join_commit_cycle():
    bm = KvBlockManager(8, BS)
    hashes = [101, 202]
    # owner allocates fresh and reserves
    a = bm.allocate(hashes, 40)  # 3 blocks
    assert a.cached_tokens == 0 and a.joined_tokens == 0
    assert bm.reserve(hashes[0], a.block_ids[0])
    assert bm.reserve(hashes[1], a.block_ids[1])
    assert not bm.reserve(hashes[0], 7)  # already reserved

    # follower with the same chain joins the owner's in-flight blocks
    b = bm.allocate(hashes, 40)
    assert b.joined_tokens == 2 * BS
    assert b.block_ids[:2] == a.block_ids[:2]
    assert b.block_ids[2] != a.block_ids[2]  # final block stays private

    # commit resolves the reservation and flips block_committed
    assert not bm.block_committed(a.block_ids[0])
    bm.commit(a.block_ids[0], hashes[0], None)
    assert bm.block_committed(a.block_ids[0])
    assert not bm.is_reserved(hashes[0])
    assert bm.is_reserved(hashes[1])

    # owner abort: unresolved reservation dropped, committed one unaffected
    bm.unreserve(hashes[1], a.block_ids[1])
    assert not bm.is_reserved(hashes[1])
    assert bm.lookup(hashes[0]) == a.block_ids[0]


def test_evicted_block_clears_committed_flag():
    bm = KvBlockManager(2, BS)
    a = bm.allocate([11], 20)
    bm.commit(a.block_ids[0], 11, None)
    bm.release(a.block_ids)
    # both blocks get recycled through fresh allocation
    b = bm.allocate([], BS + 1)
    assert all(not bm.block_committed(bid) for bid in b.block_ids)


# ----------------------------------------------------------- engine behavior
def _engine(chunk=0):
    cfg = ModelConfig.tiny()
    model = LlamaModel(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ecfg = EngineConfig(
        max_batch_size=4, max_model_len=256, block_size=BS, num_blocks=64,
        prefill_chunk_tokens=chunk,
        enable_prefix_reuse=True,
    )
    return EngineCore(model, params, ecfg, eos_token_ids=[])


def _req(rid, prompt, sink):
    return EngineRequest(
        request_id=rid, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=4, ignore_eos=True),
        emit=lambda out, rid=rid: sink.setdefault(rid, []).append(out),
    )


def _drain(engine, max_steps=400):
    for _ in range(max_steps):
        if not engine.step() and not engine.has_work():
            break


def test_concurrent_identical_prompts_share_one_prefill():
    engine = _engine()
    sink = {}
    prompt = list(np.random.default_rng(0).integers(1, 200, size=100))
    # n=4 fan-out: what the HTTP service submits for n>1 of one prompt
    for i in range(4):
        engine.submit(_req(f"r{i}", prompt, sink))
    _drain(engine)

    # all four finished with identical greedy continuations
    outs = []
    for i in range(4):
        toks = [t for o in sink[f"r{i}"] for t in o.token_ids]
        assert len(toks) == 4
        outs.append(toks)
    assert all(o == outs[0] for o in outs)

    # followers reported the owner's 6 full blocks (96 tokens) as cached —
    # they joined in-flight blocks instead of prefilling duplicates
    followers_cached = sorted(
        max(o.cached_tokens for o in sink[f"r{i}"]) for i in range(4)
    )
    assert followers_cached == [0, 96, 96, 96]

    # prefill work: ONE full-prompt dispatch (bucket 128) + 3 tail
    # dispatches (≤16 tokens each).  Without dedupe this is 4 full ones.
    assert engine.prefill_steps == 4
    # the real check: total prompt tokens computed ≈ 100 + 3*4, not 400
    assert engine.prompt_tokens_computed <= 100 + 3 * BS


def test_owner_abort_follower_takes_over():
    engine = _engine()
    sink = {}
    prompt = list(range(1, 70))
    engine.submit(_req("owner", prompt, sink))
    engine.submit(_req("follower", prompt, sink))
    # admit both (no dispatch yet): run the admission path only
    engine._admit()
    assert engine.slots[0] is not None and engine.slots[1] is not None
    # owner dies before any chunk commits
    engine.abort("owner")
    _drain(engine)
    toks = [t for o in sink["follower"] for t in o.token_ids]
    assert len(toks) == 4  # follower completed by computing the prompt itself
    finished = [o for o in sink["owner"] if o.finish_reason is not None]
    assert finished and finished[0].finish_reason.value == "cancelled"


def test_joiner_with_longer_prompt_extends_chain():
    engine = _engine(chunk=BS)  # chunked: joiner absorbs progressively
    sink = {}
    base = list(range(1, 65))  # 64 tokens = 4 full blocks
    engine.submit(_req("a", base + [200, 201], sink))
    engine.submit(_req("b", base + [210, 211, 212, 213, 214], sink))
    _drain(engine)
    for rid in ("a", "b"):
        toks = [t for o in sink[rid] for t in o.token_ids]
        assert len(toks) == 4
    # b reused a's 4 shared blocks (64 tokens) once committed
    assert max(o.cached_tokens for o in sink["b"]) == 64

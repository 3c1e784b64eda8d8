"""Admission builds a prompt's block chain once (``EngineCore._admit``,
``tokens.BlockChainMemo``): a prompt that starts as an earlier one did takes
that one's blocks, and what the engine then does - the tokens it samples, the
prefix it finds cached, the KV events it publishes - is what it does with
every chain hashed afresh; a request that waits for blocks keeps its chain.
"""

import jax
import numpy as np
import pytest

from dynamo_tpu.engine import EngineConfig, EngineCore
from dynamo_tpu.engine.request import EngineRequest
from dynamo_tpu.llm.kv.block_manager import NoFreeBlocks
from dynamo_tpu.llm.protocols import SamplingOptions, StopConditions
from dynamo_tpu.models.config import ModelConfig
from dynamo_tpu.models.llama import LlamaModel
from dynamo_tpu.tokens import STRIDE_BLOCKS, BlockChainMemo

BS = 2
SPAN = STRIDE_BLOCKS * BS


@pytest.fixture(scope="module")
def tiny():
    model = LlamaModel(ModelConfig.tiny())
    return model, model.init_params(jax.random.PRNGKey(0))


def make_core(tiny, num_blocks: int) -> EngineCore:
    model, params = tiny
    conf = EngineConfig(max_batch_size=2, max_model_len=512, block_size=BS,
                        num_blocks=num_blocks, prefill_chunk_tokens=128)
    return EngineCore(model, params, conf, eos_token_ids=[])


def request(name: str, prompt, max_tokens: int, out: dict) -> EngineRequest:
    out[name] = []
    return EngineRequest(
        request_id=name, prompt=list(prompt),
        sampling=SamplingOptions(temperature=0.0),
        stops=StopConditions(max_tokens=max_tokens, ignore_eos=True),
        emit=lambda o: out[name].extend(o.token_ids))


def ids(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(1, 250, n)]


def serve_questions(core: EngineCore):
    """Two questions on one document, one after the other: the tokens, the
    cached prefix each found and the KV events, in order."""
    doc = ids(0, 2 * SPAN + 21)
    events, out, reqs = [], {}, []
    core.block_manager.event_sink = events.append
    for name, seed in (("a", 1), ("b", 2)):
        reqs.append(request(name, doc + ids(seed, 30), 6, out))
        core.submit(reqs[-1])
        while core.step():
            pass
    return out, [r.cached_tokens for r in reqs], events, reqs


def test_a_shared_document_is_served_as_with_every_chain_hashed_afresh(tiny):
    core = make_core(tiny, 512)
    assert core._chain_memo.capacity == 512 // STRIDE_BLOCKS
    out, cached, events, reqs = serve_questions(core)
    plain = make_core(tiny, 512)
    plain._chain_memo = BlockChainMemo(0)       # never holds a stride
    assert serve_questions(plain)[:3] == (out, cached, events)
    assert len(out["a"]) == len(out["b"]) == 6 and events
    # the second question found the document's K/V, to the block
    doc_blocks = (2 * SPAN + 21) // BS
    assert cached == [0, doc_blocks * BS]
    # and its chain is the first one's blocks for the two whole strides
    a, b = (r.seq.blocks for r in reqs)
    assert all(x is y for x, y in zip(a[: 2 * STRIDE_BLOCKS], b))
    assert a[2 * STRIDE_BLOCKS] is not b[2 * STRIDE_BLOCKS]
    m, pm = core.metrics(), plain.metrics()
    prompt_blocks = (2 * SPAN + 21 + 30) // BS
    assert m["prompt_blocks_admitted_total"] == 2 * prompt_blocks \
        == pm["prompt_blocks_admitted_total"]
    assert m["prompt_blocks_reused_total"] == 2 * STRIDE_BLOCKS
    assert pm["prompt_blocks_reused_total"] == 0


def test_a_request_waiting_for_blocks_builds_its_chain_once(tiny):
    core = make_core(tiny, 3 * STRIDE_BLOCKS + 8)
    built, refused = [], []
    sequence, allocate = core._chain_memo.sequence, core.block_manager.allocate

    def counting_sequence(prompt, block_size):
        built.append(len(prompt))
        return sequence(prompt, block_size)

    def counting_allocate(hashes, total):
        try:
            return allocate(hashes, total)
        except NoFreeBlocks:
            refused.append(total)
            raise

    core._chain_memo.sequence = counting_sequence
    core.block_manager.allocate = counting_allocate
    out = {}
    # the first holds more than half the pool while it answers; the second
    # (another document) fits only once the first has let go
    first, second = ids(3, 2 * SPAN + 9), ids(4, 2 * SPAN - 7)
    core.submit(request("first", first, 12, out))
    core.submit(request("second", second, 4, out))
    while core.step():
        pass
    assert len(out["first"]) == 12 and len(out["second"]) == 4
    assert len(refused) >= 5 and set(refused) == {len(second)}
    assert built == [len(first), len(second)]
    assert core.metrics()["prompt_blocks_admitted_total"] \
        == len(first) // BS + len(second) // BS

"""What one admission costs the engine's thread, on the host that runs this.

A request on a long shared document (the ``docs-shared-closed`` cells: 16 k /
24 k / 32 k tokens and a 160-token question, block size 32) is admitted by
building its block chain, allocating its blocks on a full prefix hit and,
when it ends, releasing them.  Each is timed alone, the chain three ways:
``TokenBlockSequence`` (every block hashed), through a cold
``BlockChainMemo`` (every stride a miss) and through a warm one (the
document's strides held, the question hashed).  ``strides`` times the warm
build with the memo's stride at 16 ... 256 blocks: what ``STRIDE_BLOCKS``
was chosen by.  No JAX, no device: the numbers are this host's Python.

    chiprun -- python3 benchmarks/probe_admission.py chiprun_out/admission.json
"""

from __future__ import annotations

import json
import os
import random
import statistics
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from dynamo_tpu import tokens as tokens_mod  # noqa: E402
from dynamo_tpu.llm.kv.block_manager import KvBlockManager  # noqa: E402
from dynamo_tpu.tokens import BlockChainMemo, TokenBlockSequence  # noqa: E402

BLOCK_SIZE = 32
NUM_BLOCKS = 12_288
QUESTION = 160
DOCUMENTS = (16_384, 24_576, 32_768)
STRIDES = (16, 32, 64, 128, 256)
REPEATS = 15


def timed_ms(fn) -> tuple[float, object]:
    t = time.perf_counter()
    out = fn()
    return (time.perf_counter() - t) * 1e3, out


def median_ms(fn, prompts) -> float:
    return statistics.median(timed_ms(lambda: fn(p))[0] for p in prompts)


def probe_document(rng: random.Random, doc_len: int) -> dict:
    doc = [rng.randrange(131_072) for _ in range(doc_len)]
    prompts = [doc + [rng.randrange(131_072) for _ in range(QUESTION)]
               for _ in range(REPEATS)]
    out = {"prompt_tokens": doc_len + QUESTION}
    out["plain_ms"] = median_ms(
        lambda p: TokenBlockSequence(p, BLOCK_SIZE), prompts)
    out["memo_miss_ms"] = median_ms(
        lambda p: BlockChainMemo(NUM_BLOCKS // tokens_mod.STRIDE_BLOCKS)
        .sequence(p, BLOCK_SIZE), prompts)
    out["strides"] = {}
    default = tokens_mod.STRIDE_BLOCKS
    try:
        for per in STRIDES:
            tokens_mod.STRIDE_BLOCKS = per
            memo = BlockChainMemo(NUM_BLOCKS // per)
            memo.sequence(prompts[0], BLOCK_SIZE)
            out["strides"][per] = median_ms(
                lambda p: memo.sequence(p, BLOCK_SIZE), prompts[1:])
    finally:
        tokens_mod.STRIDE_BLOCKS = default
    out["memo_hit_ms"] = out["strides"][default]

    # the block manager's share, on a full hit: the document's blocks
    # committed by a first request and let go, then asked for again
    bm = KvBlockManager(NUM_BLOCKS, BLOCK_SIZE)
    first = TokenBlockSequence(prompts[0], BLOCK_SIZE)
    held = bm.allocate(first.sequence_hashes(), len(prompts[0]))
    for bid, blk in zip(held.block_ids, first.blocks):
        bm.commit(bid, blk.sequence_hash, blk.parent_sequence_hash)
    bm.release(held.block_ids)
    allocate, release = [], []
    for p in prompts[1:]:
        hashes = TokenBlockSequence(p, BLOCK_SIZE).sequence_hashes()
        ms, alloc = timed_ms(lambda: bm.allocate(hashes, len(p)))
        assert alloc.cached_tokens == doc_len, alloc.cached_tokens
        allocate.append(ms)
        release.append(timed_ms(lambda: bm.release(alloc.block_ids))[0])
    out["allocate_full_hit_ms"] = statistics.median(allocate)
    out["release_ms"] = statistics.median(release)
    return out


def main(argv: list[str]) -> None:
    rng = random.Random(61)
    report = {"block_size": BLOCK_SIZE, "question_tokens": QUESTION,
              "stride_blocks": tokens_mod.STRIDE_BLOCKS, "repeats": REPEATS,
              "documents": [probe_document(rng, n) for n in DOCUMENTS]}
    text = json.dumps(report, indent=1)
    print(text)
    if argv:
        os.makedirs(os.path.dirname(os.path.abspath(argv[0])), exist_ok=True)
        with open(argv[0], "w") as f:
            f.write(text + "\n")


if __name__ == "__main__":
    main(sys.argv[1:])

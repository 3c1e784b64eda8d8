"""Unit tests for the token-block library (mirrors reference lib/tokens tests)."""

import numpy as np
import pytest

from dynamo_tpu.tokens import (
    STRIDE_BLOCKS,
    BlockChainMemo,
    TokenBlockSequence,
    block_hashes,
    compute_block_hash,
    compute_seq_hash,
    sequence_hashes,
)


def test_block_hash_deterministic():
    a = compute_block_hash([1, 2, 3, 4])
    b = compute_block_hash([1, 2, 3, 4])
    assert a == b
    assert a != compute_block_hash([1, 2, 3, 5])


def test_seq_hash_chains():
    h0 = compute_seq_hash(None, [1, 2, 3, 4])
    h1 = compute_seq_hash(h0, [5, 6, 7, 8])
    # chaining means same tokens under a different parent hash differently
    assert h1 != compute_seq_hash(None, [5, 6, 7, 8])
    # and salt perturbs the root
    assert h0 != compute_seq_hash(None, [1, 2, 3, 4], salt=1)


def test_fast_paths_match_object_path():
    toks = list(range(37))
    seq = TokenBlockSequence(toks, block_size=8)
    assert [b.block_hash for b in seq.blocks] == block_hashes(toks, 8)
    assert seq.sequence_hashes() == sequence_hashes(toks, 8)


def test_shared_prefix_shares_hashes():
    a = sequence_hashes(list(range(32)) + [100 + t for t in range(8)], 8)
    b = sequence_hashes(list(range(32)) + [200 + t for t in range(8)], 8)
    assert a[:4] == b[:4]
    assert a[4] != b[4]


def test_sequence_append_and_partial():
    seq = TokenBlockSequence(block_size=4)
    completed = []
    for t in range(10):
        blk = seq.append(t)
        if blk is not None:
            completed.append(blk)
    assert len(completed) == 2
    assert len(seq.blocks) == 2
    assert seq.partial.tokens == [8, 9]
    assert seq.total_tokens == 10
    assert seq.tokens == list(range(10))
    assert seq.blocks[0].position == 0
    assert seq.blocks[1].parent_sequence_hash == seq.blocks[0].sequence_hash


def test_truncate():
    seq = TokenBlockSequence(range(20), block_size=4)
    hashes = seq.sequence_hashes()
    seq.truncate(10)
    assert seq.total_tokens == 10
    assert len(seq.blocks) == 2
    assert seq.sequence_hashes() == hashes[:2]
    with pytest.raises(ValueError):
        seq.truncate(11)


def test_extend_returns_completed():
    seq = TokenBlockSequence(block_size=4)
    done = seq.extend(range(9))
    assert len(done) == 2
    assert seq.partial.tokens == [8]


# --------------------------------------------------------- the chain memo
BS = 4
SPAN = STRIDE_BLOCKS * BS      # tokens a stride of the memo covers


def _doc(seed: int, n: int) -> list[int]:
    return [int(t) for t in np.random.RandomState(seed).randint(0, 50_000, n)]


def _same(got: TokenBlockSequence, prompt, block_size=BS, salt=0) -> None:
    """``got`` is the plain sequence in every field of every block."""
    plain = TokenBlockSequence(prompt, block_size, salt)
    assert got.blocks == plain.blocks      # a frozen dataclass: all five fields
    assert [b.position for b in got.blocks] == list(range(len(got.blocks)))
    assert got.partial.tokens == plain.partial.tokens
    assert got.tokens == list(prompt)
    assert (got.block_size, got.salt) == (block_size, salt)


def _first_build_all_misses():
    memo = BlockChainMemo(8)
    prompt = _doc(0, 3 * SPAN + 37)
    seq, reused = memo.sequence(prompt, BS)
    _same(seq, prompt)
    assert reused == 0 and len(memo) == 3


def _second_build_all_hits():
    memo = BlockChainMemo(8)
    prompt = _doc(0, 3 * SPAN + 37)
    first, _ = memo.sequence(prompt, BS)
    seq, reused = memo.sequence(prompt, BS)
    _same(seq, prompt)
    assert reused == 3 * STRIDE_BLOCKS and len(memo) == 3
    # the blocks themselves, not copies, in a list of the sequence's own
    assert all(a is b for a, b in zip(seq.blocks[:reused], first.blocks))
    assert seq.blocks is not first.blocks


def _same_document_another_question():
    memo = BlockChainMemo(8)
    doc = _doc(1, 2 * SPAN + 2 * BS)
    memo.sequence(doc + _doc(2, 41), BS)
    prompt = doc + _doc(3, 97)
    seq, reused = memo.sequence(prompt, BS)
    _same(seq, prompt)
    assert reused == 2 * STRIDE_BLOCKS


def _shorter_than_a_stride():
    memo = BlockChainMemo(8)
    prompt = _doc(4, SPAN - 1)
    for _ in range(2):
        seq, reused = memo.sequence(prompt, BS)
        _same(seq, prompt)
        assert reused == 0 and len(memo) == 0


def _exactly_n_strides():
    memo = BlockChainMemo(8)
    prompt = _doc(5, 2 * SPAN)
    memo.sequence(prompt, BS)
    seq, reused = memo.sequence(prompt, BS)
    _same(seq, prompt)
    assert reused == 2 * STRIDE_BLOCKS and seq.partial.tokens == []


def _two_documents_diverge_mid_stride():
    memo = BlockChainMemo(8)
    a = _doc(6, 3 * SPAN + 5)
    b = a[: SPAN + SPAN // 2] + _doc(7, SPAN + SPAN // 2 + 9)
    memo.sequence(a, BS)
    seq, reused = memo.sequence(b, BS)
    _same(seq, b)
    assert reused == STRIDE_BLOCKS      # the stride they part in is b's own
    again, reused = memo.sequence(b, BS)
    _same(again, b)
    assert reused == 3 * STRIDE_BLOCKS
    seq, reused = memo.sequence(a, BS)  # and a's chain is still a's
    _same(seq, a)
    assert reused == 3 * STRIDE_BLOCKS


def _another_salt_finds_nothing():
    memo = BlockChainMemo(8)
    prompt = _doc(8, 2 * SPAN + 3)
    memo.sequence(prompt, BS)
    seq, reused = memo.sequence(prompt, BS, salt=7)
    _same(seq, prompt, salt=7)
    assert reused == 0


def _another_block_size_finds_nothing():
    memo = BlockChainMemo(8)
    prompt = _doc(9, 4 * SPAN + 3)
    memo.sequence(prompt, BS)
    seq, reused = memo.sequence(prompt, 2 * BS)
    _same(seq, prompt, block_size=2 * BS)
    assert reused == 0
    seq, reused = memo.sequence(prompt, BS)
    _same(seq, prompt)
    assert reused == 4 * STRIDE_BLOCKS


def _edits_leave_a_sharer_alone():
    memo = BlockChainMemo(8)
    prompt = _doc(10, 2 * SPAN + BS - 1)
    memo.sequence(prompt, BS)
    one, _ = memo.sequence(prompt, BS)
    two, _ = memo.sequence(prompt, BS)
    assert one.last_token == prompt[-1]
    done = one.append(11)
    assert done is not None and done.position == 2 * STRIDE_BLOCKS
    one.append(12)
    _same(one, prompt + [11, 12])
    assert one.last_token == 12
    one.truncate(SPAN + 5)
    _same(one, prompt[: SPAN + 5])
    _same(two, prompt)
    seq, reused = memo.sequence(prompt, BS)     # nor the memo's strides
    _same(seq, prompt)
    assert reused == 2 * STRIDE_BLOCKS


def _eviction_at_capacity():
    memo = BlockChainMemo(2)
    a, b = _doc(11, 2 * SPAN + 1), _doc(12, 2 * SPAN + 1)
    memo.sequence(a, BS)
    seq, reused = memo.sequence(b, BS)          # b's two strides push a's out
    _same(seq, b)
    assert reused == 0 and len(memo) == 2
    seq, reused = memo.sequence(a, BS)          # rebuilt, equal again
    _same(seq, a)
    assert reused == 0 and len(memo) == 2
    seq, reused = memo.sequence(a, BS)
    _same(seq, a)
    assert reused == 2 * STRIDE_BLOCKS
    none = BlockChainMemo(0)                    # a pool under a stride's size
    for _ in range(2):
        seq, reused = none.sequence(a, BS)
        _same(seq, a)
        assert reused == 0 and len(none) == 0


@pytest.mark.parametrize("case", [
    _first_build_all_misses, _second_build_all_hits,
    _same_document_another_question, _shorter_than_a_stride,
    _exactly_n_strides, _two_documents_diverge_mid_stride,
    _another_salt_finds_nothing, _another_block_size_finds_nothing,
    _edits_leave_a_sharer_alone, _eviction_at_capacity,
], ids=lambda f: f.__name__.lstrip("_"))
def test_a_sequence_built_through_the_memo_is_the_plain_one(case):
    case()


def test_the_least_recently_used_stride_goes_first():
    memo = BlockChainMemo(3)
    a, b = _doc(13, 2 * SPAN), _doc(14, SPAN)
    memo.sequence(a, BS)
    memo.sequence(b, BS)
    memo.sequence(a, BS)                        # a's strides are the newest
    memo.sequence(_doc(15, SPAN), BS)           # so b's goes
    assert memo.sequence(a, BS)[1] == 2 * STRIDE_BLOCKS
    assert memo.sequence(b, BS)[1] == 0

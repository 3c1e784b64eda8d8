"""Token-block sequences with chained content hashes.

This is the foundation the whole KV-routing scheme rests on: a prompt is
split into fixed-size blocks of token ids; each block gets

  * a ``block_hash``    — hash of the block's tokens alone, and
  * a ``sequence_hash`` — chained hash of (parent sequence_hash, tokens),

so that two requests sharing a prefix produce identical sequence hashes for
the shared blocks.  Workers publish {stored, removed} events keyed by
sequence hash; the router's radix tree matches incoming prompts against them.

Reference parity: lib/tokens/src/lib.rs:44-300 (Tokens, TokenBlock,
PartialTokenBlock, TokenBlockSequence, xxh3 chained hashing with salt) and
lib/llm/src/kv_router/indexer.rs:99 (compute_block_hash, seed 1337).

Design notes (TPU rebuild): hashing is plain xxh3-64 over little-endian
u32 token bytes, chained through a u64 parent hash.  This is pure-Python +
xxhash (C speed); block hashing of a full prompt is vectorised via a single
pass over a memoryview, not per-token Python loops.
"""

from __future__ import annotations

from array import array
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Iterable, Optional, Sequence

import numpy as np
import xxhash

# Same seed the reference pins (lib/llm/src/kv_router/indexer.rs:64) so that
# recorded event streams hash identically across implementations.
BLOCK_HASH_SEED = 1337

__all__ = [
    "BLOCK_HASH_SEED",
    "compute_hash",
    "compute_block_hash",
    "compute_seq_hash",
    "block_hashes",
    "sequence_hashes",
    "TokenBlock",
    "PartialTokenBlock",
    "TokenBlockSequence",
    "STRIDE_BLOCKS",
    "BlockChainMemo",
]


def _tokens_to_bytes(tokens: Sequence[int]) -> bytes:
    return np.asarray(tokens, dtype=np.uint32).tobytes()


def compute_hash(data: bytes, seed: int = BLOCK_HASH_SEED) -> int:
    """xxh3-64 of raw bytes (reference: lib/tokens/src/lib.rs:44)."""
    return xxhash.xxh3_64_intdigest(data, seed=seed)


def compute_block_hash(tokens: Sequence[int]) -> int:
    """Hash of a block's tokens alone (local hash, no chaining)."""
    return compute_hash(_tokens_to_bytes(tokens))


def compute_seq_hash(parent: Optional[int], tokens: Sequence[int], salt: int = 0) -> int:
    """Chained sequence hash.

    The root block mixes in ``salt`` (lets a deployment partition its cache
    space, reference lib/tokens/src/lib.rs:277); children mix in the parent's
    sequence hash.
    """
    if parent is None:
        prefix = np.uint64(salt).tobytes()
    else:
        prefix = np.uint64(parent).tobytes()
    return compute_hash(prefix + _tokens_to_bytes(tokens))


def block_hashes(tokens: Sequence[int], block_size: int) -> list[int]:
    """Local hashes for each *complete* block of ``tokens``."""
    toks = np.asarray(tokens, dtype=np.uint32)
    n_full = len(toks) // block_size
    raw = toks[: n_full * block_size].tobytes()
    bs = block_size * 4
    return [compute_hash(raw[i * bs : (i + 1) * bs]) for i in range(n_full)]


def sequence_hashes(tokens: Sequence[int], block_size: int, salt: int = 0) -> list[int]:
    """Chained sequence hashes for each complete block — the fast path used
    by the router on every request (no TokenBlock object churn)."""
    toks = np.asarray(tokens, dtype=np.uint32)
    n_full = len(toks) // block_size
    out: list[int] = []
    parent: Optional[int] = None
    raw = toks[: n_full * block_size].tobytes()
    bs = block_size * 4
    for i in range(n_full):
        chunk = raw[i * bs : (i + 1) * bs]
        prefix = np.uint64(salt if parent is None else parent).tobytes()
        parent = compute_hash(prefix + chunk)
        out.append(parent)
    return out


@dataclass(frozen=True)
class TokenBlock:
    """An immutable, complete block of ``block_size`` token ids."""

    tokens: tuple[int, ...]
    block_hash: int
    sequence_hash: int
    parent_sequence_hash: Optional[int]
    position: int  # block index within its sequence

    @staticmethod
    def build(
        tokens: Sequence[int],
        parent: Optional["TokenBlock"],
        position: int,
        salt: int = 0,
    ) -> "TokenBlock":
        parent_hash = parent.sequence_hash if parent is not None else None
        return TokenBlock(
            tokens=tuple(int(t) for t in tokens),
            block_hash=compute_block_hash(tokens),
            sequence_hash=compute_seq_hash(parent_hash, tokens, salt),
            parent_sequence_hash=parent_hash,
            position=position,
        )


@dataclass
class PartialTokenBlock:
    """Mutable tail block being filled (reference lib/tokens/src/lib.rs:221)."""

    block_size: int
    tokens: list[int] = field(default_factory=list)

    @property
    def remaining(self) -> int:
        return self.block_size - len(self.tokens)

    def push(self, token: int) -> bool:
        """Append one token; returns True when the block became full."""
        if self.remaining <= 0:
            raise ValueError("pushing into a full partial block")
        self.tokens.append(int(token))
        return self.remaining == 0


class TokenBlockSequence:
    """A growing token sequence maintaining complete blocks + a partial tail.

    Reference parity: lib/tokens/src/lib.rs:300 (TokenBlockSequence).
    Supports O(1) append (per token), bulk extend, and truncate — the ops the
    engine's request state machine needs while decoding.
    """

    def __init__(self, tokens: Iterable[int] = (), block_size: int = 16, salt: int = 0):
        if block_size <= 0:
            raise ValueError("block_size must be positive")
        self.block_size = block_size
        self.salt = salt
        self.blocks: list[TokenBlock] = []
        self.partial = PartialTokenBlock(block_size)
        self.extend(tokens)

    # ------------------------------------------------------------------ state
    @property
    def total_tokens(self) -> int:
        return len(self.blocks) * self.block_size + len(self.partial.tokens)

    @property
    def tokens(self) -> list[int]:
        out: list[int] = []
        for b in self.blocks:
            out.extend(b.tokens)
        out.extend(self.partial.tokens)
        return out

    def sequence_hashes(self) -> list[int]:
        return [b.sequence_hash for b in self.blocks]

    # ---------------------------------------------------------------- updates
    def append(self, token: int) -> Optional[TokenBlock]:
        """Append one token; returns the newly completed block, if any."""
        if self.partial.push(token):
            parent = self.blocks[-1] if self.blocks else None
            block = TokenBlock.build(
                self.partial.tokens, parent, position=len(self.blocks), salt=self.salt
            )
            self.blocks.append(block)
            self.partial = PartialTokenBlock(self.block_size)
            return block
        return None

    @property
    def last_token(self) -> int:
        """The newest token, without rebuilding the whole list (``tokens``
        copies every block: a decode step over a 30 k-token context asks
        for one)."""
        if self.partial.tokens:
            return self.partial.tokens[-1]
        return self.blocks[-1].tokens[-1]

    def extend(self, tokens: Iterable[int]) -> list[TokenBlock]:
        """Append many tokens; returns all blocks completed by this call.
        Whole blocks are hashed from one buffer of the tokens' bytes (as
        ``sequence_hashes`` does), not pushed token by token: admitting a
        30 k-token prompt is a thousand hashes, not thirty thousand Python
        calls on the engine's thread.  Same hashes as ``append``.  A few
        tokens (a decode step's one) take ``append``: nothing to batch."""
        if not isinstance(tokens, (list, tuple)):
            tokens = list(tokens)
        bs = self.block_size
        if len(tokens) < bs:
            return [b for b in map(self.append, tokens) if b is not None]
        buf = self.partial.tokens + [int(t) for t in tokens]
        n_full = len(buf) // bs
        completed: list[TokenBlock] = []
        if n_full:
            raw = np.asarray(buf[: n_full * bs], dtype=np.uint32).tobytes()
            parent = self.blocks[-1].sequence_hash if self.blocks else None
            for i in range(n_full):
                chunk = raw[i * bs * 4 : (i + 1) * bs * 4]
                prefix = np.uint64(
                    self.salt if parent is None else parent).tobytes()
                block = TokenBlock(
                    tokens=tuple(buf[i * bs : (i + 1) * bs]),
                    block_hash=compute_hash(chunk),
                    sequence_hash=compute_hash(prefix + chunk),
                    parent_sequence_hash=parent,
                    position=len(self.blocks),
                )
                self.blocks.append(block)
                completed.append(block)
                parent = block.sequence_hash
        self.partial = PartialTokenBlock(bs, buf[n_full * bs :])
        return completed

    def truncate(self, n_tokens: int) -> None:
        """Shrink the sequence to its first ``n_tokens`` tokens."""
        if n_tokens > self.total_tokens or n_tokens < 0:
            raise ValueError("truncate out of range")
        toks = self.tokens[:n_tokens]
        self.blocks = []
        self.partial = PartialTokenBlock(self.block_size)
        self.extend(toks)

    def __len__(self) -> int:
        return self.total_tokens

    def __repr__(self) -> str:  # pragma: no cover
        return (
            f"TokenBlockSequence(blocks={len(self.blocks)}, "
            f"partial={len(self.partial.tokens)}/{self.block_size})"
        )


# Blocks a stride of the chain memo covers.  At the serving block size of 32
# that is 2,048 tokens: a long prompt is a few to sixteen strides (a lookup
# each), and a shared prefix is found to within a stride of its end.  A
# smaller stride finds a little more and looks up more; 64 is where the
# hit's cost stops falling (benchmarks/probe_admission.py).
STRIDE_BLOCKS = 64


class BlockChainMemo:
    """The block chains of prompts already built, by strides of
    ``STRIDE_BLOCKS`` blocks, so that a prompt which starts as an earlier one
    did takes that one's ``TokenBlock``s instead of hashing them again (a
    document asked many questions: ~770 blocks, two xxh3 and a tuple each).

    Stride j's key is xxh3-64 of the key of stride j - 1 (salt and block size
    for the first) and the stride's raw token bytes, so a key names the whole
    prefix up to the stride's end, its salt and its block size.  A held key
    yields the stride's blocks, which are frozen and shared between
    sequences; the first stride that misses and everything after it go
    through ``TokenBlockSequence.extend``, and the whole strides so built
    are kept.  Equal 64-bit keys are taken for equal prefixes with no second
    look at the tokens: the trust the prefix cache already puts in
    ``sequence_hash`` when it hands one request another's K/V.

    At most ``capacity`` strides are held, the least recently used dropped
    first.  One owner, one thread (the engine's): no lock.
    """

    def __init__(self, capacity: int):
        self.capacity = capacity
        self._strides: OrderedDict[int, tuple[TokenBlock, ...]] = OrderedDict()

    def __len__(self) -> int:
        return len(self._strides)

    def sequence(
        self, tokens: Sequence[int], block_size: int, salt: int = 0
    ) -> tuple[TokenBlockSequence, int]:
        """``TokenBlockSequence(tokens, block_size, salt)``, equal in every
        field of every block, and how many of its blocks the memo supplied."""
        seq = TokenBlockSequence(block_size=block_size, salt=salt)
        per = STRIDE_BLOCKS
        span = per * block_size
        n_strides = len(tokens) // span if self.capacity > 0 else 0
        if n_strides == 0:      # a chat prompt: nothing to look up or keep
            seq.extend(tokens)
            return seq, 0
        raw = array("I", tokens).tobytes()
        keys: list[int] = []
        key = compute_hash(np.array([salt, block_size], np.uint64).tobytes())
        for j in range(n_strides):
            key = compute_hash(
                np.uint64(key).tobytes() + raw[j * span * 4 : (j + 1) * span * 4])
            keys.append(key)
        held = self._strides
        hits = 0
        while hits < n_strides and keys[hits] in held:
            held.move_to_end(keys[hits])
            seq.blocks.extend(held[keys[hits]])
            hits += 1
        seq.extend(tokens[hits * span :])
        for j in range(hits, n_strides):
            held[keys[j]] = tuple(seq.blocks[j * per : (j + 1) * per])
            held.move_to_end(keys[j])
        while len(held) > self.capacity:
            held.popitem(last=False)
        return seq, hits * per

"""The latent-attention decoder family with routed experts of which this chip
holds a share: GLM-5.2 (``glm_moe_dsa``: a learned sparse-attention indexer
shared between layers, sigmoid routing with a correction bias) and
Mistral-Small-4 (``mistral4``: no indexer, every layer attends to every
cached row, YaRN with a position-dependent query scale, softmax routing, no
dense layer; docs/mistral4_mla.md).

Per layer (pre-norm residual blocks, docs/glm_dsa.md has the equations):

  * **Latent attention**, absorbed form: the cache holds one row a token
    (c_kv ‖ roped k_pe) *once* (ops/latent_cache.py), queries are taken into
    latent space through kv_b's K half, the attended latent comes back
    through its V half.
  * **Indexer**, in layers whose ``indexer_types`` entry is ``full``: a small
    multi-head scorer over a cache of its own keys picks, for every query
    token, the ``index_topk`` positions it attends to.  The scores take one
    of two forms, by what the call can observe: a decode step on the TPU
    (one query a row) reads the keys where they lie, through the block
    table, a document's keys once for the rows that ask it
    (``ops/pallas/dsa_index_scores.py``: no copy of the table's keys); a
    prefill chunk, and every call where the kernels are off, gathers its
    context's keys and scores them in XLA (``index_scores``, the kernel's
    oracle).  A ``shared`` layer
    has no indexer and attends to the selection of the nearest ``full``
    layer before it.  A ``none`` layer has no indexer either and attends to
    every cached row (``latent_cache.dense_attention``: two Pallas kernels
    over whole blocks on the TPU); a stack is of ``none`` layers only or of
    none of them, because the two read different cache layouts.
  * Attention over a selection takes one of two forms, chosen statically by
    the call's shape (``attends_masked``).  In a decode step, and in a batch
    of several sequences, each query *gathers* its rows:
    ``sparse_latent_attention``, a Pallas kernel on the TPU.  A prefill
    chunk of one sequence — a document's 2,048 tokens, a question's 64-256
    after a prefix hit — scores every key of the context once for all its
    queries and masks what was not selected
    (``latent_cache.masked_attention``): the same result, by the matrix
    unit, wherever a tile of 16 queries walking the context costs less than
    their 16 x 2,048 one-row fetches (up to ~60 k positions on one v5e:
    ``registry.masked_prefill_is_cheaper``).
  * **Experts**: the router scores all ``router_experts``; this chip
    computes the part of the sum its own ``n_routed_experts`` give
    (``grouped_expert_dispatch(held=...)``), plus the shared expert.  What
    the other chips of the expert-parallel group would add is left out: no
    code stands in for them.

The stack has up to six kinds of layer (dense or expert MLP × ``full``,
``shared`` or ``none`` indexer).  Parameters are stacked per kind, and each run of
consecutive layers of one kind is one ``lax.scan``; expert weights are read
where they lie (the layer-indexed form of ``grouped_expert_dispatch``).

One chip only: no partition specs.  Block movers (host pool, persistent
store, streamed or remote prefill) know neither layout of the latent cache,
and the engine refuses them for this model at start-up
(``private_cache_layout``).
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.models.deepseek import (
    apply_rope_interleaved,
    moe_route,
    routing_groups,
)
from dynamo_tpu.models.llama import (
    EXPERT_COUNT_KEYS,
    EXPERT_COUNTS,
    experts_touched,
    grouped_expert_dispatch,
    rms_norm,
    rope_inv_freq,
    split_heads,
    yarn_inv_freq,
    yarn_mscale,
)
from dynamo_tpu.ops import latent_cache
from dynamo_tpu.ops.paged_attention import (
    sparse_attention_impl,
    sparse_latent_attention,
)
from dynamo_tpu.ops.pallas.registry import (
    MLA_MASKED_KEYS_PER_TILE,
    masked_prefill_is_cheaper,
)

Params = Any

__all__ = ["GlmDsaConfig", "GlmDsaModel", "ROUTER_BIAS_STD",
           "kth_largest", "index_scores", "select_mask", "selected_slots",
           "latent_params", "latent_projections", "latent_values",
           "dense_attention_impls"]

# queries scored at a time by the indexer ([tile, heads, context] in f32)
INDEX_QUERY_TILE = 64
# queries whose selection is compacted at a time ([tile, topk, context/128])
COMPACT_QUERY_TILE = 32
INDEX_NORM_EPS = 1e-6
# standard deviation of the seeded e_score_correction_bias.  The eight
# largest of 256 sigmoid scores lie ~0.02 apart, so a bias of 0.1 chose the
# same few experts for every token (the busiest took 10x its share, and the
# experts held here 0.4-2x theirs by the seed: decode time moved with it);
# at 0.01 the bias still changes about one choice in eleven and the load
# stays as even as the trained bias is there to keep it
ROUTER_BIAS_STD = 0.01
NEG_INF = -jnp.inf


def _context_blocks(s: int, table_blocks: int, block_size: int,
                    prefix_blocks) -> int:
    """Blocks of the table a call of ``s`` tokens a sequence reads: the
    static ``prefix_blocks`` cached ones and its own; None: all."""
    if prefix_blocks is None:
        return table_blocks
    return min(table_blocks, prefix_blocks + -(-s // block_size))


@dataclass
class GlmDsaConfig:
    vocab_size: int
    hidden_size: int
    num_layers: int
    num_heads: int
    qk_nope_head_dim: int
    qk_rope_head_dim: int
    v_head_dim: int
    kv_lora_rank: int
    q_lora_rank: int | None        # None: W_q direct, no bottleneck, no norm
    intermediate_size: int
    moe_intermediate_size: int
    n_routed_experts: int          # experts held HERE
    router_experts: int            # experts the router chooses among
    expert_first: int              # index of the first expert held here
    num_experts_per_tok: int
    n_shared_experts: int
    routed_scaling_factor: float
    norm_topk_prob: bool
    index_n_heads: int             # the three are 0 without an indexer
    index_head_dim: int
    index_topk: int
    indexer_types: tuple           # per layer: "full" | "shared" | "none"
    mlp_layer_types: tuple         # per layer: "dense" | "sparse"
    scoring_func: str = "sigmoid"
    topk_method: str = "noaux_tc"
    n_group: int = 1               # routing groups (``noaux_tc``: V3's)
    topk_group: int = 1
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    # YaRN: {"factor", "beta_fast", "beta_slow", "mscale_all_dim",
    # "original_max_position_embeddings"}; None: plain RoPE
    yarn: dict | None = None
    # query scale 1 + beta·ln(1 + floor(position / original context))
    query_scale_beta: float = 0.0
    max_position_embeddings: int = 4096
    dtype: str = "bfloat16"

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    # ---- engine-facing surface (duck-typed like ModelConfig) ----
    @property
    def num_kv_heads(self) -> int:
        return 1

    @property
    def head_dim(self) -> int:
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def jax_dtype(self):
        return {"bfloat16": jnp.bfloat16, "float32": jnp.float32}[self.dtype]

    @property
    def full_layers(self) -> int:
        return sum(t == "full" for t in self.indexer_types)

    @property
    def indexed(self) -> bool:
        """Whether attention reads a selection (False: every cached row)."""
        return "none" not in self.indexer_types

    @classmethod
    def from_hf_config(cls, cfg: dict, dtype: str = "bfloat16"
                       ) -> "GlmDsaConfig":
        """The published ``glm_moe_dsa`` or ``mistral4`` keys ->
        GlmDsaConfig.  Raises on what this port does not compute, rather
        than computing something else in silence.  A config with no
        ``indexer_types`` and no ``index_topk`` has no indexer: every
        layer is ``none``.  ``mistral4`` states neither ``scoring_func`` nor
        ``topk_method``: softmax over all experts and the k largest, the
        Mistral family's convention.  ``expert_parallel`` (not a published key) says
        which share of a layer's experts this chip holds:
        ``{"router_experts": 256, "first_expert": 0}`` beside
        ``n_routed_experts`` = the number held."""
        g = cfg.get
        n = int(g("num_hidden_layers"))
        types = tuple(g("indexer_types") or ())
        if not types and g("index_topk") is None:
            types = ("none",) * n
        mistral4 = g("model_type") == "mistral4"
        scoring = g("scoring_func", "softmax" if mistral4 else "sigmoid")
        method = g("topk_method", "greedy" if mistral4 else "noaux_tc")
        mlps = tuple(g("mlp_layer_types") or (
            ["dense"] * int(g("first_k_dense_replace", 0))
            + ["sparse"] * (n - int(g("first_k_dense_replace", 0)))))
        if len(types) != n or len(mlps) != n:
            raise ValueError(
                f"indexer_types ({len(types)}) and mlp_layer_types "
                f"({len(mlps)}) must name each of the {n} layers")
        if (set(types) - {"full", "shared", "none"}
                or set(mlps) - {"dense", "sparse"}):
            raise NotImplementedError(
                f"layer types {sorted(set(types) | set(mlps))}")
        if "none" in types and set(types) != {"none"}:
            raise NotImplementedError(
                "layers with and without an indexer in one stack: they "
                "read different layouts of the latent cache")
        if types[0] == "shared":
            raise ValueError("the first layer has no index to share")
        if scoring not in ("sigmoid", "softmax"):
            raise NotImplementedError(f"scoring_func {scoring!r}")
        if method not in ("noaux_tc", "greedy"):
            raise NotImplementedError(f"topk_method {method!r}")
        if int(g("n_group", 1) or 1) > 1 and method != "noaux_tc":
            raise NotImplementedError(
                f"group-limited expert choice under topk_method {method!r} "
                "(noaux_tc's is computed)")
        q_lora = g("q_lora_rank")
        if q_lora is None and "full" in types:
            raise NotImplementedError(
                "q_lora_rank null with an indexer (its queries are made "
                "from the bottleneck's output)")
        if bool(g("attention_bias", False)):
            raise NotImplementedError("attention_bias=True")
        if bool(g("mlp_bias", False)):
            raise NotImplementedError("mlp_bias=True")
        if g("sliding_window") is not None:
            raise NotImplementedError("sliding_window for this family")
        if g("hidden_act", "silu") != "silu":
            raise NotImplementedError(f"hidden_act {g('hidden_act')!r}")
        if int(g("moe_layer_freq", 1)) != 1:
            raise NotImplementedError("moe_layer_freq != 1")
        if not g("rope_interleave", True) or not g(
                "indexer_rope_interleave", True):
            raise NotImplementedError("rotate-half RoPE for this family")
        if g("index_topk_pattern") is not None:
            raise NotImplementedError("index_topk_pattern")
        if bool(g("tie_word_embeddings", False)):
            raise NotImplementedError("tie_word_embeddings=True")
        rope = g("rope_parameters") or {}
        yarn, beta = _read_rope_scaling(rope, g("rope_scaling"))
        ep = g("expert_parallel") or {}
        held = int(g("n_routed_experts"))
        total = int(ep.get("router_experts", held))
        first = int(ep.get("first_expert", 0))
        if not 0 <= first <= total - held:
            raise ValueError(
                f"experts {first}..{first + held - 1} are not among the "
                f"router's {total}")
        return cls(
            vocab_size=int(g("vocab_size")), hidden_size=int(g("hidden_size")),
            num_layers=n, num_heads=int(g("num_attention_heads")),
            qk_nope_head_dim=int(g("qk_nope_head_dim")),
            qk_rope_head_dim=int(g("qk_rope_head_dim")),
            v_head_dim=int(g("v_head_dim")),
            kv_lora_rank=int(g("kv_lora_rank")),
            q_lora_rank=None if q_lora is None else int(q_lora),
            intermediate_size=int(g("intermediate_size")),
            moe_intermediate_size=int(g("moe_intermediate_size")),
            n_routed_experts=held, router_experts=total, expert_first=first,
            num_experts_per_tok=int(g("num_experts_per_tok")),
            n_shared_experts=int(g("n_shared_experts", 1)),
            routed_scaling_factor=float(g("routed_scaling_factor", 1.0)),
            norm_topk_prob=bool(g("norm_topk_prob", True)),
            index_n_heads=int(g("index_n_heads", 0)),
            index_head_dim=int(g("index_head_dim", 0)),
            index_topk=int(g("index_topk", 0)),
            indexer_types=types, mlp_layer_types=mlps,
            scoring_func=scoring, topk_method=method,
            **routing_groups(cfg, total),
            rms_norm_eps=float(g("rms_norm_eps", 1e-5)),
            rope_theta=float(rope.get("rope_theta", g("rope_theta", 10000.0))),
            yarn=yarn, query_scale_beta=beta,
            max_position_embeddings=int(g("max_position_embeddings", 4096)),
            dtype=dtype,
        )


_YARN_KEYS = {"rope_type", "type", "rope_theta", "factor", "beta_fast",
              "beta_slow", "mscale", "mscale_all_dim",
              "original_max_position_embeddings", "llama_4_scaling_beta"}


def _read_rope_scaling(rope: dict, legacy) -> tuple[dict | None, float]:
    """(the YaRN block, the query scale's beta) of ``rope_parameters``.
    YaRN is read one way only — ``mscale`` equal to ``mscale_all_dim`` (so
    cos and sin keep their size and the softmax scale takes m², the
    DeepSeek-V3 convention whose keys these are), the trained context
    stated, ``truncate`` left at true — and every other scaling raises."""
    kind = rope.get("rope_type", rope.get("type", "default"))
    if legacy or kind not in ("default", "yarn"):
        raise NotImplementedError(
            f"RoPE scaling {kind if not legacy else legacy!r} for this "
            "family (YaRN under rope_parameters is read)")
    if kind == "default":
        if rope.get("llama_4_scaling_beta"):
            raise NotImplementedError(
                "llama_4_scaling_beta without YaRN's trained context")
        return None, 0.0
    if set(rope) - _YARN_KEYS:
        raise NotImplementedError(
            f"YaRN keys {sorted(set(rope) - _YARN_KEYS)}")
    if "original_max_position_embeddings" not in rope or "factor" not in rope:
        raise NotImplementedError(
            "YaRN without factor and original_max_position_embeddings")
    if rope.get("mscale", 1) != rope.get("mscale_all_dim", 0):
        raise NotImplementedError(
            f"YaRN with mscale {rope.get('mscale', 1)} != mscale_all_dim "
            f"{rope.get('mscale_all_dim', 0)}")
    yarn = {
        "factor": float(rope["factor"]),
        "beta_fast": float(rope.get("beta_fast", 32)),
        "beta_slow": float(rope.get("beta_slow", 1)),
        "mscale_all_dim": float(rope["mscale_all_dim"]),
        "original_max_position_embeddings": int(
            rope["original_max_position_embeddings"]),
    }
    return yarn, float(rope.get("llama_4_scaling_beta", 0.0))


def kth_largest(x: jax.Array, k) -> jax.Array:
    """The k-th largest value of each row of ``x`` [..., C] (f32; ``k`` an
    int or an int array [..., 1], one k a row), exactly,
    as [..., 1]; the smallest value where a row has fewer than k.  A radix
    select on the order-preserving integer image of the floats: 32 passes
    of compare-and-count, no sort.  (−0.0 orders below +0.0 here.)"""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    keys = jax.lax.bitcast_convert_type(
        bits ^ ((bits >> 31) & jnp.int32(0x7FFFFFFF)), jnp.uint32
    ) ^ jnp.uint32(0x80000000)

    def one(i, best):
        cand = best | (jnp.uint32(1) << (jnp.uint32(31) - i.astype(jnp.uint32)))
        enough = jnp.sum(keys >= cand, axis=-1, keepdims=True) >= k
        return jnp.where(enough, cand, best)

    best = jax.lax.fori_loop(
        0, 32, one, jnp.zeros((*x.shape[:-1], 1), jnp.uint32))
    signed = jax.lax.bitcast_convert_type(
        best ^ jnp.uint32(0x80000000), jnp.int32)
    return jax.lax.bitcast_convert_type(
        signed ^ ((signed >> 31) & jnp.int32(0x7FFFFFFF)), jnp.float32)


def select_mask(scores: jax.Array, seen: jax.Array, k: int) -> jax.Array:
    """bool [..., C]: exactly the positions ``lax.top_k(scores, k)`` would
    pick among the ``seen`` ones — those above the k-th score and, of those
    equal to it, the earliest — all of them where fewer than k are seen.
    ``scores`` is -inf where not seen.  Two radix selects, no sort."""
    at = jnp.arange(scores.shape[-1], dtype=jnp.float32)
    kth = kth_largest(scores, k)
    above, tie = scores > kth, scores == kth
    room = k - above.sum(axis=-1, keepdims=True)
    # the ``room`` earliest of the tied: the room-th smallest position among
    # them is minus the room-th largest of the negated positions
    last = -kth_largest(jnp.where(tie, -at, NEG_INF), room)
    return seen & (above | (tie & (at <= last)))


def _bytes_of(x: jax.Array, n: int) -> jax.Array:
    """int32 [..., C] (values in [0, 256**n)) -> bf16 [..., n * C]: the
    values' n bytes, low first, each exact in bf16."""
    return jnp.concatenate(
        [((x >> (8 * i)) & 0xFF).astype(jnp.bfloat16) for i in range(n)],
        axis=-1)


def _compact(sel: jax.Array, k: int, payload: jax.Array):
    """One tile of ``selected_slots``: sel bool [B, T, G, 128], payload bf16
    [B, G, n·128] (``_bytes_of`` a value of every position, laid out like
    ``sel``) -> (positions [B, T, k], the payload's value there [B, T, k],
    count [B, T]; past a query's count the first two hold anything).
    Output slot j belongs to the lane group whose running
    count first passes j; a one-hot of that group, made by two comparisons,
    picks the group's lanes out of ``sel`` and out of the payload on the
    matrix unit, and the lane is the one whose rank within the group (a
    prefix sum, also a matmul) is j's.  No gather, sort or scatter; every
    number carried through a dot is an integer below 256, exact in bf16."""
    b, t, g, lanes = sel.shape
    ones = jnp.triu(jnp.ones((lanes, lanes), jnp.bfloat16))
    rank = jnp.dot(sel.astype(jnp.bfloat16), ones,
                   preferred_element_type=jnp.float32)    # 1-based, in group
    marks = jnp.where(sel, rank, 0).astype(jnp.bfloat16)
    held = rank[..., -1].astype(jnp.int32)                # [B, T, G]
    upto = jnp.cumsum(held, axis=-1)
    count = upto[..., -1]
    below = (upto - held)[:, :, None, :]
    slot = jnp.arange(k, dtype=jnp.int32)
    mine = (below <= slot[:, None]) & (slot[:, None] < upto[:, :, None, :])
    onehot = mine.astype(jnp.bfloat16)                    # [B, T, k, G]
    before = jnp.sum(jnp.where(mine, below, 0), axis=-1)
    picked = jnp.einsum("btkg,btgl->btkl", onehot, marks,
                        preferred_element_type=jnp.bfloat16)
    hit = picked.astype(jnp.int32) == (slot - before + 1)[..., None]
    group = jnp.sum(jnp.where(mine, jnp.arange(g, dtype=jnp.int32), 0),
                    axis=-1)
    pos = group * lanes + jnp.argmax(hit, axis=-1).astype(jnp.int32)
    there = jnp.einsum("btkg,bgl->btkl", onehot, payload,
                       preferred_element_type=jnp.bfloat16)
    there = there.astype(jnp.int32).reshape(b, t, k, -1, lanes)
    whole = sum(there[..., i, :] << (8 * i) for i in range(there.shape[-2]))
    return pos, jnp.sum(jnp.where(hit, whole, 0), axis=-1), count


def selected_slots(sel: jax.Array, k: int, slot_of: jax.Array,
                   slot_bound: int):
    """What a mask [B, S, C] selects (at most k a query), in ascending order
    of position: (positions [B, S, k] int32, slots [B, S, k], count [B, S]).
    ``slot_of`` int32 [B, C] is the flat cache slot of every position of a
    row's block table, below ``slot_bound`` (static); entries past a
    query's count hold position 0 and its slot.  ``COMPACT_QUERY_TILE``
    queries at a time, so that a 256-query call holds no [256, k, C/128]
    array."""
    b, s, c = sel.shape
    lanes = latent_cache.LANES
    pad = -c % lanes
    groups = jnp.pad(sel, ((0, 0), (0, 0), (0, pad))).reshape(
        b, s, -1, lanes)
    n = max(1, -(-(slot_bound - 1).bit_length() // 8))
    payload = _bytes_of(
        jnp.pad(slot_of, ((0, 0), (0, pad))).reshape(b, -1, lanes), n)
    t = COMPACT_QUERY_TILE
    if b * s <= t or s % t:
        pos, slots, count = _compact(groups, k, payload)
    else:
        tiles = jnp.moveaxis(groups.reshape(b, s // t, t, -1, lanes), 1, 0)
        pos, slots, count = (
            jnp.moveaxis(a, 0, 1).reshape(b, s, *a.shape[3:])
            for a in jax.lax.map(lambda tile: _compact(tile, k, payload),
                                 tiles))
    valid = jnp.arange(k, dtype=jnp.int32) < count[..., None]
    return (jnp.where(valid, pos, 0),
            jnp.where(valid, slots, slot_of[:, None, :1]), count)


def index_scores(q: jax.Array, w: jax.Array, keys: jax.Array) -> jax.Array:
    """I[b, s, c] = Hi^-1/2 · Di^-1/2 · Σ_h w[b,s,h] · relu(q[b,s,h] · k[b,c]).
    q [B, S, Hi, Di], w [B, S, Hi], keys [B, C, Di] -> f32 [B, S, C],
    ``INDEX_QUERY_TILE`` queries at a time."""
    b, s, hi, di = q.shape
    scale = (hi * di) ** -0.5

    def tile(args):
        qt, wt = args                       # [B, t, Hi, Di], [B, t, Hi]
        dots = jnp.einsum("bthd,bcd->bthc", qt, keys,
                          preferred_element_type=jnp.float32)
        return jnp.einsum("bthc,bth->btc", jax.nn.relu(dots),
                          wt.astype(jnp.float32)) * scale

    t = INDEX_QUERY_TILE
    if s <= t or s % t:
        return tile((q, w))
    qs = jnp.moveaxis(q.reshape(b, s // t, t, hi, di), 1, 0)
    ws = jnp.moveaxis(w.reshape(b, s // t, t, hi), 1, 0)
    out = jax.lax.map(tile, (qs, ws))       # [S/t, B, t, C]
    return jnp.moveaxis(out, 0, 1).reshape(b, s, -1)


def _layer_norm(x, weight, bias, eps):
    xf = x.astype(jnp.float32)
    mu = xf.mean(axis=-1, keepdims=True)
    var = ((xf - mu) ** 2).mean(axis=-1, keepdims=True)
    y = (xf - mu) * jax.lax.rsqrt(var + eps)
    return (y * weight.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(x.dtype)


def _rope_head(x, positions, inv_freq, rope_dim):
    """Interleaved RoPE on the first ``rope_dim`` of the last axis."""
    return jnp.concatenate(
        [apply_rope_interleaved(x[..., :rope_dim], positions, inv_freq),
         x[..., rope_dim:]], axis=-1)


def latent_params(cfg, n: int, dense, dt) -> dict:
    """The projections of ``n`` stacked latent-attention layers but W_o,
    drawn in this order (``dense(shape, fan_in)`` draws one): the query's —
    through a bottleneck of ``q_lora_rank`` with its norm, or W_q direct
    where that is None — then kv_a, its norm, kv_b.  ``cfg`` is any
    configuration with the latent keys (GlmDsaConfig, HybridLinearConfig)."""
    dm, h = cfg.hidden_size, cfg.num_heads
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    r, ql = cfg.kv_lora_rank, cfg.q_lora_rank
    if ql is None:
        p = {"wq": dense((n, dm, h * (nope + rope)), dm)}
    else:
        p = {"q_a": dense((n, dm, ql), dm),
             "q_a_norm": jnp.ones((n, ql), dt),
             "q_b": dense((n, ql, h * (nope + rope)), ql)}
    p.update(kv_a=dense((n, dm, r + rope), dm),
             kv_a_norm=jnp.ones((n, r), dt),
             kv_b=dense((n, r, h * (nope + vd)), r))
    return p


def latent_projections(cfg, lp, x, positions, inv_freq):
    """Latent attention's projections of the normed input ``x`` [B, S, Dm]
    in absorbed form: (the H queries in latent space ‖ their roped part
    [B, S, H, r + rope], the row to cache ĉ ‖ rope(k_pe) [B, S, r + rope],
    kv_b as [r, H, nope + v], the query bottleneck's normed output or None).
    q_nope[h]·(Wk[h]ᵀ c) = (Wk[h] q_nope[h])·c: the queries go through
    kv_b's K half once, and no key is ever expanded."""
    nh, nope, vd, r = (cfg.num_heads, cfg.qk_nope_head_dim, cfg.v_head_dim,
                       cfg.kv_lora_rank)
    if "wq" in lp:
        c_q, q = None, split_heads(x @ lp["wq"], nh)
    else:
        c_q = rms_norm(x @ lp["q_a"], lp["q_a_norm"], cfg.rms_norm_eps)
        q = split_heads(c_q @ lp["q_b"], nh)
    q_pe = apply_rope_interleaved(q[..., nope:], positions, inv_freq)
    ckv = x @ lp["kv_a"]
    c_hat = rms_norm(ckv[..., :r], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = apply_rope_interleaved(
        ckv[:, :, None, r:], positions, inv_freq)[:, :, 0]
    kv_b = lp["kv_b"].reshape(r, nh, nope + vd)
    q_lat = jnp.concatenate(
        [jnp.einsum("bshn,rhn->bshr", q[..., :nope], kv_b[..., :nope]),
         q_pe], axis=-1)
    return q_lat, jnp.concatenate([c_hat, k_pe], axis=-1), kv_b, c_q


def latent_values(cfg, out, kv_b, dtype):
    """The attended latent ``out`` [B, S, H, >= r] back through kv_b's V
    half: [B, S, H, v]."""
    return jnp.einsum(
        "bshr,rhv->bshv", out[..., :cfg.kv_lora_rank].astype(dtype),
        kv_b[..., cfg.qk_nope_head_dim:])


def dense_attention_impls() -> dict[str, tuple[str, str]]:
    """phase -> ("pallas" | "xla", why) for the engine's start-up line,
    where every cached row is attended: the dense kernels follow one rule
    (``latent_cache.kernels_on``)."""
    impl, why = sparse_attention_impl("prefill")
    return {"decode": (impl, f"{why}; mla_dense_decode"),
            "prefill": (impl, f"{why}; mla_dense_prefill")}


@dataclass(frozen=True)
class _Run:
    """Consecutive layers of one kind: a scan."""
    kind: str        # params group: "<mlp>_<indexer>"
    start: int       # first index within the group's stacks
    count: int
    layer0: int      # first layer index (row of the latent cache)
    full0: int       # first row of the indexer-key cache (full kinds)


class GlmDsaModel:
    """Engine-facing functional model (same protocol as LlamaModel)."""

    # the cache is a pytree in a layout of this family's own (ops/
    # latent_cache.py): EngineCore refuses what would move blocks without
    # knowing it
    private_cache_layout = True
    moe_count_keys = EXPERT_COUNT_KEYS
    supports_ragged_prefill = False
    supports_unified_dispatch = False
    supports_seq_parallel = False
    # forward() sizes its context gather by ``prefix_blocks`` (ctx_blocks):
    # a program a bucket of it
    prefix_blocks_sizes_forward = True

    def __init__(self, config: GlmDsaConfig):
        self.config = config
        # (block tables, lengths, block size) -> cached rows a decode
        # dispatch's attention fetches a layer, for EngineCore's count:
        # what ``mla_dense_decode`` fetches, and with an indexer nothing to
        # say (the selection is counted as it is)
        self.decode_rows_fetched = None
        # the same -> index-key rows a decode dispatch's indexer fetches a
        # ``full`` layer where it scores them in place (what
        # ``dsa_index_scores`` fetches); None without an indexer
        self.index_keys_read = None
        if not config.indexed:
            from dynamo_tpu.ops.pallas.mla_dense_attention import (
                decode_rows_fetched,
            )
            self.decode_rows_fetched = decode_rows_fetched
        else:
            from dynamo_tpu.ops.pallas.dsa_index_scores import index_keys_read
            self.index_keys_read = index_keys_read
        self.sm_scale = float(config.qk_head_dim ** -0.5)
        yarn = config.yarn
        if yarn is None:
            self.inv_freq = rope_inv_freq(config.qk_rope_head_dim,
                                          config.rope_theta)
        else:
            self.inv_freq = yarn_inv_freq(
                config.qk_rope_head_dim, config.rope_theta, yarn["factor"],
                yarn["original_max_position_embeddings"], yarn["beta_fast"],
                yarn["beta_slow"])
            # mscale = mscale_all_dim: cos and sin keep their size, and the
            # softmax scale takes m² (the DeepSeek-V3 reading of these keys)
            self.sm_scale *= yarn_mscale(
                yarn["factor"], yarn["mscale_all_dim"]) ** 2
        kinds = [f"{m}_{i}" for m, i in zip(config.mlp_layer_types,
                                            config.indexer_types)]
        runs, seen, fulls = [], {}, 0
        for li, kind in enumerate(kinds):
            at = seen.get(kind, 0)
            if runs and runs[-1].kind == kind:
                last = runs[-1]
                runs[-1] = _Run(kind, last.start, last.count + 1,
                                last.layer0, last.full0)
            else:
                runs.append(_Run(kind, at, 1, li, fulls))
            seen[kind] = at + 1
            fulls += kind.endswith("_full")
        self.runs = tuple(runs)
        self.group_sizes = seen

    # ------------------------------------------------------------------ init
    def init_params(self, rng: jax.Array) -> Params:
        cfg = self.config
        dt = cfg.jax_dtype
        dm, h = cfg.hidden_size, cfg.num_heads
        vd, ql = cfg.v_head_dim, cfg.q_lora_rank
        hi, di = cfg.index_n_heads, cfg.index_head_dim
        keys = iter(jax.random.split(rng, 128))

        def dense(shape, fan_in):
            return (jax.random.normal(next(keys), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dt)

        def group(kind: str, n: int) -> dict:
            mlp, idx = kind.split("_")
            p = {
                "attn_norm": jnp.ones((n, dm), dt),
                "mlp_norm": jnp.ones((n, dm), dt),
                **latent_params(cfg, n, dense, dt),
                "wo": dense((n, h * vd, dm), h * vd),
            }
            if idx == "full":
                p.update(
                    idx_wq_b=dense((n, ql, hi * di), ql),
                    idx_wk=dense((n, dm, di), dm),
                    idx_k_norm_w=jnp.ones((n, di), dt),
                    idx_k_norm_b=(0.1 * jax.random.normal(
                        next(keys), (n, di), jnp.float32)).astype(dt),
                    idx_weights=dense((n, dm, hi), dm),
                )
            if mlp == "dense":
                f = cfg.intermediate_size
                p.update(w_gate=dense((n, dm, f), dm),
                         w_up=dense((n, dm, f), dm),
                         w_down=dense((n, f, dm), f))
            else:
                e, f = cfg.n_routed_experts, cfg.moe_intermediate_size
                fs = f * cfg.n_shared_experts
                p.update(router=dense((n, dm, cfg.router_experts), dm))
                if cfg.topk_method == "noaux_tc":
                    # e_score_correction_bias: non-zero, so that seeded
                    # weights exercise the choice-only bias
                    p.update(router_bias=ROUTER_BIAS_STD * jax.random.normal(
                        next(keys), (n, cfg.router_experts), jnp.float32))
                p.update(
                    w_gate=dense((n, e, dm, f), dm),
                    w_up=dense((n, e, dm, f), dm),
                    w_down=dense((n, e, f, dm), f),
                    shared_gate=dense((n, dm, fs), dm),
                    shared_up=dense((n, dm, fs), dm),
                    shared_down=dense((n, fs, dm), fs),
                )
            return p

        return {
            "embed": dense((cfg.vocab_size, dm), dm),
            "groups": {kind: group(kind, n)
                       for kind, n in sorted(self.group_sizes.items())},
            "final_norm": jnp.ones((dm,), dt),
            "lm_head": dense((dm, cfg.vocab_size), dm),
        }

    # -------------------------------------------------------------- sharding
    def partition_specs(self) -> Params:
        raise NotImplementedError(
            "GlmDsaModel serves one chip's share of an expert-parallel "
            "deployment on one chip; it has no partition specs (the "
            "exchange of an expert-parallel layer across chips is not built)")

    def cache_spec(self, quant: bool = False):
        if quant:
            raise NotImplementedError("int8 latent cache")
        if not self.config.indexed:
            return {"latent": P(None, None, None, None),
                    "moe_counts": P(None, None, None)}
        return {"latent": P(None, None, None, None, None),
                "index_k": P(None, None, None, None)}

    # --------------------------------------------------------------- kv cache
    def init_kv_cache(self, num_blocks: int, block_size: int, dtype=None):
        """The cache of ops/latent_cache.py: the latent row once a token
        and layer, the indexer's key beside it in ``full`` layers; with no
        indexer the dense layout and, beside it, what the expert layers
        counted (``moe_counts`` int32 [L, 1, 4]: router picks, picks that
        fell on the experts held here, calls, experts touched — shaped like
        a part of the cache, after ``latent`` in the pytree's order;
        ``EngineCore`` reads its sums back with each dispatch)."""
        cfg = self.config
        if dtype is not None and jnp.dtype(dtype) != jnp.dtype(cfg.jax_dtype):
            raise NotImplementedError(f"latent cache dtype {dtype!r}")
        if not cfg.indexed:
            return {
                **latent_cache.init_dense_cache(
                    cfg.num_layers, num_blocks, block_size, cfg.head_dim,
                    cfg.jax_dtype),
                "moe_counts": jnp.zeros(
                    (cfg.num_layers, 1, EXPERT_COUNTS), jnp.int32)}
        return latent_cache.init_latent_cache(
            cfg.num_layers, cfg.full_layers, num_blocks, block_size,
            cfg.head_dim, cfg.index_head_dim, cfg.jax_dtype)

    def attention_impls(self) -> dict[str, tuple[str, str]]:
        """phase -> ("pallas" | "xla", why) for the engine's start-up line."""
        if not self.config.indexed:
            return dense_attention_impls()
        from dynamo_tpu.ops.pallas.mla_masked_prefill import KERNEL_NAME

        out = {p: sparse_attention_impl(p) for p in ("decode", "prefill")}
        impl, why = out["prefill"]
        out["prefill"] = (impl, f"{why}; gathers in a batch of sequences")
        out["prefill_chunk"] = (
            impl, f"{why}; {KERNEL_NAME}: one sequence's chunk, a question "
            "after a prefix hit too, scores the context once a tile of "
            f"queries and masks the selection up to {self.masked_up_to():,} "
            "positions, gathers over a longer context")
        return out

    def attends_masked(self, b: int, s: int, table_blocks: int,
                       block_size: int, prefix_blocks,
                       probe: bool = False) -> bool:
        """Which of its two forms the attention of a call over a selection
        takes, from the call's static shape alone — ``forward`` traces by it
        and the engine counts by it: a prefill chunk of one sequence masks
        where walking its static context a tile of queries at a time costs
        less than gathering each query's rows; a decode step, a batch of
        sequences and a probe gather."""
        cfg = self.config
        if not cfg.indexed or b != 1 or s == 1 or probe:
            return False
        return masked_prefill_is_cheaper(
            block_size * _context_blocks(
                s, table_blocks, block_size, prefix_blocks), cfg.index_topk)

    def masked_up_to(self) -> int:
        """The longest static context, in whole key tiles, whose chunk still
        attends masked (for the start-up line)."""
        tiles = 0
        while masked_prefill_is_cheaper(
                (tiles + 1) * MLA_MASKED_KEYS_PER_TILE,
                self.config.index_topk):
            tiles += 1
        return tiles * MLA_MASKED_KEYS_PER_TILE

    # ---------------------------------------------------------------- forward
    def _select(self, lp, fi, x, c_q, positions, cache, block_tables,
                seq_lens, slot_idx, ctx_blocks, sparse: bool, groups=None):
        """The indexer of a ``full`` layer: writes this call's keys, scores
        every cached position and returns the selection — (slots [N, K],
        nvalid [N]) for the gather, or a mask [B, S, C] for a dense chunk —
        with the updated cache.  ``groups`` (a decode step on the TPU:
        ``latent_cache.index_decode_groups``) has the keys scored where they
        lie; None gathers them for ``index_scores``."""
        cfg = self.config
        b, s, _ = x.shape
        hi, di, rope = (cfg.index_n_heads, cfg.index_head_dim,
                        cfg.qk_rope_head_dim)
        bs = cache["index_k"].shape[2]
        q = split_heads(c_q @ lp["idx_wq_b"], hi)
        q = _rope_head(q, positions, self.inv_freq, rope)
        k = _layer_norm(x @ lp["idx_wk"], lp["idx_k_norm_w"],
                        lp["idx_k_norm_b"], INDEX_NORM_EPS)
        k = _rope_head(k[:, :, None, :], positions, self.inv_freq,
                       rope)[:, :, 0]
        w = x @ lp["idx_weights"]                        # [B, S, Hi]
        index_k = latent_cache.write_rows(
            cache["index_k"], fi, k.reshape(b * s, di),
            slot_idx.reshape(b * s))
        cache = {**cache, "index_k": index_k}
        if groups is None:
            keys = index_k[fi, block_tables[:, :ctx_blocks]].reshape(
                b, ctx_blocks * bs, di)
            scores = index_scores(q, w, keys)            # [B, S, C] f32
        else:
            # what no row of a group sees is left as it was: ``seen`` below
            # is the one place a position's visibility is decided
            scores = latent_cache.decode_index_scores(
                q[:, 0], w[:, 0], index_k, fi, block_tables[:, :ctx_blocks],
                positions, seq_lens, groups)[:, None]
        at = jnp.arange(ctx_blocks * bs, dtype=jnp.int32)
        seen = ((at[None, None, :] <= positions[:, :, None])
                & (at[None, None, :] < seq_lens[:, None, None]))
        scores = jnp.where(seen, scores, NEG_INF)
        k_sel = min(cfg.index_topk, ctx_blocks * bs)
        # the same positions in both forms (``lax.top_k``'s, without its
        # sort: two 8.6 ms sorts a decode step on the chip, 40% of it)
        sel = select_mask(scores, seen, k_sel)
        if not sparse:
            return sel, cache
        c = scores.shape[-1]
        # the flat slot of every position of the table: a broadcast, and the
        # compaction carries it along, so no position is looked up
        slot_of = (block_tables[:, :ctx_blocks, None] * bs
                   + jnp.arange(bs, dtype=jnp.int32)).reshape(b, c)
        picked, slots, nvalid = selected_slots(
            sel, k_sel, slot_of, index_k.shape[1] * bs)
        picked = picked.reshape(b * s, k_sel)
        vals = jnp.take_along_axis(scores.reshape(b * s, c), picked, axis=1)
        # (slots, nvalid) is what attention reads; positions and scores ride
        # along for ``forward(probe=True)`` and cost nothing otherwise
        return (slots.reshape(b * s, k_sel), nvalid.reshape(b * s),
                picked, vals), cache

    def _query_scale(self, positions: jax.Array) -> jax.Array:
        """f32 [B, S]: what multiplies a query before a dense kernel — the
        softmax scale (YaRN's m² in it) times the position's factor
        1 + beta·ln(1 + floor(p / trained context))."""
        cfg = self.config
        scale = jnp.full(positions.shape, self.sm_scale, jnp.float32)
        if cfg.query_scale_beta:
            trained = cfg.yarn["original_max_position_embeddings"]
            scale = scale * (1.0 + cfg.query_scale_beta * jnp.log1p(
                (positions // trained).astype(jnp.float32)))
        return scale

    def _attention(self, lp, li, fi, h_in, positions, cache, block_tables,
                   seq_lens, slot_idx, sel, ctx_blocks, sparse, full,
                   groups=None):
        cfg = self.config
        b, s, _ = h_in.shape
        nh, vd, r = cfg.num_heads, cfg.v_head_dim, cfg.kv_lora_rank
        with jax.named_scope("attn_proj"):
            x = rms_norm(h_in, lp["attn_norm"], cfg.rms_norm_eps)
            q_lat, row, kv_b, c_q = latent_projections(
                cfg, lp, x, positions, self.inv_freq)
            row = row.reshape(b * s, -1)
            if cfg.indexed:
                latent = latent_cache.write_latent(
                    cache["latent"], li, latent_cache.pack_rows(row),
                    slot_idx.reshape(b * s))
            else:
                latent = latent_cache.write_dense(
                    cache["latent"], li, row, slot_idx.reshape(b * s))
                # the softmax scale and the position's factor ride on the
                # query: the dense kernels know neither
                q_lat = (q_lat.astype(jnp.float32) * self._query_scale(
                    positions)[:, :, None, None]).astype(q_lat.dtype)
            cache = {**cache, "latent": latent}
        with jax.named_scope("attn"):
            if full:
                with jax.named_scope("indexer"):
                    sel, cache = self._select(
                        lp, fi, x, c_q, positions, cache, block_tables,
                        seq_lens, slot_idx, ctx_blocks, sparse, groups)
            if not cfg.indexed:
                out = latent_cache.dense_attention(
                    q_lat, cache["latent"], li, block_tables[:, :ctx_blocks],
                    positions, seq_lens, dv=r, groups=groups)
            elif sparse:
                slots, nvalid = sel[:2]
                out = sparse_latent_attention(
                    q_lat.reshape(b * s, nh, -1), cache["latent"], li, slots,
                    nvalid, sm_scale=self.sm_scale,
                    phase="decode" if s == 1 else "prefill",
                ).reshape(b, s, nh, -1)
            else:
                out = latent_cache.masked_attention(
                    q_lat, cache["latent"], li,
                    block_tables[:, :ctx_blocks], sel, self.sm_scale, dv=r,
                    live=jnp.sum(slot_idx >= 0, axis=1), seq_lens=seq_lens)
        with jax.named_scope("attn_out"):
            o = latent_values(cfg, out, kv_b, h_in.dtype)
            h = h_in + o.reshape(b, s, nh * vd) @ lp["wo"]
        return h, cache, sel

    def _mlp(self, group: dict, lp: dict, i, x, dense: bool, valid=None):
        """(the layer's output, int32 [4] or None: what an expert layer
        counts for the tokens of ``valid`` [B, S] — None: all — the router's
        picks, those that fell on the experts held here, this call, and the
        held experts with a row of any token: those whose weights it
        reads)."""
        cfg = self.config
        if dense:
            return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) \
                @ lp["w_down"], None
        b, s, d = x.shape
        xf = x.reshape(b * s, d)
        with jax.named_scope("moe_router"):
            weights, topi = moe_route(cfg, lp["router"], xf,
                                      lp.get("router_bias"))
            real = (jnp.ones((b * s, 1), bool) if valid is None
                    else valid.reshape(b * s, 1))
            here = ((topi >= cfg.expert_first)
                    & (topi < cfg.expert_first + cfg.n_routed_experts) & real)
            counted = jnp.stack([
                real.sum(dtype=jnp.int32) * cfg.num_experts_per_tok,
                here.sum(dtype=jnp.int32), jnp.int32(1),
                experts_touched(topi, cfg.expert_first,
                                cfg.n_routed_experts)])
        with jax.named_scope("moe_experts"):
            # the group's whole expert stacks, read where they lie
            routed = grouped_expert_dispatch(
                xf, weights, topi, cfg.router_experts,
                group["w_gate"], group["w_up"], group["w_down"],
                jax.nn.silu, layer=i,
                held=(cfg.expert_first, cfg.n_routed_experts))
        shared = (jax.nn.silu(xf @ lp["shared_gate"])
                  * (xf @ lp["shared_up"])) @ lp["shared_down"]
        return (routed + shared).reshape(b, s, d), counted

    def forward(self, params, tokens, positions, cache, block_tables,
                seq_lens, slot_idx, prefix_blocks=None, probe=False):
        """(hidden [B,S,Dm], cache).  ``prefix_blocks`` (static) bounds the
        context a prefill chunk reads: that many cached blocks plus its
        own; None reads the whole block table.  A chunk's padding tokens
        (``slot_idx`` < 0) come after its live ones.  ``probe`` (static;
        takes the gather form) also returns, for every ``full`` layer in
        order, what its indexer selected: (positions [N, K], scores [N, K],
        nvalid [N]) — for scripts/glm_longctx_check.py, not for serving."""
        cfg = self.config
        b, s = tokens.shape
        bs = cache["latent"].shape[2]
        m = block_tables.shape[1]
        ctx_blocks = _context_blocks(s, m, bs, prefix_blocks)
        sparse = cfg.indexed and not self.attends_masked(
            b, s, m, bs, prefix_blocks, probe)
        valid = slot_idx >= 0       # a padding token writes no row
        with jax.named_scope("embed"):
            hidden = params["embed"][tokens].astype(cfg.jax_dtype)
        probes = []
        groups = None
        if not cfg.indexed:
            sel = None
            if s == 1:
                # the rows that ask the same document, found once for every
                # layer's kernel (as LlamaModel orders rows by length once)
                groups = latent_cache.dense_decode_groups(
                    block_tables[:, :ctx_blocks], positions, seq_lens, bs)
        elif sparse:
            if s == 1:
                # the rows that ask the same document, found once for every
                # ``full`` layer's indexer (which then reads its keys once)
                groups = latent_cache.index_decode_groups(
                    cache["index_k"], cfg.index_n_heads,
                    block_tables[:, :ctx_blocks], positions, seq_lens)
            k_sel = min(cfg.index_topk, ctx_blocks * bs)
            sel = (jnp.zeros((b * s, k_sel), jnp.int32),
                   jnp.zeros((b * s,), jnp.int32),
                   jnp.zeros((b * s, k_sel), jnp.int32),
                   jnp.zeros((b * s, k_sel), jnp.float32))
        else:
            sel = jnp.zeros((b, s, ctx_blocks * bs), bool)

        expert_keys = ("w_gate", "w_up", "w_down")
        for run in self.runs:
            group = params["groups"][run.kind]
            dense = run.kind.startswith("dense")
            full = run.kind.endswith("_full")
            sliced = {k: v for k, v in group.items()
                      if dense or k not in expert_keys}

            def step(carry, at, group=group, sliced=sliced, dense=dense,
                     full=full, shared_sel=sel):
                h, cache, sel = carry if full else (*carry, shared_sel)
                i, li, fi = at
                lp = jax.tree.map(lambda a: a[i], sliced)
                h, cache, sel = self._attention(
                    lp, li, fi, h, positions, cache, block_tables, seq_lens,
                    slot_idx, sel, ctx_blocks, sparse, full, groups)
                with jax.named_scope("mlp"):
                    x = rms_norm(h, lp["mlp_norm"], cfg.rms_norm_eps)
                    y, counted = self._mlp(group, lp, i, x, dense, valid)
                    h = h + y
                    if counted is not None and "moe_counts" in cache:
                        cache = {**cache, "moe_counts":
                                 cache["moe_counts"].at[li, 0].add(counted)}
                seen = (sel[2], sel[3], sel[1]) if probe and full else None
                return ((h, cache, sel) if full else (h, cache)), seen

            n = jnp.arange(run.count, dtype=jnp.int32)
            xs = (run.start + n, run.layer0 + n, run.full0 + n)
            init = (hidden, cache, sel) if full else (hidden, cache)
            out, seen = jax.lax.scan(step, init, xs)
            if full:
                hidden, cache, sel = out
            else:
                hidden, cache = out
            if seen is not None:
                probes += [jax.tree.map(lambda a, i=i: a[i], seen)
                           for i in range(run.count)]
        hidden = rms_norm(hidden, params["final_norm"], cfg.rms_norm_eps)
        return (hidden, cache, probes) if probe else (hidden, cache)

    def compute_logits(self, params, hidden):
        with jax.named_scope("logits"):
            w = params["lm_head"]
            return jnp.matmul(hidden.astype(w.dtype), w,
                              preferred_element_type=jnp.float32)

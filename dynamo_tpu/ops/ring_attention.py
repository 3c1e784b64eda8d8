"""Ring attention — context/sequence parallelism over an ICI mesh axis.

Long-context capability the reference lacks entirely (SURVEY.md §5
"long-context / sequence parallelism: absent from the reference") but a
TPU-native engine needs: a prompt too long for one chip's HBM is sharded
along the sequence axis of the mesh, and attention runs blockwise while
K/V chunks rotate around the ring (jax.lax.ppermute over ICI), overlapping
the collective with compute.  Online-softmax accumulation (the
flash-attention recurrence) makes the result exact, not approximate.

    device i holds Q_i forever; at ring step t it multiplies against
    KV_{(i-t) mod n}, merging partial results with the running (m, l, o)
    log-sum-exp state.  n steps visit every KV chunk once.

Designed for use under ``jax.shard_map`` (wrapper below) so GSPMD sees the
per-device program explicitly — no accidental all-gather of the sequence.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from dynamo_tpu.ops.paged_attention import softcap
from dynamo_tpu.utils.mesh import AXIS_SP

__all__ = ["ring_attention", "ring_attention_inner"]

_NEG_INF = -1e30


def ring_attention_inner(
    q: jax.Array,       # [B, Sq, Hq, D]  local query shard
    k: jax.Array,       # [B, Sk, Hk, D]  local key shard
    v: jax.Array,       # [B, Sk, Hk, D]  local value shard
    q_pos: jax.Array,   # [B, Sq] int32   global positions of local queries
    kv_pos: jax.Array,  # [B, Sk] int32   global positions of local keys
    axis_name: str,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Per-device ring attention body (call under shard_map).

    Returns [B, Sq, Hq, D] in q.dtype.  GQA handled by repeating kv heads.
    Masking is position-based (q_pos >= kv_pos), so ragged/padded chunks
    work: give padding keys a position larger than any query.  ``window``
    adds sliding-window masking (q_pos − kv_pos < window).
    """
    if window is not None and not causal:
        # the window mask lives inside the causal branch; silently
        # ignoring it for bidirectional callers would be a wrong answer
        raise ValueError("window requires causal=True")
    n = jax.lax.psum(1, axis_name)
    b, sq, hq, d = q.shape
    hk = k.shape[2]
    rep = hq // hk
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    perm = [(j, (j + 1) % n) for j in range(n)]

    # grouped layout [B, Sq, Hk, rep, D]: the kv-head broadcast of GQA fuses
    # into the matmuls instead of materialising rep× copies of each K/V chunk
    qf = q.astype(jnp.float32).reshape(b, sq, hk, rep, d)

    def step(carry, _):
        o, m, l, k_c, v_c, kv_pos_c = carry
        kf = k_c.astype(jnp.float32)
        vf = v_c.astype(jnp.float32)
        # [B, Hk, rep, Sq, Sk]
        s = jnp.einsum("bqgrd,bkgd->bgrqk", qf, kf) * scale
        if logit_cap is not None:  # Gemma2 attention score softcap
            s = softcap(s, logit_cap)
        if causal:
            mask = q_pos[:, None, None, :, None] >= kv_pos_c[:, None, None, None, :]
            if window is not None:
                mask &= (q_pos[:, None, None, :, None]
                         - kv_pos_c[:, None, None, None, :]) < window
            s = jnp.where(mask, s, _NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        # fully-masked rows: m_new is still _NEG_INF, so s - m_new == 0 and
        # p would be 1 for every masked key — zero it (flash-attention guard)
        p = jnp.where((m_new == _NEG_INF)[..., None], 0.0, p)
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        o_new = o * corr[..., None] + jnp.einsum("bgrqk,bkgd->bgrqd", p, vf)
        # rotate the KV chunk to the next device; XLA overlaps this ICI
        # ppermute with the next step's matmuls
        k_c = jax.lax.ppermute(k_c, axis_name, perm)
        v_c = jax.lax.ppermute(v_c, axis_name, perm)
        kv_pos_c = jax.lax.ppermute(kv_pos_c, axis_name, perm)
        return (o_new, m_new, l_new, k_c, v_c, kv_pos_c), None

    o0 = jnp.zeros((b, hk, rep, sq, d), jnp.float32)
    m0 = jnp.full((b, hk, rep, sq), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hk, rep, sq), jnp.float32)
    (o, _, l, _, _, _), _ = jax.lax.scan(
        step, (o0, m0, l0, k, v, kv_pos), None, length=n
    )
    out = o / jnp.where(l == 0.0, 1.0, l)[..., None]  # fully-masked rows -> 0
    # [B, Hk, rep, Sq, D] -> [B, Sq, Hk*rep = Hq, D]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d).astype(q.dtype)


def ring_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    q_pos: jax.Array,
    kv_pos: jax.Array,
    *,
    mesh: jax.sharding.Mesh,
    axis: str = AXIS_SP,
    causal: bool = True,
    sm_scale: Optional[float] = None,
    logit_cap: Optional[float] = None,
    window: Optional[int] = None,
) -> jax.Array:
    """Sequence-parallel attention: inputs sharded on their seq axis over
    ``mesh[axis]``; output keeps that sharding.  q/k/v: [B, S, H, D] global;
    q_pos/kv_pos: [B, S] global positions."""
    if axis not in mesh.axis_names:
        # a renamed/missing axis must fail HERE: a PartitionSpec naming an
        # axis the mesh doesn't have would otherwise silently replicate the
        # sequence on every chip and psum(1) over a size-1 axis would make
        # the ring degenerate to a single (wrong) step
        raise ValueError(
            f"ring_attention axis {axis!r} not in mesh axes "
            f"{tuple(mesh.axis_names)}"
        )
    inner = functools.partial(
        ring_attention_inner, axis_name=axis, causal=causal,
        sm_scale=sm_scale, logit_cap=logit_cap, window=window,
    )
    seq = P(None, axis, None, None)
    pos = P(None, axis)
    wrapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(seq, seq, seq, pos, pos),
        out_specs=seq,
        check_vma=False,
    )
    return wrapped(q, k, v, q_pos, kv_pos)

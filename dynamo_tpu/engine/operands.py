"""A dispatch's small operands as one buffer.

A serving dispatch takes ten-odd small host arrays (tokens, positions,
block tables, lengths, sampling rows, grammar row state).  A host->device
transfer costs the host the same whatever its size, and under a mesh each
array is one transfer *per device*: on four chips the upload of nine
arrays was 36 transfers and the largest idle gap of the turn, and on one
chip nine puts are most of the launch once a program is a few
milliseconds.  So the arrays travel as one int32 vector (``pack``): int32
as it is, bool as 0/1, float32 as its bits (``ndarray.view``: exact, a
``-0.0``, a denormal or a NaN's payload arrives as it left).  The jitted
serving program itself takes the vector apart with static slices
(``unpack``, traced: ``engine/core.py::packed``), so no program runs ahead
of it.  An array of another dtype travels beside the vector as it is.

The ``layout`` that joins the two is static and hashable: the tree's
structure and, per leaf, where, what shape, what it was.  It is a function
of the operands' shapes and keys, which key the serving call's executable
anyway: a shape the warm-up has served has its program."""

from __future__ import annotations

import math

import jax
import numpy as np
from jax import lax

__all__ = ["pack", "unpack"]

_INT, _FLOAT, _BOOL = np.dtype(np.int32), np.dtype(np.float32), np.dtype(bool)


def pack(tree):
    """``((buf, *others), layout)`` for a tree of host arrays: the int32,
    bool and float32 leaves joined into the int32 vector ``buf``, a leaf of
    any other dtype on its own."""
    leaves, treedef = jax.tree.flatten(tree)
    slots, parts, others = [], [], []
    at = 0
    for leaf in leaves:
        if leaf.dtype in (_INT, _BOOL, _FLOAT):
            slots.append((leaf.dtype.kind, at, leaf.shape))
            flat = leaf.ravel()
            parts.append(flat.view(_INT) if leaf.dtype == _FLOAT else flat)
            at += leaf.size
        else:
            slots.append(("o", len(others), None))
            others.append(leaf)
    buf = np.concatenate(parts, dtype=_INT) if parts else np.zeros(0, _INT)
    return (buf, *others), (tuple(slots), treedef)


def unpack(bufs, layout):
    """The tree ``pack`` was given, out of its buffers (traced)."""
    buf, *others = bufs
    slots, treedef = layout
    leaves = []
    for kind, at, shape in slots:
        if kind == "o":
            leaves.append(others[at])
            continue
        # lax, not jnp: an indexing or a comparison of jnp's is a jitted
        # function of its own, traced and lowered again in every program
        leaf = lax.reshape(
            lax.slice(buf, (at,), (at + math.prod(shape),)), shape)
        if kind == "f":
            leaf = lax.bitcast_convert_type(leaf, _FLOAT)
        leaves.append(lax.ne(leaf, _INT.type(0)) if kind == "b" else leaf)
    return jax.tree.unflatten(treedef, leaves)

"""A dispatch's small operands as two buffers.

A serving dispatch takes ten-odd small host arrays (tokens, positions,
block tables, lengths, sampling rows, grammar row state).  A host->device
transfer costs the host the same whatever its size, and under a mesh each
array is one transfer *per device*: on four chips the upload of nine
arrays was 36 transfers and the largest idle gap of the turn.  So the
int32/bool arrays travel as one int32 vector and the float32 arrays as
another (``pack``), and a small jitted program takes them apart again on
the device with static slices (``unpack``, traced).  An array of another
dtype travels beside them as it is.

The ``layout`` that joins the two is static and hashable: the tree's
structure and, per leaf, which buffer, where, what shape.  It is a function
of the operands' shapes and keys, which key the serving call's executable
too: a shape the warm-up has served has both programs."""

from __future__ import annotations

import math

import jax
import numpy as np

__all__ = ["pack", "unpack"]

_INT, _FLOAT, _BOOL = np.dtype(np.int32), np.dtype(np.float32), np.dtype(bool)


def pack(tree):
    """``((ints, floats, *others), layout)`` for a tree of host arrays:
    the int32/bool leaves joined into ``ints``, the float32 leaves into
    ``floats``, a leaf of any other dtype on its own."""
    leaves, treedef = jax.tree.flatten(tree)
    slots, ints, floats, others = [], [], [], []
    n_int = n_float = 0
    for leaf in leaves:
        if leaf.dtype in (_INT, _BOOL):
            slots.append(("i", n_int, leaf.shape, leaf.dtype == _BOOL))
            ints.append(leaf.ravel())
            n_int += leaf.size
        elif leaf.dtype == _FLOAT:
            slots.append(("f", n_float, leaf.shape, False))
            floats.append(leaf.ravel())
            n_float += leaf.size
        else:
            slots.append(("o", len(others), None, False))
            others.append(leaf)
    join = lambda parts, dtype: (
        np.concatenate(parts, dtype=dtype) if parts else np.zeros(0, dtype))
    return ((join(ints, _INT), join(floats, _FLOAT), *others),
            (tuple(slots), treedef))


def unpack(bufs, layout):
    """The tree ``pack`` was given, out of its buffers (traced)."""
    ints, floats, *others = bufs
    slots, treedef = layout
    leaves = []
    for kind, at, shape, is_bool in slots:
        if kind == "o":
            leaves.append(others[at])
            continue
        buf = ints if kind == "i" else floats
        leaf = buf[at:at + math.prod(shape)].reshape(shape)
        leaves.append(leaf != 0 if is_bool else leaf)
    return jax.tree.unflatten(treedef, leaves)

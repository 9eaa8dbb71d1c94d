"""H-tree collectives: log-depth pairwise tree all-reduce over a mesh axis.

The paper's re-architected die replaces the shared output bus with a binary
H-tree whose internal RPUs add partial sums pairwise on the way to the root
(Sec. III-C, ``core/htree.py::htree_time``).  This module is the SPMD
rendering of the same dataflow: shards are the leaves, each up-sweep round
is one tree level (``ppermute`` + add), and the down-sweep broadcasts the
root's total back out.  Both sides share the depth model —
``core.htree.tree_depth(n)`` rounds for ``n`` leaves — so the latency the
analytical model charges (``depth * level_lat``) is exactly the number of
communication rounds the collective issues.

Numerically the tree reduction equals ``jax.lax.psum`` (same summands,
different association); tests assert equality for power-of-two and ragged
axis sizes alike.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.htree import tree_depth


def htree_allreduce(x: jax.Array, axis_name: str) -> jax.Array:
    """All-reduce-sum ``x`` over ``axis_name`` via a binary reduction tree.

    Works for any axis size (non-powers-of-two get a ragged last level, the
    same way a die with a non-power-of-two plane count pads its H-tree).
    Must be called inside ``shard_map``/``pmap`` with ``axis_name`` bound.
    """
    n = jax.lax.axis_size(axis_name)
    if n == 1:
        return x
    idx = jax.lax.axis_index(axis_name)
    depth = tree_depth(n)
    # up-sweep: level r merges subtrees of span 2**r; the left sibling
    # (an RPU in ALU mode) accumulates, the right sibling goes quiet
    for r in range(depth):
        span = 1 << r
        pairs = [(i + span, i) for i in range(0, n, 2 * span) if i + span < n]
        if not pairs:
            continue
        recv = jax.lax.ppermute(x, axis_name, pairs)
        x = x + recv                      # non-receivers add ppermute's zeros
    # down-sweep: the root's total retraces the tree to every leaf
    for r in reversed(range(depth)):
        span = 1 << r
        pairs = [(i, i + span) for i in range(0, n, 2 * span) if i + span < n]
        if not pairs:
            continue
        recv = jax.lax.ppermute(x, axis_name, pairs)
        x = jnp.where((idx % (2 * span)) == span, recv, x)
    return x


def allreduce(x: jax.Array, axis_name: str, collective: str = "psum") -> jax.Array:
    """Reducer hook dispatched by ``Runtime.collective``."""
    if collective == "htree":
        return htree_allreduce(x, axis_name)
    if collective == "psum":
        return jax.lax.psum(x, axis_name)
    raise ValueError(f"unknown collective {collective!r}; want psum|htree")

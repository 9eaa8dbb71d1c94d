"""QLC-SLC hybrid KV cache (Sec. IV-A, Fig. 10d) with slotted residency.

Weights live in the dense, never-written "QLC region" (int8, nibble-packable)
while the KV cache lives in the fast-append "SLC region": int8 entries with
per-(token, head) scales, appended in place every generated token.  On TPU
the SLC region is an int8 buffer updated with ``dynamic_update_slice`` —
cheap, constant-time appends, exactly the paper's write-friendly role.

For continuous batching the batch axis is a pool of *slots*: each slot holds
one in-flight request at its own sequence position, so appends land at a
heterogeneous ``[B]`` position vector (vmapped ``dynamic_update_slice`` —
the SLC-region analogue of paged KV, one page per request).  Slots are
allocated when a request is admitted and freed (length reset to 0) when it
retires; the backing buffers never reallocate, so ``cache_bytes`` is
invariant under slot churn.

Layouts (per layer, stacked over layers as the leading axis):
  k_q, v_q     : [L, B, S_max, H_kv·D_h]  int8, lane-dense — heads and
                 head_dim merged in the minor axis, S on the sublane axis,
                 so a token's append is a one-row write and no head_dim
                 pads to 128
  k_s, v_s     : [L, B, S_max, H_kv, 1]   f32
  (MLA latent) : [L, B, S_max, C_latent]  int8 (+ [.., 1] f32 scale)
Every GQA cache takes that layout: the decode pool
(``transformer.init_decode_state``), the encoder-decoder's self- and
cross-attention caches and the :class:`KVCache` reference model.  K/V are
computed per head ([.., H_kv, D_h]); :func:`merge_heads` and
:func:`split_heads` are the one conversion each way.
SSM layers instead carry a fixed-size recurrent state — the most
flash-write-friendly cache of all (constant footprint; see DESIGN.md).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp

from repro.core.quant import quantize_kv


def slot_positions(pos: jax.Array | int, batch: int) -> jax.Array:
    """Normalise a scalar or [B] position argument to a [B] int32 vector."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 0:
        pos = pos[None]
    return jnp.broadcast_to(pos, (batch,))


def merge_heads(x: jax.Array) -> jax.Array:
    """Per-head K/V rows ``[..., H_kv, D_h]`` -> the cache's lane-dense
    ``[..., H_kv·D_h]`` (a reshape: the bytes are the same)."""
    return x.reshape(x.shape[:-2] + (-1,))


def split_heads(x: jax.Array, n_kv_heads: int) -> jax.Array:
    """Lane-dense cache rows ``[..., H_kv·D_h]`` -> per head
    ``[..., H_kv, D_h]``: the inverse of :func:`merge_heads`."""
    return x.reshape(x.shape[:-1] + (n_kv_heads, -1))


def quantize_rows(x: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Per-(token, head) int8 quantization of ``[..., H_kv, D_h]`` float
    K/V -> (lane-dense int8 rows, ``[..., H_kv, 1]`` f32 scales)."""
    x_q, x_s = quantize_kv(x)
    return merge_heads(x_q), x_s


def batched_update(buf: jax.Array, new: jax.Array, pos: jax.Array) -> jax.Array:
    """Write ``new[b]`` into ``buf[b]`` at sequence offset ``pos[b]``.

    buf: [B, S, ...]; new: [B, T, ...]; pos: [B] int32 (clamped by XLA).
    The vmapped ``dynamic_update_slice`` is the batched SLC append: every
    slot lands at its own position in one fused update.
    """
    pos = slot_positions(pos, buf.shape[0])

    def one(b, n, p):
        return jax.lax.dynamic_update_slice(b, n.astype(b.dtype),
                                            (p,) + (0,) * (b.ndim - 1))

    return jax.vmap(one)(buf, new, pos)


def append_rows(pool: jax.Array, layer: jax.Array | int, new: jax.Array,
                pos: jax.Array) -> jax.Array:
    """Write slot ``b``'s one new row ``new[b, 0]`` into layer ``layer`` of
    the stacked pool at offset ``pos[b]``: :func:`batched_update` one level
    up, for a pool carried through the decode layer scan.

    pool: [L, B, S, ...]; new: [B, 1, ...]; pos: [B] int32 (clamped to
    ``S - 1`` as :func:`batched_update` clamps).  Each slot's write is a
    one-row ``dynamic_update_slice`` into the carried pool, so nothing is
    sliced out and written back.  In the lane-dense int8 leaves S is the
    sublane axis and the row is a sublane write of ``H_kv·D_h`` bytes.
    """
    B, S = pool.shape[1:3]
    pos = jnp.clip(slot_positions(pos, B), 0, S - 1)
    layer = jnp.asarray(layer, jnp.int32)
    rest = (jnp.int32(0),) * (pool.ndim - 3)
    for b in range(B):
        pool = jax.lax.dynamic_update_slice(
            pool, new[None, b:b + 1].astype(pool.dtype),
            (layer, jnp.int32(b), pos[b]) + rest)
    return pool


def chunk_update(buf: jax.Array, new: jax.Array, start: jax.Array | int,
                 ) -> jax.Array:
    """Append a ``[B, C, ...]`` chunk into ``buf`` (``[B, S, ...]``) at the
    *shared* sequence offset ``start`` — the chunked-prefill SLC append: one
    contiguous multi-token write into a slot row at an arbitrary cursor,
    where :func:`batched_update` is its per-slot-offset decode sibling.

    ``start`` may be a traced scalar, so one compiled chunk step serves
    every cursor position.
    """
    start = jnp.asarray(start, jnp.int32)
    idx = (jnp.int32(0), start) + (jnp.int32(0),) * (buf.ndim - 2)
    return jax.lax.dynamic_update_slice(buf, new.astype(buf.dtype), idx)


def pool_headroom(spec_k: int = 0, spec_tree: int = 0,
                  multi_step: int = 1) -> int:
    """Scratch rows each slot needs past ``max_len`` — the one audited
    sizing rule for every lane that writes ahead of the committed cursor:

    - linear spec: a verify window appends ``spec_k + 1`` rows at
      ``pos .. pos + spec_k`` with ``pos <= max_len - 1``, so ``spec_k``
      rows of headroom;
    - tree spec: a window appends ``spec_tree + 1`` node rows (root +
      drafts) at ``pos .. pos + spec_tree`` — ``spec_tree`` rows;
    - fused multi-step: a block appends up to ``m`` rows at
      ``pos .. pos + m - 1`` before the host truncates a mid-block stop —
      ``m - 1`` rows.

    The lanes are mutually exclusive per step, so the pool only needs the
    max.  Every row a lane writes past a slot's committed cursor must fall
    inside this margin — rollback is a cursor move (``rewind_pos``), and
    rows beyond ``max_len + headroom`` would clamp into live rows of the
    window itself.
    """
    if min(spec_k, spec_tree, multi_step - 1) < 0:
        raise ValueError("negative spec_k/spec_tree or multi_step < 1")
    return max(spec_k, spec_tree, multi_step - 1)


def path_gather(buf: jax.Array, base: jax.Array, sel: jax.Array,
                keep: jax.Array) -> jax.Array:
    """Compact an accepted tree path's scattered rows into contiguous rows.

    buf: [L, B, S, ...] (slot axis 1, sequence axis 2 — a pooled decode
    leaf or a stacked reference-cache leaf); base: [B] int32 committed
    cursors; sel: [B, W] int32 in-window *node indices* of the accepted
    root-path in order (``sel[b, w]`` >= w + 1: tree nodes are
    topologically ordered, so a path only ever moves rows *down*);
    keep: [B] int32 accepted path length (<= W).

    Row ``base[b] + sel[b, w]`` moves to ``base[b] + 1 + w`` for
    ``w < keep[b]`` (row ``base[b]`` — the root / last committed token —
    is already in place); rows past the path are left as dead in-place
    entries for ``rewind_pos`` to hide, per the SLC write-in-place
    discipline.  All index operands may be traced.
    """
    W = sel.shape[1]
    if W == 0:
        # Zero-width window: nothing to compact.  Guarded statically so the
        # degenerate trace never builds an empty gather/scatter (some XLA
        # backends reject size-0 take_along_axis operands).
        return buf
    base = jnp.asarray(base, jnp.int32)
    src = (base[:, None] + jnp.asarray(sel, jnp.int32)).reshape(
        (1, buf.shape[1], W) + (1,) * (buf.ndim - 3))
    rows = jnp.take_along_axis(buf, src, axis=2)         # [L, B, W, ...]

    def one(b, r, start, n):
        # b: [L, S, ...]; r: [L, W, ...] — per-slot contiguous write-back
        old = jax.lax.dynamic_slice_in_dim(b, start, W, axis=1)
        m = (jnp.arange(W) < n).reshape((1, W) + (1,) * (b.ndim - 2))
        return jax.lax.dynamic_update_slice_in_dim(
            b, jnp.where(m, r, old), start, axis=1)

    return jax.vmap(one, in_axes=(1, 1, 0, 0), out_axes=1)(
        buf, rows, base + 1, jnp.asarray(keep, jnp.int32))


def gather_path(cache: "KVCache", base: jax.Array, sel: jax.Array,
                keep: jax.Array) -> "KVCache":
    """Reference-cache accepted-path compaction: apply :func:`path_gather`
    to every leaf and commit the path — each slot's length becomes
    ``base + 1 + keep`` (root row + accepted path), the tree-spec sibling
    of :func:`rewind_lengths`."""
    return dataclasses.replace(
        cache,
        k_q=path_gather(cache.k_q, base, sel, keep),
        k_s=path_gather(cache.k_s, base, sel, keep),
        v_q=path_gather(cache.v_q, base, sel, keep),
        v_s=path_gather(cache.v_s, base, sel, keep),
        lengths=jnp.asarray(base, jnp.int32) + 1 + jnp.asarray(keep, jnp.int32))


def append_layer_chunk(cache: "KVCache", layer: int, k: jax.Array,
                       v: jax.Array, start: jax.Array | int) -> "KVCache":
    """Chunked-prefill append of ``[B, C, H_kv, D_h]`` float k/v into one
    layer of the slotted cache at sequence offset ``start`` (quantized on
    the way in, like :func:`append_layer`)."""
    (k_q, k_s), (v_q, v_s) = quantize_rows(k), quantize_rows(v)
    return dataclasses.replace(
        cache,
        k_q=cache.k_q.at[layer].set(chunk_update(cache.k_q[layer], k_q, start)),
        k_s=cache.k_s.at[layer].set(chunk_update(cache.k_s[layer], k_s, start)),
        v_q=cache.v_q.at[layer].set(chunk_update(cache.v_q[layer], v_q, start)),
        v_s=cache.v_s.at[layer].set(chunk_update(cache.v_s[layer], v_s, start)),
    )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class KVCache:
    k_q: jax.Array
    k_s: jax.Array
    v_q: jax.Array
    v_s: jax.Array
    lengths: jax.Array           # [B] int32 — tokens cached per slot

    @property
    def n_slots(self) -> int:
        return self.k_q.shape[1]

    @property
    def max_len(self) -> int:
        return self.k_q.shape[2]


def init_cache(n_layers: int, n_slots: int, max_len: int, n_kv_heads: int,
               head_dim: int) -> KVCache:
    shape = (n_layers, n_slots, max_len, n_kv_heads * head_dim)
    sshape = (n_layers, n_slots, max_len, n_kv_heads, 1)
    return KVCache(
        k_q=jnp.zeros(shape, jnp.int8),
        k_s=jnp.zeros(sshape, jnp.float32),
        v_q=jnp.zeros(shape, jnp.int8),
        v_s=jnp.zeros(sshape, jnp.float32),
        lengths=jnp.zeros((n_slots,), jnp.int32),
    )


def append_layer(cache: KVCache, layer: int, k: jax.Array, v: jax.Array,
                 pos: jax.Array | int) -> KVCache:
    """Append one step's k/v ([B, T, H_kv, D_h] float) at position ``pos``.

    ``pos`` may be a scalar (all slots aligned — the single-batch paper
    setting) or a [B] vector of heterogeneous per-slot positions.
    """
    (k_q, k_s), (v_q, v_s) = quantize_rows(k), quantize_rows(v)
    return dataclasses.replace(
        cache,
        k_q=cache.k_q.at[layer].set(batched_update(cache.k_q[layer], k_q, pos)),
        k_s=cache.k_s.at[layer].set(batched_update(cache.k_s[layer], k_s, pos)),
        v_q=cache.v_q.at[layer].set(batched_update(cache.v_q[layer], v_q, pos)),
        v_s=cache.v_s.at[layer].set(batched_update(cache.v_s[layer], v_s, pos)),
    )


def bump_length(cache, n: jax.Array | int = 1):
    """Advance per-slot lengths; ``n`` may be scalar or a [B] mask/step."""
    return dataclasses.replace(cache, lengths=cache.lengths + n)


def rewind_lengths(cache, lengths: jax.Array):
    """Speculative-decode rollback on the reference cache: set each slot's
    length to its committed prefix ([B] int32).  The rejected-suffix int8
    rows past the new length are *not* erased — they are dead entries the
    attention mask hides, overwritten in place by the next append (the SLC
    write-in-place discipline that makes rollback a free cursor move).

    Like the rest of this dataclass API (``alloc_slot``/``free_slot``/
    ``bump_length``) this is the property-tested *reference model* of the
    discipline; the serve engine's production rollback is the same cursor
    move on the pooled decode state (``transformer.rewind_pos``)."""
    return dataclasses.replace(
        cache, lengths=jnp.asarray(lengths, jnp.int32))


def alloc_slot(cache, slot: jax.Array | int, length: jax.Array | int):
    """Claim ``slot`` for a request whose prompt occupies ``length`` tokens."""
    return dataclasses.replace(
        cache, lengths=cache.lengths.at[slot].set(jnp.int32(length)))


def free_slot(cache, slot: jax.Array | int):
    """Retire a slot: its length drops to 0 and the stale int8 rows are
    simply overwritten by the next resident (no erase cycle — the SLC
    write-in-place discipline)."""
    return dataclasses.replace(cache, lengths=cache.lengths.at[slot].set(0))


def copy_prefix(cache: KVCache, src: jax.Array | int, dst: jax.Array | int,
                n: jax.Array | int) -> KVCache:
    """Row-range copy between slots: ``dst``'s first ``n`` sequence rows
    become ``src``'s (int8 payload + scales), and ``dst``'s length becomes
    ``n`` — the prefix-cache admission gather.  Rows at or past ``n`` in
    ``dst`` are left as the dead in-place entries they already were (the
    attention mask hides them; the next append overwrites them).

    ``src``/``dst``/``n`` may all be traced scalars, so one compiled gather
    serves every (source leaf, destination slot, match length) triple.
    """
    src = jnp.asarray(src, jnp.int32)
    dst = jnp.asarray(dst, jnp.int32)
    n = jnp.asarray(n, jnp.int32)

    def one(buf: jax.Array) -> jax.Array:
        # buf: [L, B, S, ...] — slot axis 1, sequence axis 2
        row = jax.lax.dynamic_index_in_dim(buf, src, axis=1, keepdims=True)
        old = jax.lax.dynamic_index_in_dim(buf, dst, axis=1, keepdims=True)
        keep = (jnp.arange(buf.shape[2]) < n).reshape(
            (1, 1, buf.shape[2]) + (1,) * (buf.ndim - 3))
        return jax.lax.dynamic_update_slice_in_dim(
            buf, jnp.where(keep, row, old), dst, axis=1)

    return dataclasses.replace(
        cache,
        k_q=one(cache.k_q), k_s=one(cache.k_s),
        v_q=one(cache.v_q), v_s=one(cache.v_s),
        lengths=cache.lengths.at[dst].set(n))


class SlotLedger:
    """Host-side refcounts over pool slots for the prefix cache.

    A slot row in the SLC pool can be held by a trie leaf (the cached
    prefix claims rows ``[0:n)``) and, while a request aliases that leaf,
    by an active writer — one hold each, counted here.  The slot returns
    to the scheduler's free heap exactly when its count drops to zero;
    releasing a hold that was never taken raises (the double-free guard:
    a slot freed twice would be handed to two residents at once and the
    second admission would silently corrupt the first's KV rows).
    """

    def __init__(self) -> None:
        self._counts: dict[int, int] = {}

    def count(self, slot: int) -> int:
        return self._counts.get(slot, 0)

    def incref(self, slot: int) -> int:
        c = self._counts.get(slot, 0) + 1
        self._counts[slot] = c
        return c

    def decref(self, slot: int) -> int:
        c = self._counts.get(slot, 0)
        if c <= 0:
            raise RuntimeError(
                f"slot {slot}: release without a matching hold (double free)")
        c -= 1
        if c:
            self._counts[slot] = c
        else:
            del self._counts[slot]
        return c

    def held(self) -> set[int]:
        return set(self._counts)


class ColdStore:
    """Cold tier of the two-tier KV pool: evicted / preempted slot rows as
    quantized host-side blocks (the flash/SLC-resident side of the paper's
    hybrid — the hot tier is the donated int8 device pool).

    Blocks are keyed opaque pytrees (already truncated to their live rows by
    the swap layer) with LRU order and a row budget.  ``pinned`` blocks —
    swapped-out preemption victims that *must* survive until re-admission —
    are never evicted to make room; demoted prefix-cache leaves are
    best-effort and may be.  ``put`` reports which unpinned keys it evicted
    so the owner (the prefix cache) can drop the matching leaves.
    """

    def __init__(self, row_budget: int) -> None:
        if row_budget < 0:
            raise ValueError("row_budget must be >= 0")
        self.row_budget = int(row_budget)
        # key -> (tree, n_rows, n_bytes, pinned); insertion order is LRU
        self._blocks: dict[Any, tuple[Any, int, int, bool]] = {}
        self.rows_used = 0
        self.bytes_used = 0

    def __len__(self) -> int:
        return len(self._blocks)

    def has(self, key: Any) -> bool:
        return key in self._blocks

    def rows_of(self, key: Any) -> int:
        return self._blocks[key][1]

    def put(self, key: Any, tree: Any, n_rows: int, *,
            pinned: bool = False) -> tuple[bool, list[Any]]:
        """Store a block, evicting unpinned LRU blocks to make room.

        Returns ``(ok, evicted_keys)``; on ``ok=False`` nothing was stored
        (and nothing evicted) — the caller falls back to dropping the rows
        (prefix leaf) or recompute-preemption (swap victim).
        """
        if key in self._blocks:
            self.drop(key)
        need = int(n_rows)
        free = self.row_budget - self.rows_used
        victims = []
        if need > free:
            reclaim = 0
            for k, (_, rows, _, pin) in self._blocks.items():
                if pin:
                    continue
                victims.append(k)
                reclaim += rows
                if need <= free + reclaim:
                    break
            if need > free + reclaim:
                return False, []
        for k in victims:
            self.drop(k)
        n_bytes = cache_bytes(tree)
        self._blocks[key] = (tree, need, n_bytes, bool(pinned))
        self.rows_used += need
        self.bytes_used += n_bytes
        return True, victims

    def pop(self, key: Any) -> tuple[Any, int]:
        """Remove and return ``(tree, n_rows)`` — the swap-in side."""
        tree, n_rows, n_bytes, _ = self._blocks.pop(key)
        self.rows_used -= n_rows
        self.bytes_used -= n_bytes
        return tree, n_rows

    def get(self, key: Any) -> tuple[Any, int]:
        """Peek ``(tree, n_rows)`` without removing — the keep-in-store
        read (a restored block retained as a recovery copy)."""
        tree, n_rows, _, _ = self._blocks[key]
        return tree, n_rows

    def unpin(self, key: Any) -> None:
        """Make a pinned block LRU-evictable: a restored victim's retained
        recovery copy is best-effort, and must not strand row budget."""
        tree, n_rows, n_bytes, _ = self._blocks[key]
        self._blocks[key] = (tree, n_rows, n_bytes, False)

    def pin(self, key: Any) -> None:
        """Make a block eviction-proof again: a retained recovery copy the
        scheduler has committed to restoring from must not vanish under an
        LRU pass before the owner is re-admitted."""
        tree, n_rows, n_bytes, _ = self._blocks[key]
        self._blocks[key] = (tree, n_rows, n_bytes, True)

    def drop(self, key: Any) -> bool:
        if key not in self._blocks:
            return False
        self.pop(key)
        return True

    def touch(self, key: Any) -> None:
        """Refresh LRU recency of ``key`` (a cold-tier hit)."""
        self._blocks[key] = self._blocks.pop(key)


def layer_view(cache: KVCache, layer: int) -> tuple[jax.Array, ...]:
    return (cache.k_q[layer], cache.k_s[layer],
            cache.v_q[layer], cache.v_s[layer])


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class LatentCache:
    """MLA compressed-latent cache (DeepSeek-V3): the SLC region holds the
    576-dim latent instead of per-head K/V — ~14x smaller appends."""
    c_q: jax.Array               # [L, B, S_max, C] int8
    c_s: jax.Array               # [L, B, S_max, 1] f32
    lengths: jax.Array           # [B] int32

    @property
    def n_slots(self) -> int:
        return self.c_q.shape[1]

    @property
    def max_len(self) -> int:
        return self.c_q.shape[2]


def init_latent_cache(n_layers: int, n_slots: int, max_len: int,
                      dim: int) -> LatentCache:
    return LatentCache(
        c_q=jnp.zeros((n_layers, n_slots, max_len, dim), jnp.int8),
        c_s=jnp.zeros((n_layers, n_slots, max_len, 1), jnp.float32),
        lengths=jnp.zeros((n_slots,), jnp.int32),
    )


def append_latent(cache: LatentCache, layer: int, c: jax.Array,
                  pos: jax.Array | int) -> LatentCache:
    amax = jnp.max(jnp.abs(c), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    c_q = jnp.clip(jnp.round(c / scale), -127, 127).astype(jnp.int8)
    return dataclasses.replace(
        cache,
        c_q=cache.c_q.at[layer].set(batched_update(cache.c_q[layer], c_q, pos)),
        c_s=cache.c_s.at[layer].set(batched_update(cache.c_s[layer], scale, pos)),
    )


def cache_bytes(tree: Any) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(tree))

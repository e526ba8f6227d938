"""Cache commit after speculative verification, and per-slot batch surgery
for continuous batching.

Attention caches roll back by *position invalidation*: any slot holding a
position beyond the last accepted token is marked empty (-1) — the next
write reuses it. Recurrent caches (SSM state, RG-LRU h, conv windows) cannot
be invalidated in place, so decode forwards emit per-token snapshots
(models/ssm.py, models/hybrid.py) and commit selects the snapshot of the
last accepted token.

Per-slot surgery (``batch_axes`` / ``write_slot`` / ``reset_slot``) is what
lets the scheduler admit a request *into a live batch*: a prompt is prefilled
as a batch-1 state, then every batched leaf's row 0 is scattered into the
victim slot of the running state. The batch axis of each leaf is inferred
structurally — by diffing abstract evaluations of the same state at two batch
sizes — so the machinery is agnostic to cache layout (stacked super-block
KV, ring buffers, recurrent snapshots, drafter caches alike).

Paged (block) KV layout
-----------------------
``paged_state`` / ``gather_state`` / ``scatter_state`` / ``admit_pages``
re-express every *full-length* attention KV cache (a sub-dict with
``k/v/positions/ring`` whose window equals ``max_len``) as a **shared pool of
fixed-size position pages** plus a per-slot block table:

    contiguous   k (..., B, max_len, KV, hd)
    paged        k (..., n_pool_pages, page, KV, hd)   + table (B, max_len/page)

Pages are the allocation unit (``BlockAllocator``): admission claims
``ceil(need/page)`` pages instead of a full max-length row, EOS returns them,
and a pool of fixed byte size holds as many *requests* as their actual
lengths — not their worst case — allow. Ring (sliding-window) caches and
recurrent leaves (SSM state, conv windows, RG-LRU h) are already
memory-bounded per slot and stay in per-slot rows.

The target's pools are read and written in place by the decode step:
``attach_table`` puts the block table beside each paged KV dict, the
model's decode reads each layer's pages through it and writes only the
step's new rows (``models/layers.paged_view`` / ``paged_update``), and
``commit`` writes the rejected rows empty. Every other paged leaf (a
drafter's cache, or every leaf under the sharded engine) runs on a
*gathered view*: ``gather_state`` reassembles each slot's pages into the
contiguous per-slot layout its forward expects, and ``scatter_state``
writes the updated view back through the table. Either way speculative
rollback-invalidation and recurrent snapshot commit give the same tokens
across layouts.

Swap-to-host (the SWAPPED lifecycle state)
------------------------------------------
Preemption's third page state beyond allocated/free: instead of discarding
a victim's pages and re-paying the prefix as a recompute-prefill, the
engine snapshots the slot with ``extract_slot`` (per-slot rows + gathered
page payloads in one jit), trims the copy host-side to the refcount==1
pages, and parks the bytes in a ``HostPagePool``. Pages shared with the
prefix cache (or another slot) stay *resident* — the swap handle keeps the
slot's reference, pinning them against LRU eviction — so only the
exclusive remainder moves. Swap-in re-admits the host bytes through
``admit_pages`` with a ``scatter_row`` that masks the still-resident
pages, which makes resume a pure device scatter: bitwise the state the
victim had at its eviction step boundary, for attention KV, recurrent
stream state, and sampling/logprob rows alike.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.models import layers as L

Array = jax.Array
_SNAP_LEAVES = ("state", "conv", "h")
NO_BATCH = -1          # batch_axes sentinel: leaf has no batch dimension

# paged-spec leaf tags (structure-matched int pytree over a decode state)
NOT_PAGED = 0          # per-slot leaf: handled by write_slot/reset_slot
PAGED_KV = 1           # k/v pool leaf: pages on axis -4
PAGED_POS = 2          # positions pool leaf: pages on axis -2


def _path_str(path) -> str:
    parts = []
    for pe in path:
        parts.append(str(getattr(pe, "key", getattr(pe, "idx", pe))))
    return "/".join(parts)


def commit(cache, snapshots, commit_pos: Array, accept_idx: Array,
           block: int = 1):
    """cache: model cache pytree; snapshots: matching pytree from
    ModelOutput.aux["snapshots"] (or None for attention-only models);
    commit_pos (B,): last valid absolute position; accept_idx (B,): index of
    the last committed token within the just-verified block; ``block``: the
    number of rows the step wrote per slot (K+1). A paged KV cache (pools
    with their block table, ``L.is_paged``) has only its rejected rows,
    ``commit_pos+1 .. commit_pos - accept_idx + block - 1``, written empty
    in place."""
    snap_map = {}
    if snapshots is not None:
        flat, _ = jax.tree_util.tree_flatten_with_path(snapshots)
        snap_map = {_path_str(p): l for p, l in flat}

    def fix(path, leaf):
        if L.is_paged(leaf):
            return _reject_rows(leaf, commit_pos, accept_idx, block)
        ps = _path_str(path)
        name = ps.rsplit("/", 1)[-1]
        if name == "positions":
            # leaf (..., B, W); B is dim -2
            cp = commit_pos.reshape((1,) * (leaf.ndim - 2) + (-1, 1))
            return jnp.where(leaf > cp, -1, leaf)
        if name in _SNAP_LEAVES and ps in snap_map:
            snap = snap_map[ps]                    # cache leaf + extra T axis
            stacked = snap.ndim == leaf.ndim + 1
            t_axis = 2 if ps.startswith("blocks") else 1
            b_axis = t_axis - 1
            idx = accept_idx.reshape(
                (1,) * b_axis + (-1,) + (1,) * (snap.ndim - b_axis - 1))
            sel = jnp.take_along_axis(snap, idx, axis=t_axis)
            return jnp.squeeze(sel, axis=t_axis).astype(leaf.dtype)
        return leaf

    return jax.tree_util.tree_map_with_path(fix, cache, is_leaf=L.is_paged)


def _reject_rows(cache: dict, commit_pos: Array, accept_idx: Array,
                 block: int) -> dict:
    """Positions -1 at the rows a step wrote past ``commit_pos`` (at most
    ``block - 1`` a slot), in every layer the pools stack."""
    n = block - 1
    if n <= 0:
        return cache

    def one(pos, table):
        idx, _ = L.paged_rows({"positions": pos, "block_table": table},
                              commit_pos + 1, n)
        pg, off = idx
        rejected = jnp.arange(n)[None, :] < (n - accept_idx)[:, None]
        pg = jnp.where(rejected, pg, pos.shape[-2])
        return pos.at[pg, off].set(-1, mode="drop")

    for _ in range(cache["block_table"].ndim - 2):     # stacked layers
        one = jax.vmap(one)
    return {**cache, "positions": one(cache["positions"],
                                      cache["block_table"])}


# ---------------------------------------------------------------------------
# per-slot batch surgery (continuous batching)
# ---------------------------------------------------------------------------

def batch_axes(tree_b1, tree_b2):
    """Infer each leaf's batch axis by diffing two abstract evaluations of the
    same pytree built at two different batch sizes (jax.eval_shape — no device
    work). Returns a matching pytree of ints: the first axis whose extent
    differs, or ``NO_BATCH`` for leaves without a batch dimension (scalar
    counters, ring flags)."""
    def ax(a, b):
        for i, (x, y) in enumerate(zip(a.shape, b.shape)):
            if x != y:
                return i
        return NO_BATCH
    return jax.tree.map(ax, tree_b1, tree_b2)


def write_slot(dst, src, slot: Array, axes):
    """Scatter batch row 0 of ``src`` (a batch-1 state/cache pytree) into
    batch row ``slot`` of ``dst``. Leaves without a batch axis (``axes`` leaf
    == NO_BATCH: scalar counters, ring flags) keep their dst value.
    jit-friendly: ``slot`` may be traced; ``axes`` must be static."""
    def w(d, s, ax):
        if ax < 0:
            return d
        row = jax.lax.index_in_dim(s, 0, axis=ax, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(
            d, row.astype(d.dtype), slot, axis=ax)
    return jax.tree.map(w, dst, src, axes)


def reset_slot(tree, slot: Array, axes, fills: Optional[dict] = None):
    """Blank batch row ``slot``: cache ``positions`` leaves become -1 (empty —
    nothing to attend), and so does the ``drafts`` log (no draft proposed);
    every other batched leaf becomes 0. ``fills`` overrides
    the fill value by leaf name (e.g. {"new_count": max_new} to keep a freed
    slot frozen under the Engine's budget check). Leaves without a batch axis
    are untouched."""
    fills = fills or {}

    def r(path, d, ax):
        if ax < 0:
            return d
        name = _path_str(path).rsplit("/", 1)[-1]
        fill = fills.get(name, -1 if name in ("positions", "drafts") else 0)
        shape = list(d.shape)
        shape[ax] = 1
        row = jnp.full(shape, fill, d.dtype)
        return jax.lax.dynamic_update_slice_in_dim(d, row, slot, axis=ax)

    return jax.tree_util.tree_map_with_path(r, tree, axes)


# ---------------------------------------------------------------------------
# paged (block) KV layout
# ---------------------------------------------------------------------------

class BlockAllocator:
    """Host-side refcounted free-list allocator over a fixed pool of KV
    pages.

    ``alloc(n)`` pops n page ids at refcount 1 (returns None — allocating
    nothing — when the pool can't satisfy the request, so admission can
    simply wait); ``free(pages)`` drops one reference per page and returns
    a page to the free list only when its count reaches zero. ``incref``
    adds owners — the prefix cache shares one physical page between its
    index and every slot whose block table maps it, so a page may outlive
    the request that prefilled it. Double-free (decref past zero) and
    foreign ids raise: leaked or aliased pages corrupt neighbouring
    requests silently, so the allocator is the loud line of defense."""

    def __init__(self, n_pages: int):
        if n_pages <= 0:
            raise ValueError(f"need a positive pool, got {n_pages}")
        self.n_pages = n_pages
        self._free: List[int] = list(range(n_pages - 1, -1, -1))
        self._ref: Dict[int, int] = {}   # page id -> reference count (>= 1)
        self.peak_used = 0     # high-water mark (honest residency metrics)

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_used(self) -> int:
        return len(self._ref)

    def alloc(self, n: int) -> Optional[List[int]]:
        """Pop ``n`` page ids off the free list at refcount 1 (LIFO —
        freshly freed pages are reused first, which keeps the working set
        compact).

        Returns the page ids, or None — allocating *nothing* — when fewer
        than ``n`` pages are free, so a caller can atomically wait/preempt
        instead of holding a partial claim. Raises on negative ``n``.

        A recycled page may carry the previous owner's stale bytes: every
        acquisition path must blank or fully overwrite it (admission
        scatters cover admission; ``Engine.ensure_capacity`` blanks growth
        pages explicitly — blanking at free time is impossible now that
        cached pages survive their request)."""
        if n < 0:
            raise ValueError(f"alloc({n})")
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._ref[p] = 1
        self.peak_used = max(self.peak_used, len(self._ref))
        return pages

    def incref(self, pages: List[int]) -> None:
        """Add one owner to each page (block-table sharing / CoW-source
        pinning / prefix-cache insertion). Raises on a page that is not
        currently allocated — sharing a free page would alias whatever the
        free list hands out next."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"incref of page {p} not currently allocated")
        for p in pages:
            self._ref[p] += 1

    def refcount(self, page: int) -> int:
        """Current owner count of ``page`` (0 when free)."""
        return self._ref.get(page, 0)

    def free(self, pages: List[int]) -> None:
        """Drop one reference per page; a page returns to the pool only at
        refcount zero (shared pages survive until their last owner lets
        go). Raises on a page that is not currently allocated (double-free
        past zero, or a foreign id) — silent aliasing would corrupt a
        neighbouring request's KV."""
        for p in pages:
            if p not in self._ref:
                raise ValueError(f"free of page {p} not currently allocated")
        for p in pages:
            self._ref[p] -= 1
            if self._ref[p] == 0:
                del self._ref[p]
                self._free.append(p)

    def reset_stats(self) -> None:
        """Restart the ``peak_used`` high-water mark at the CURRENT
        residency. Multi-phase benchmark runs (table12/13/16/19 compare
        disciplines or warm-up vs measured passes in one process) call this
        between phases so each phase reports its own honest peak instead of
        the max across every phase so far."""
        self.peak_used = self.n_used


class HostPagePool:
    """Byte-budgeted host-side store for swapped-out requests (the SWAPPED
    page-lifecycle state). Entries are opaque handles keyed by request id;
    the pool only does byte accounting — ``put`` refuses (returns False)
    when the budget would overflow, which is the scheduler's signal to fall
    back to recompute-prefill preemption instead of crashing or stalling.
    ``peak_used``/``reset_stats`` mirror the BlockAllocator's high-water
    discipline so multi-phase benchmarks report honest per-phase peaks."""

    def __init__(self, capacity_bytes: int = 0):
        if capacity_bytes < 0:
            raise ValueError(f"host_pool_bytes={capacity_bytes}")
        self.capacity = int(capacity_bytes)   # 0 = unbounded
        self._entries: Dict[object, tuple] = {}   # key -> (handle, nbytes)
        self.used_bytes = 0
        self.peak_used = 0

    def __contains__(self, key) -> bool:
        return key in self._entries

    def __len__(self) -> int:
        return len(self._entries)

    def can_store(self, nbytes: int) -> bool:
        """Whether ``nbytes`` more would still fit the budget."""
        return self.capacity <= 0 or self.used_bytes + nbytes <= self.capacity

    def put(self, key, handle, nbytes: int) -> bool:
        """Store ``handle`` under ``key``; False (storing nothing) when the
        budget can't hold it. Duplicate keys raise — two live snapshots of
        one request would mean a lost or double resume."""
        if key in self._entries:
            raise ValueError(f"swap handle for {key!r} already stored")
        nbytes = int(nbytes)
        if not self.can_store(nbytes):
            return False
        self._entries[key] = (handle, nbytes)
        self.used_bytes += nbytes
        self.peak_used = max(self.peak_used, self.used_bytes)
        return True

    def get(self, key):
        """The stored handle, or None."""
        ent = self._entries.get(key)
        return None if ent is None else ent[0]

    def pop(self, key):
        """Remove and return the handle, releasing its bytes (swap-in
        consumed it, or an abort/fallback dropped it). Missing keys raise —
        like the allocator, double-free means corrupted bookkeeping."""
        if key not in self._entries:
            raise KeyError(f"no swap handle for {key!r}")
        handle, nbytes = self._entries.pop(key)
        self.used_bytes -= nbytes
        return handle

    def reset_stats(self) -> None:
        """Restart the ``peak_used`` high-water mark at current usage (same
        contract as BlockAllocator.reset_stats)."""
        self.peak_used = self.used_bytes


def _is_paged_dict(d: dict, max_len: int) -> bool:
    """A pageable KV cache: the make_kv_cache contract (k/v/positions/ring)
    at full length. Ring caches (positions window < max_len) are already
    memory-bounded and stay per-slot; so do recurrent leaves and the encdec
    cross K/V (no positions leaf)."""
    if not (isinstance(d, dict)
            and {"k", "v", "positions", "ring"} <= set(d.keys())):
        return False
    return d["positions"].shape[-1] == max_len


def has_ring_cache(cache_tree, max_len: int) -> bool:
    """Whether any attention KV cache in the tree is a ring (sliding-window)
    buffer — positions window shorter than max_len. Ring caches wrap on
    write (slot = pos % W), so right-padding a prefill past the window
    would evict live prompt entries; callers must chunk instead of pad."""
    found = False

    def walk(node):
        nonlocal found
        if isinstance(node, dict):
            if {"k", "v", "positions", "ring"} <= set(node.keys()):
                found |= node["positions"].shape[-1] != max_len
                return
            for v in node.values():
                walk(v)
        elif isinstance(node, (list, tuple)):
            for v in node:
                walk(v)

    walk(cache_tree)
    return found


def paged_spec(cache_tree, max_len: int):
    """Structure-matched int pytree tagging each leaf of a decode-state (or
    cache) subtree: PAGED_KV / PAGED_POS for pool leaves, NOT_PAGED
    otherwise. Computed from the *contiguous* template; the same spec
    addresses both layouts since paging preserves tree structure."""
    def walk(node):
        if isinstance(node, dict):
            if _is_paged_dict(node, max_len):
                return {k: (PAGED_KV if k in ("k", "v")
                            else PAGED_POS if k == "positions"
                            else NOT_PAGED) for k in node}
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v) for v in node)
        return NOT_PAGED
    return walk(cache_tree)


def _page_axis(tag: int) -> int:
    # pool page axis: k/v (..., NP, page, KV, hd) → -4; positions (..., NP,
    # page) → -2. Same offsets index the (B, W) axes of the contiguous view.
    return -4 if tag == PAGED_KV else -2


def paged_pool(leaf, tag: int, page: int, n_pool_pages: int):
    """Pool counterpart of one contiguous cache leaf: the (B, W) axes become
    (n_pool_pages, page), leading stack axes are preserved. positions init
    to -1 (empty), K/V to zero."""
    ax = _page_axis(tag)
    stack = leaf.shape[:leaf.ndim + ax]             # dims before (B, W)
    tail = leaf.shape[leaf.ndim + ax + 2:]
    shape = stack + (n_pool_pages, page) + tail
    fill = -1 if tag == PAGED_POS else 0
    return jnp.full(shape, fill, leaf.dtype)


def paged_state(state_tree, spec, page: int, n_pool_pages: int):
    """Rebuild a contiguous decode state with every paged leaf replaced by
    its pool. Non-paged leaves are kept as-is (same objects)."""
    return jax.tree.map(
        lambda leaf, tag: leaf if tag == NOT_PAGED
        else paged_pool(leaf, tag, page, n_pool_pages), state_tree, spec)


def gather_pages(pool, table: Array, tag: int):
    """pool (..., NP, page, ...) + table (B, nb) → contiguous view
    (..., B, nb*page, ...). Unallocated table entries (-1) read page 0 but
    their positions are forced to -1, so the view region is *empty* — K/V
    garbage under an empty position is masked by every attention path."""
    ax = _page_axis(tag)
    nd = pool.ndim
    B, nb = table.shape
    view = jnp.take(pool, jnp.clip(table, 0, None), axis=nd + ax)
    # (..., B, nb, page, ...) → merge (nb, page)
    shape = (view.shape[:nd + ax] + (B, nb * pool.shape[nd + ax + 1])
             + view.shape[nd + ax + 3:])
    view = view.reshape(shape)
    if tag == PAGED_POS:
        invalid = jnp.repeat(table < 0, pool.shape[-1], axis=1)   # (B, W)
        view = jnp.where(invalid, -1, view)
    return view


def scatter_pages(pool, view, table: Array, tag: int):
    """Inverse of gather_pages: write the per-slot view back through the
    block table. Rows of unallocated pages (table -1) are dropped (their
    index is forced out of range). Indexing stays on the native page axis —
    no transposes, so XLA lowers a single scatter."""
    ax = pool.ndim + _page_axis(tag)             # absolute page axis
    B, nb = table.shape
    page = pool.shape[ax + 1]
    blocks = view.reshape(view.shape[:ax] + (B * nb, page)
                          + view.shape[ax + 2:])
    idx = jnp.where(table < 0, pool.shape[ax], table).reshape(-1)
    sl = (slice(None),) * ax + (idx,)
    return pool.at[sl].set(blocks.astype(pool.dtype), mode="drop")


def gather_state(pstate, table: Array, spec):
    """Paged decode state → contiguous per-slot view (non-paged leaves pass
    through untouched)."""
    return jax.tree.map(
        lambda leaf, tag: leaf if tag == NOT_PAGED
        else gather_pages(leaf, table, tag), pstate, spec)


def scatter_state(pstate, view_state, table: Array, spec):
    """Contiguous view (post-step) → paged state: paged leaves scatter into
    their pools, everything else takes the stepped view value."""
    return jax.tree.map(
        lambda pool, view, tag: view if tag == NOT_PAGED
        else scatter_pages(pool, view, table, tag), pstate, view_state, spec)


def attach_table(tree, spec, table: Array):
    """``tree`` with the block table (B, nb) beside the pools of each paged
    KV dict (``spec`` tags them), broadcast over the pools' leading stack
    axes as every other leaf of the dict is stacked: the paged cache the
    model's decode reads and writes in place (``L.is_paged``)."""
    def walk(node, sp):
        if isinstance(node, dict):
            if sp.get("positions") == PAGED_POS:
                stack = node["positions"].shape[:-2]
                return {**node, "block_table": jnp.broadcast_to(
                    table, stack + table.shape)}
            return {k: walk(v, sp[k]) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            return type(node)(walk(v, s) for v, s in zip(node, sp))
        return node
    return walk(tree, spec)


def detach_table(tree):
    """Inverse of ``attach_table``: drop the block table from each paged
    KV dict."""
    return jax.tree.map(
        lambda d: {k: v for k, v in d.items() if k != "block_table"}
        if L.is_paged(d) else d, tree, is_leaf=L.is_paged)


def take_pools(tree, spec):
    """``(pools, rest)``: the leaves ``spec`` tags paged, as a tuple in
    flatten order, and ``tree`` with None in their place. The decode step
    takes the pools as their own argument, so that they alone are
    donated."""
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    tags = jax.tree_util.tree_leaves(spec)
    pools = tuple(x for x, t in zip(leaves, tags) if t != NOT_PAGED)
    rest = treedef.unflatten([None if t != NOT_PAGED else x
                              for x, t in zip(leaves, tags)])
    return pools, rest


def put_pools(rest, pools):
    """Inverse of ``take_pools``."""
    leaves, treedef = jax.tree_util.tree_flatten(
        rest, is_leaf=lambda x: x is None)
    it = iter(pools)
    return treedef.unflatten([next(it) if x is None else x for x in leaves])


def blank_pages(pstate, table_row: Array, spec):
    """Mark every position slot of the pages in ``table_row`` (nb,) empty
    (-1). A recycled page MUST read as empty at ACQUISITION time:
    incremental growth (``Engine.ensure_capacity``) splices a pool page
    into another slot's table without the full-row overwrite an admission
    does, so a stale positions entry would resurrect the previous owner's
    KV as attendable history. Blanking runs on alloc, not free — a freed
    page may still be mapped by the prefix cache or a sharing slot, and
    blanking it at free time would corrupt the surviving owners' history.
    K/V bytes are left in place — empty positions mask them on every
    attention path. Unallocated entries (-1) are dropped."""
    def blank(pool, tag):
        if tag != PAGED_POS:
            return pool
        ax = pool.ndim + _page_axis(tag)
        nb, page = table_row.shape[0], pool.shape[ax + 1]
        view = jnp.full(pool.shape[:ax] + (1, nb * page), -1, pool.dtype)
        return scatter_pages(pool, view, table_row[None], tag)
    return jax.tree.map(blank, pstate, spec)


def copy_page(pstate, src: Array, dst: Array, spec):
    """Copy one pool page — K/V bytes and positions alike — from page id
    ``src`` to page id ``dst`` across every paged leaf. This is the
    copy-on-write step of prefix caching: a cached page whose token chain
    matches but whose content a new request must amend (the divergent last
    drafter entry) is duplicated into a freshly allocated page the slot
    owns, leaving the shared original byte-stable for its other owners.
    ``src``/``dst`` may be traced scalars, so one trace serves every page
    pair."""
    def cp(pool, tag):
        if tag == NOT_PAGED:
            return pool
        ax = pool.ndim + _page_axis(tag)
        page = jax.lax.dynamic_index_in_dim(pool, src, axis=ax, keepdims=True)
        return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=ax)
    return jax.tree.map(cp, pstate, spec)


def admit_pages(pstate, src, slot: Array, table_row: Array, axes, spec,
                scatter_row: Optional[Array] = None):
    """Admit a batch-1 contiguous state ``src`` into a paged state: per-slot
    leaves go through ``write_slot`` (pool leaves have no batch axis in the
    paged layout, so the inferred ``axes`` skip them automatically), paged
    leaves scatter src row 0 into the pages of ``table_row`` (nb,).

    ``scatter_row`` (default: ``table_row``) selects which of the row's
    pages actually receive the src view — a prefix-cache hit masks the
    shared prefix pages to -1 (dropped by ``scatter_pages``) so admission
    writes only the freshly prefilled suffix pages and never touches pages
    other slots (or the cache index) still map."""
    out = write_slot(pstate, src, slot, axes)
    sr = table_row if scatter_row is None else scatter_row

    def admit(pool, s, tag):
        if tag == NOT_PAGED:
            return pool
        return scatter_pages(pool, jax.lax.index_in_dim(
            s, 0, axis=s.ndim + _page_axis(tag), keepdims=True),
            sr[None], tag)

    return jax.tree.map(admit, out, src, spec)


def view_width_axis(ndim: int, tag: int) -> int:
    """Absolute index of the W (position-within-slot) axis of a contiguous
    view leaf with ``ndim`` dims — one right of where the pool's page axis
    sits. Host-side swap code uses this to slice page spans (page ``i``
    occupies ``[i*page, (i+1)*page)`` along this axis) out of / back into
    the gathered view with plain numpy indexing."""
    return ndim + _page_axis(tag) + 1


def extract_slot(pstate, slot: Array, table_row: Array, axes, spec):
    """Inverse of ``admit_pages``: re-express batch row ``slot`` of a paged
    state as a batch-1 *contiguous* state — per-slot leaves slice their
    ``slot`` row, paged leaves gather the row's pages (``table_row`` (nb,))
    into the per-slot view. Leaves without a batch axis (global counters)
    pass through unchanged; restore paths must ignore them (``write_slot``
    already does). This is the device half of swap-out: one jit-friendly
    gather whose output, round-tripped through host memory, re-admits
    bitwise via ``admit_pages`` — unallocated table entries (-1) read as
    empty positions exactly as ``gather_pages`` guarantees, and the matching
    swap-in drops those spans via its ``scatter_row`` mask."""
    def ex(leaf, ax, tag):
        if tag != NOT_PAGED:
            return gather_pages(leaf, table_row[None], tag)
        if ax < 0:
            return leaf
        return jax.lax.dynamic_slice_in_dim(leaf, slot, 1, axis=ax)
    return jax.tree.map(ex, pstate, axes, spec)

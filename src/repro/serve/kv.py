"""Block-paged per-request KV/state management for the serve engine.

The continuous-batching engine joins and retires requests mid-stream, but
model decode caches are dense ``(batch, ..., seq, ...)`` arrays compiled
for a bucket shape.  :class:`PagedKV` bridges the two, vLLM-style: every
request owns an isolated logical KV sequence stored as fixed-size **pages**
of device-resident pools, mapped through a per-request :class:`PageTable`.
Each engine step the executor *materializes* the batch's rows: one jitted
gather on the device builds the dense cache (padded to the bucket) from
the pools, through a per-token index table of a few kilobytes.  The
compiled step runs on that copy, then *harvest* scatters only the newly
written slots, and each row's recurrent state, back into the pools, also
on the device.  Nothing but the index tables crosses the host link.
Retiring a request returns its pages to a free list, so memory is reused
across the stream and no page is ever shared between two live requests.

The host keeps the allocator's bookkeeping: the free lists
(:class:`PagePool`), each request's pages and length, and the page-demand
pre-check that lets a capacity failure raise before anything changes.

**The page size is a constant of the manager.**  It sets the allocation
granularity and nothing else a step can observe: the pool holds
``ceil(capacity_tokens / page_size) * page_size + 1`` token rows, so for
every page size that divides the capacity the gather and scatter lower
to the same programs, and for every page size that divides ``max_len``
any batch of requests that fits the capacity fits the pages.  A search
over it would choose between identical behaviours by noise, and would
keep a pool per candidate alive while requests admitted under each
drained; a caller that wants one page per request passes
``page_size=max_len``.

Cache pytree leaves are classified by the model's logical axes
(``model.cache_axes(cfg)``), so the manager is generic across mixers:

* ``seq_kv`` in axes      -> **paged**: a pool of token rows, the leaf's
  axes with the batch axis dropped and the sequence axis holding tokens
  (attention/MLA KV),
* ``batch`` without seq   -> **row state**: a pool of row slots, one per
  request that fits the capacity plus the template row, the leaf's axes
  with slots on the batch axis (SSM/RWKV recurrent state, O(1) in
  sequence length),
* neither                 -> **shared** (e.g. ``slot_pos``), uploaded
  from the template every step.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import telemetry

__all__ = ["PageError", "PagePool", "PageTable", "PagedKV"]

#: An index past every pool: a gather reads zeros there, a scatter drops it.
_NOWHERE = np.iinfo(np.int32).max


class PageError(RuntimeError):
    """Page-allocator invariant violation (double free, foreign page,
    out of pages)."""


class PagePool:
    """Fixed-capacity page allocator with a LIFO free list.

    LIFO reuse keeps recently retired pages hot in cache and makes
    free-list reuse observable in tests: the next alloc after a retire
    returns the just-freed page.
    """

    def __init__(self, num_pages: int, page_size: int):
        if num_pages <= 0:
            raise ValueError(f"num_pages must be positive, got {num_pages}")
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free: list[int] = list(range(self.num_pages - 1, -1, -1))
        self._live: set[int] = set()
        self.allocs = 0
        self.frees = 0
        self.high_water = 0

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_pages(self) -> int:
        return len(self._live)

    def alloc(self) -> int:
        if not self._free:
            raise PageError(f"out of pages ({self.num_pages} total, "
                            f"{len(self._live)} live)")
        pid = self._free.pop()
        self._live.add(pid)
        self.allocs += 1
        self.high_water = max(self.high_water, len(self._live))
        return pid

    def free(self, pid: int) -> None:
        if pid < 0 or pid >= self.num_pages:
            raise PageError(f"page {pid} does not belong to this pool "
                            f"(capacity {self.num_pages})")
        if pid not in self._live:
            raise PageError(f"double free of page {pid}")
        self._live.remove(pid)
        self._free.append(pid)
        self.frees += 1


@dataclasses.dataclass
class PageTable:
    """One request's logical KV sequence: its pages and token length."""

    rid: str
    pages: list[int] = dataclasses.field(default_factory=list)
    length: int = 0                      # tokens written so far
    slot: int | None = None              # row-state slot, from 1st harvest

    def pool_rows(self, ps: int, start: int, n: int) -> np.ndarray:
        """The pool's token rows behind slots ``[start, start + n)``, for
        pages of ``ps`` tokens."""
        slots = np.arange(start, start + n)
        return np.asarray(self.pages, np.int32)[slots // ps] * ps + slots % ps


# -- leaf classification --------------------------------------------------------

_PAGED, _ROW, _SHARED = "paged", "row", "shared"


@dataclasses.dataclass
class _LeafSpec:
    kind: str
    bat_i: int | None       # batch axis index in the original layout
    seq_i: int | None       # seq_kv axis index in the original layout
    shape: tuple            # original template shape (batch dim == 1)
    dtype: Any
    template: "np.ndarray | None" = None   # shared leaves: uploaded as is

    def token_shape(self) -> tuple:
        """One token's slice of a paged leaf: its axes but batch and seq."""
        return tuple(n for d, n in enumerate(self.shape)
                     if d not in (self.bat_i, self.seq_i))


# -- the device programs ----------------------------------------------------------
#
# A paged pool is token-major, ``(tokens + 1, *token_shape)``: the TPU's
# gather and scatter index the major axis in place, where a pool in the
# leaf's own axis order is first copied whole into that layout (both
# ways, every step).  Its last row stays zero: slots past a row's length
# and padding rows read it.  A row-state pool keeps the leaf's axis order
# with slots on the batch axis: the request slots, then the template row,
# then a sink that padding rows write (a row is written with an in-place
# dynamic update, which cannot drop a write).

def _to_leaf(x, bat_i: int, seq_i: int):
    """``(B, S, *token_shape)`` -> the leaf's own axis order."""
    rest = [d for d in range(x.ndim) if d not in (bat_i, seq_i)]
    return jnp.transpose(x, [0 if d == bat_i else 1 if d == seq_i
                             else 2 + rest.index(d) for d in range(x.ndim)])


def _from_leaf(x, bat_i: int, seq_i: int):
    """The leaf's own axis order -> ``(B, S, *token_shape)``."""
    return jnp.transpose(x, [bat_i, seq_i] + [
        d for d in range(x.ndim) if d not in (bat_i, seq_i)])


@functools.partial(jax.jit, static_argnames=("paged", "row"))
def _gather(pools, row_pools, table, *, paged, row):
    """The dense paged and row-state leaves of one step.

    ``table (B, S + 1)``: each row's pool token rows (``_NOWHERE`` past
    its length and on padding rows), then its row-state slot.
    ``pools[i]`` is the i-th paged leaf's pool, whose (batch, seq) axes
    are ``paged[i]``; ``row`` holds the batch axes of ``row_pools``."""
    tokens, slot = table[:, :-1], table[:, -1]
    b, s = tokens.shape
    out = []
    for pool, (bat_i, seq_i) in zip(pools, paged):
        # the clip sends _NOWHERE to the pool's last row, which stays zero
        leaf = jnp.take(pool, tokens.reshape(-1), axis=0, mode="clip")
        out.append(_to_leaf(leaf.reshape((b, s) + pool.shape[1:]),
                            bat_i, seq_i))
    for pool, bat_i in zip(row_pools, row):
        out.append(jnp.take(pool, slot, axis=bat_i, mode="clip"))
    return out


@functools.partial(jax.jit, static_argnames=("paged", "row"),
                   donate_argnums=(0, 1))
def _scatter(pools, row_pools, new_paged, new_row, table, *, paged, row):
    """Write one step's new slots and row state into the (donated) pools.

    ``table (B, W + 2)``: the pool token rows of the ``W`` slots from
    each row's window start (``_NOWHERE`` for a slot it did not write),
    the window start, and its row-state slot (the sink for padding
    rows)."""
    w = table.shape[1] - 2
    dest, start, slot = table[:, :w], table[:, w], table[:, w + 1]
    b = table.shape[0]
    out_pools = []
    for pool, leaf, (bat_i, seq_i) in zip(pools, new_paged, paged):
        size = list(leaf.shape)
        size[bat_i], size[seq_i] = 1, w
        window = []
        for r in range(b):
            at = [0] * leaf.ndim
            at[bat_i], at[seq_i] = r, start[r]
            window.append(lax.dynamic_slice(leaf, at, size))
        window = _from_leaf(jnp.concatenate(window, axis=bat_i), bat_i, seq_i)
        window = window.reshape((b * w,) + window.shape[2:])
        out_pools.append(pool.at[dest.reshape(-1)].set(
            window.astype(pool.dtype), mode="drop"))
    out_rows = []
    for pool, leaf, bat_i in zip(row_pools, new_row, row):
        for r in range(b):
            pool = lax.dynamic_update_slice_in_dim(
                pool, lax.slice_in_dim(leaf, r, r + 1, axis=bat_i).astype(
                    pool.dtype), slot[r], axis=bat_i)
        out_rows.append(pool)
    return out_pools, out_rows


class PagedKV:
    """Block-paged state manager over an arbitrary cache pytree.

    ``template`` is a cache built for ``batch=1`` at full ``max_len``
    (``model.init_cache(cfg, 1, max_len, opts)``); ``axes`` is the
    matching logical-axes pytree (``model.cache_axes(cfg)``).  The
    manager owns one device pool per paged leaf and one row-slot pool per
    row-state leaf, all built here on ``device`` (``None``: the default
    device); each step's dense cache is a fresh gathered copy the step
    program may donate.

    ``capacity_tokens`` bounds the paged pools, cut into pages of
    ``page_size`` tokens, and ``capacity_tokens // max_len`` requests
    hold row state at once.
    """

    def __init__(self, template: Any, axes: Any, *, max_len: int,
                 capacity_tokens: int, page_size: int = 16,
                 device: Any = None):
        self.device = device
        if max_len <= 0:
            raise ValueError(f"max_len must be positive, got {max_len}")
        if page_size <= 0:
            raise ValueError(f"page_size must be positive, got {page_size}")
        if capacity_tokens < max_len:
            raise ValueError(f"capacity_tokens ({capacity_tokens}) below "
                             f"max_len ({max_len}): one request cannot fit")
        self.max_len = int(max_len)
        self.capacity_tokens = int(capacity_tokens)
        t_leaves, self._treedef = jax.tree_util.tree_flatten(template)
        a_leaves, _ = jax.tree_util.tree_flatten(
            axes, is_leaf=lambda x: isinstance(x, tuple))
        if len(t_leaves) != len(a_leaves):
            raise ValueError(
                f"template has {len(t_leaves)} leaves but axes has "
                f"{len(a_leaves)}; the pytrees must match")
        self._leaves: list[_LeafSpec] = []
        self._rows: list = []                # row-state pools, in leaf order
        slots = self.capacity_tokens // self.max_len
        for leaf, ax in zip(t_leaves, a_leaves):
            ax = tuple(ax)
            if len(ax) != np.ndim(leaf):
                raise ValueError(f"axes {ax} do not match leaf shape "
                                 f"{np.shape(leaf)}")
            bat_i = ax.index("batch") if "batch" in ax else None
            seq_i = ax.index("seq_kv") if "seq_kv" in ax else None
            if seq_i is not None and bat_i is None:
                raise ValueError(f"leaf with axes {ax} has seq_kv but no "
                                 f"batch axis; cannot page it per request")
            shape, dtype = tuple(np.shape(leaf)), np.dtype(leaf.dtype)
            if seq_i is not None:
                if shape[seq_i] != self.max_len:
                    raise ValueError(
                        f"paged leaf seq capacity {shape[seq_i]} != "
                        f"max_len {self.max_len}; windowed (SWA) caches "
                        f"are not pageable per request")
                self._leaves.append(_LeafSpec(_PAGED, bat_i, seq_i, shape,
                                              dtype))
            elif bat_i is not None:
                self._leaves.append(_LeafSpec(_ROW, bat_i, None, shape,
                                              dtype))
                # the request slots, the template row (what padding rows
                # and fresh requests read) and the sink
                row = jax.device_put(np.asarray(leaf), device)
                self._rows.append(jnp.repeat(row, slots + 2, axis=bat_i))
            else:
                # Shared leaves are kept on host and re-uploaded each
                # materialize: handlers may donate the cache argument, so
                # a device buffer handed out once cannot be reused.
                self._leaves.append(_LeafSpec(
                    _SHARED, None, None, shape, dtype,
                    template=np.asarray(leaf).copy()))
        self._paged_idx = [i for i, l in enumerate(self._leaves)
                           if l.kind == _PAGED]
        self._row_idx = [i for i, l in enumerate(self._leaves)
                         if l.kind == _ROW]
        self._paged_axes = tuple((self._leaves[i].bat_i,
                                  self._leaves[i].seq_i)
                                 for i in self._paged_idx)
        self._row_axes = tuple(self._leaves[i].bat_i for i in self._row_idx)
        self._slots = PagePool(slots, 1) if self._row_idx else None
        self._template_slot, self._sink_slot = slots, slots + 1
        num_pages = max(1, math.ceil(self.capacity_tokens / page_size))
        self._pages = PagePool(num_pages, page_size)
        rows = num_pages * self._pages.page_size + 1     # the last stays zero
        self._pools = [jnp.zeros((rows,) + self._leaves[i].token_shape(),
                                 self._leaves[i].dtype, device=device)
                       for i in self._paged_idx]
        self._tables: dict[str, PageTable] = {}
        self._wide = 1                       # widest multi-token scatter

    # -- request lifecycle ------------------------------------------------------
    def join(self, rid: str) -> PageTable:
        """Admit a request; pages are allocated lazily as tokens are
        written, and a row-state slot at its first harvest (until then it
        reads as the template row)."""
        if rid in self._tables:
            raise PageError(f"request {rid!r} already live")
        table = PageTable(rid=rid)
        self._tables[rid] = table
        return table

    def retire(self, rid: str) -> int:
        """Free a request's pages back to the pool.  Returns the number of
        pages released."""
        table = self._tables.pop(rid, None)
        if table is None:
            raise PageError(f"request {rid!r} is not live")
        for pid in table.pages:
            self._pages.free(pid)
        if table.slot is not None:
            self._slots.free(table.slot)
        return len(table.pages)

    def length(self, rid: str) -> int:
        return self._tables[rid].length

    def table(self, rid: str) -> PageTable:
        """The live request's page table (KeyError when not live)."""
        return self._tables[rid]

    def live_requests(self) -> list[str]:
        return list(self._tables)

    # -- step I/O ---------------------------------------------------------------
    def materialize(self, rids: Sequence[str], batch: int) \
            -> tuple[Any, np.ndarray]:
        """Assemble a dense device cache for one step.

        Rows ``0..len(rids)`` hold those requests' paged tokens and row
        state; rows beyond are padding (template-initial), and slots at or
        past a row's length read zero.  Returns ``(cache pytree,
        lengths)`` where ``lengths[i]`` is request i's token count — the
        executor passes it as the per-row write position vector.  The
        cache is a copy: the pools are never handed to the step.
        """
        if len(rids) > batch:
            raise ValueError(f"{len(rids)} requests do not fit in "
                             f"batch {batch}")
        tables = [self._tables[r] for r in rids]
        ps = self._pages.page_size
        with telemetry.span("kv.gather"):
            s = self.max_len if self._paged_idx else 0
            index = np.full((batch, s + 1), _NOWHERE, np.int32)
            index[:, s] = self._template_slot
            for r, t in enumerate(tables):
                if s:
                    index[r, :t.length] = t.pool_rows(ps, 0, t.length)
                if t.slot is not None:
                    index[r, s] = t.slot
        index = self._upload(index)
        out_leaves = [self._upload(spec.template)
                      if spec.kind == _SHARED else None
                      for spec in self._leaves]
        with telemetry.span("kv.gather"):
            built = _gather(self._pools, self._rows, index,
                            paged=self._paged_axes, row=self._row_axes)
        for i, leaf in zip(self._paged_idx + self._row_idx, built):
            out_leaves[i] = leaf
        cache = jax.tree_util.tree_unflatten(self._treedef, out_leaves)
        lengths = np.array([t.length for t in tables]
                           + [0] * (batch - len(tables)), np.int32)
        return cache, lengths

    def harvest(self, rids: Sequence[str], new_cache: Any,
                n_new: Sequence[int]) -> None:
        """Write each request's newly written slots back into its pages.

        Request i wrote ``n_new[i]`` tokens at slots
        ``[length, length + n_new[i])`` of row i; every real row's state
        goes to its row-state slot.  Pages and slots are allocated on
        demand; the whole-batch demand is checked *before* any mutation,
        so a capacity failure raises :class:`PageError` without
        corrupting any request's state.  Leaves are cast to the pools'
        dtypes; nothing leaves the device.
        """
        new_leaves, _ = jax.tree_util.tree_flatten(new_cache)
        if len(new_leaves) != len(self._leaves):
            raise ValueError("new_cache structure does not match template")
        tables = [self._tables[r] for r in rids]
        n_new = [int(n) for n in n_new]
        ps = self._pages.page_size
        # pre-check page demand and row-state slots
        need = 0
        for table, n in zip(tables, n_new):
            if n == 0:
                continue
            end = table.length + n
            if end > self.max_len:
                raise PageError(f"request {table.rid!r} would exceed "
                                f"max_len {self.max_len} ({end} tokens)")
            need += max(0, math.ceil(end / ps) - len(table.pages))
        if need > self._pages.free_pages:
            raise PageError(f"{need} pages needed but only "
                            f"{self._pages.free_pages} free")
        if self._slots is not None:
            fresh = sum(t.slot is None for t in tables)
            if fresh > self._slots.free_pages:
                raise PageError(f"{fresh} requests need row-state slots but "
                                f"only {self._slots.free_pages} free")
        # the step program must finish first
        with telemetry.span("kv.wait"):
            jax.block_until_ready(new_cache)
        paged = bool(self._paged_idx) and any(n_new)
        if not self._row_idx and not paged:
            return
        with telemetry.span("kv.scatter"):
            w = self._width(max(n_new)) if paged else 0
            first = (self._paged_idx + self._row_idx)[0]
            batch = new_leaves[first].shape[self._leaves[first].bat_i]
            index = np.full((batch, w + 2), _NOWHERE, np.int32)
            index[:, w] = 0
            index[:, w + 1] = self._sink_slot
            for r, (table, n) in enumerate(zip(tables, n_new)):
                if self._slots is not None:
                    if table.slot is None:
                        table.slot = self._slots.alloc()
                    index[r, w + 1] = table.slot
                if n == 0:
                    continue
                while len(table.pages) * ps < table.length + n:
                    table.pages.append(self._pages.alloc())
                if paged:
                    # a window of w slots that holds the written ones and
                    # ends inside the row
                    lo = min(table.length, self.max_len - w)
                    off = table.length - lo
                    index[r, off:off + n] = table.pool_rows(
                        ps, table.length, n)
                    index[r, w] = lo
        index = self._upload(index)
        with telemetry.span("kv.scatter"):
            pools, self._rows = _scatter(
                self._pools if paged else [], self._rows,
                [new_leaves[i] for i in self._paged_idx] if paged else [],
                [new_leaves[i] for i in self._row_idx], index,
                paged=self._paged_axes, row=self._row_axes)
            if paged:
                self._pools = pools
        for table, n in zip(tables, n_new):
            table.length += n

    def _width(self, most: int) -> int:
        """Slots a row to scatter: 1 for a decode step; for a step that
        wrote several tokens a row, the widest power of two seen so far,
        so a chunked prefill's short last chunks reuse the full chunk's
        program instead of compiling their own."""
        if most <= 1:
            return 1
        self._wide = max(self._wide,
                         min(1 << (most - 1).bit_length(), self.max_len))
        return self._wide

    # -- reporting --------------------------------------------------------------
    def stats(self) -> dict:
        pool = self._pages
        return {
            "live_requests": len(self._tables),
            "page_size": pool.page_size,
            "num_pages": pool.num_pages,
            "live_pages": pool.live_pages,
            "free_pages": pool.free_pages,
            "allocs": pool.allocs,
            "frees": pool.frees,
            "high_water": pool.high_water,
            "pool_bytes": sum(a.nbytes for a in self._pools),
            "row_state_bytes": sum(a.nbytes for a in self._rows),
        }

    def _upload(self, host: np.ndarray):
        """One host array (an index table or a shared leaf) to the step's
        device.  ``device_put`` returns before the copy lands; the programs
        that read it wait for it on the device."""
        with telemetry.span("kv.upload", bytes=host.nbytes):
            return jax.device_put(host, self.device)

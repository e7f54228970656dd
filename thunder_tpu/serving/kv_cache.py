"""Block-allocated paged KV cache for the serving engine.

One shared pool of fixed-size pages per layer (``(kv_heads, num_pages,
page_size, head_dim)`` K and V arrays) plus a host-side free list and
per-request block tables — the vLLM PagedAttention memory model, TPU-first:
requests at wildly different sequence lengths share one device allocation,
so the compiled decode step has ONE shape regardless of who is resident
(no per-request recompiles, no per-request max_len buffers).

One ``PagedKVCache`` holds the layers of ONE cache kind (``PageGeometry``:
``window`` None keeps the whole context, ``window=W`` the last W positions
in a ring of ``ceil(W / page) + 1`` pages a request, which the scheduler
recycles as the context slides); the engine keeps one a kind.

Page 0 is reserved as the scratch page: it is never allocated, inactive
decode slots write their (discarded) K/V there, and unallocated block-table
entries point at it — every table entry is always a valid pool index, which
is what lets the Pallas kernel's page walk (and the gather) run unguarded.

Pages are REFCOUNTED (copy-on-write substrate): ``alloc`` hands out pages
at refcount 1, ``retain`` lets a second block table share a page, and
``free`` only returns a page to the free list when its last reference
drops. :meth:`fork` builds a forked block table that shares every full
page of a context and copies only the partial tail page — the page the
fork will keep appending into — which is what makes best-of-N share ONE
prefill across N decode slots, and draft rollback a refcount decrement.
A page can additionally be REGISTERED by the cross-request prefix cache
(:mod:`~thunder_tpu.serving.prefix_cache`): a registered page whose
refcount reaches zero parks in the *cached* set (evictable, its K/V
preserved for future prefix hits) instead of the free list, and
``alloc`` reclaims cached pages through the registered ``evict_cb``
before ever raising ``OutOfPages`` — cached prefixes can never starve
live traffic.

A layer of the ``state`` kind keeps no pages: :class:`SlotStateCache`
holds its recurrent state, one row a decode slot (``StateGeometry``), at a
size fixed by the slot count and not by the context. A slot's row is
zeroed by the program when a request's first prefill chunk reads it, so a
freed slot needs no host work, and a preempted request re-prefills from
zero like any other.
"""

from __future__ import annotations

from collections import Counter
from dataclasses import dataclass


@dataclass
class PageGeometry:
    """Static pool geometry; everything the compiled step's shapes depend on."""

    n_layers: int
    kv_heads: int
    head_dim: int
    page_size: int       # tokens per page
    num_pages: int       # pool pages per layer, INCLUDING the reserved page 0
    pages_per_request: int  # block-table width (max context / page_size;
    #                         a window kind's ring: ceil(window / page) + 1)
    window: int | None = None  # positions a window kind keeps (None: all)

    @property
    def max_context(self) -> int:
        return self.pages_per_request * self.page_size

    def pages_for(self, n_tokens: int) -> int:
        """Pages needed to hold ``n_tokens`` of context."""
        return -(-n_tokens // self.page_size)


class DevicePools:
    """The device arrays of one cache kind, ``pools``: one dict a layer,
    donated into the compiled steps and stored back after each."""

    pools: list

    def update_pools(self, new_pools) -> None:
        """Store the updated pools returned by a compiled step (the step
        donates the old buffers, so the engine must never reuse them)."""
        self.pools = list(new_pools)

    def pools_alive(self) -> bool:
        """False when any pool buffer was deleted — a dispatch that donated
        the pools and then failed consumed them mid-execution, so replaying
        against this cache is impossible (the supervisor must rebuild)."""
        for kv in self.pools:
            for arr in kv.values():
                if getattr(arr, "is_deleted", lambda: False)():
                    return False
        return True

    def consume_pools(self) -> None:
        """Delete every pool buffer — what a real accelerator fault does to
        donated inputs mid-execution (the write-side dual of
        :meth:`pools_alive`). Only the ``serving:engine`` fault-injection
        path calls this; recovery is a supervisor pool rebuild."""
        for kv in self.pools:
            for arr in kv.values():
                try:
                    arr.delete()
                except Exception:
                    pass


@dataclass(frozen=True)
class StateGeometry:
    """A state kind's static geometry: the layers of the kind, the slots,
    and one slot's arrays a layer (``{name: (shape, dtype)}`` as a sorted
    tuple, so the geometry hashes)."""

    n_layers: int
    slots: int
    arrays: tuple           # ((name, shape, dtype name), ...)
    pages_per_request: int = 1      # the block table's one unused column
    num_pages: int = 0

    @classmethod
    def of(cls, n_layers: int, slots: int, shapes: dict) -> "StateGeometry":
        import numpy as np

        return cls(n_layers, slots, tuple(
            (name, tuple(shape), np.dtype(dt).name)
            for name, (shape, dt) in sorted(shapes.items())))

    @property
    def bytes_per_slot(self) -> int:
        """One slot's state over every layer of the kind."""
        import math

        import numpy as np

        return self.n_layers * sum(math.prod(shape) * np.dtype(dt).itemsize
                                   for _, shape, dt in self.arrays)


class SlotStateCache(DevicePools):
    """The device rows of a state kind: ``pools`` is a list (per layer) of
    ``{name: (slots, *shape) array}``, zero at construction, donated into
    the compiled steps and stored back like the page pools. No page is ever
    allocated or freed here: the engine's page loops skip the kind."""

    def __init__(self, geometry: StateGeometry):
        import jax.numpy as jnp

        self.geometry = g = geometry
        self.pools = [{name: jnp.zeros((g.slots, *shape), dt)
                       for name, shape, dt in g.arrays}
                      for _ in range(g.n_layers)]

    @property
    def nbytes(self) -> int:
        return self.geometry.slots * self.geometry.bytes_per_slot

    def assert_quiescent(self, block_tables=None) -> None:
        """A state kind leaks nothing (a row a slot, always); its block
        table's unused column must still be all scratch."""
        if block_tables is not None:
            import numpy as np

            if np.asarray(block_tables).any():
                raise AssertionError(
                    "a state kind's block table holds a page id")


class PagedKVCache(DevicePools):
    """Device page pools + host free list + per-page refcounts.

    ``pools`` is a list (per layer) of ``{"k": array, "v": array}`` with
    shape ``(kv_heads, num_pages, page_size, head_dim)``. The arrays are
    functional: the engine passes them into the compiled step (donated) and
    stores the returned updated pools back via :meth:`update_pools`.
    """

    def __init__(self, geometry: PageGeometry, dtype, *, sharding=None):
        import jax.numpy as jnp

        g = geometry
        if g.num_pages < 2:
            raise ValueError("num_pages must be >= 2 (page 0 is reserved)")
        if sharding is not None and getattr(sharding, "tp", 1) > 1:
            if g.kv_heads % sharding.tp != 0:
                from thunder_tpu.serving.errors import ShardingGeometryError

                raise ShardingGeometryError(
                    f"kv_heads={g.kv_heads} not divisible by mesh axis "
                    f"'{sharding.axis}' size {sharding.tp}: the paged pool "
                    "is sharded by kv-head, so each shard must own a whole "
                    "number of heads", kv_heads=g.kv_heads, tp=sharding.tp)
        self.geometry = g
        self.dtype = dtype
        # sharding: a distributed.gspmd.TensorParallelMesh (or None). The
        # pool keeps its GLOBAL logical shape — GSPMD splits the kv-head dim
        # across the mesh, so per-shard geometry is (kv_heads/tp, ...) while
        # block tables and the free list stay global (the page axis is whole
        # on every shard).
        self.sharding = sharding if (sharding is not None
                                     and getattr(sharding, "tp", 1) > 1) else None
        shape = (g.kv_heads, g.num_pages, g.page_size, g.head_dim)
        self.pools = [{"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
                      for _ in range(g.n_layers)]
        if self.sharding is not None:
            from thunder_tpu.distributed.gspmd import shard_kv_pools

            self.pools = shard_kv_pools(self.pools, self.sharding)
        # LIFO free list: recently-freed pages are re-served first (their
        # pool region is likeliest still warm in any cache hierarchy); the
        # mirror set keeps free()'s double-free check O(1) per page (a list
        # scan is O(pool) — quadratic on the completion/eviction hot path)
        self._free: list[int] = list(range(g.num_pages - 1, 0, -1))
        self._free_set: set[int] = set(self._free)
        self._min_free = len(self._free)  # high-water tracking (peak usage)
        # copy-on-write substrate: per-page reference counts (0 == free or
        # cached), the prefix cache's registration set, and the parked
        # rc-0 registered pages in eviction (insertion) order
        self._rc: list[int] = [0] * g.num_pages
        self._registered: set[int] = set()
        self._cached: dict[int, None] = {}   # ordered: oldest parked first
        self.evict_cb = None        # page -> list[int]: prefix-cache hook
        self.cow_copies = 0         # tail-page copies made by fork()
        self.pages_allocated = 0    # lifetime alloc count (page amplification)

    # -- allocation ---------------------------------------------------------
    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_total(self) -> int:
        """Allocatable pages (the reserved scratch page doesn't count)."""
        return self.geometry.num_pages - 1

    @property
    def cached_pages(self) -> int:
        """Pages parked by the prefix cache: refcount 0, K/V preserved,
        reclaimable by :meth:`alloc` under pressure."""
        return len(self._cached)

    @property
    def peak_pages_used(self) -> int:
        return self.pages_total - self._min_free

    def utilization(self) -> float:
        return 1.0 - self.pages_free / self.pages_total

    def reset_peak(self) -> None:
        """Restart high-water tracking (benchmarks: exclude warmup)."""
        self._min_free = len(self._free)

    def refcount(self, page: int) -> int:
        return self._rc[page]

    def can_alloc(self, n: int) -> bool:
        # cached pages count: alloc() reclaims them before back-pressuring
        return n <= len(self._free) + len(self._cached)

    def alloc(self, n: int) -> list[int]:
        """Pop ``n`` pages off the free list, reclaiming parked prefix-cache
        pages (oldest first, via ``evict_cb``) when the list runs short.
        Raises ``OutOfPages`` when free + cached can't satisfy the request —
        the scheduler turns that into admission back-pressure or preemption,
        never a crash."""
        while n > len(self._free) and self._cached:
            victim = next(iter(self._cached))
            # the prefix cache drops the victim's trie node AND its subtree
            # (descendants of an unreferenced prefix are unreferenced too);
            # without a registered cache the parked page reclaims alone
            pages = self.evict_cb(victim) if self.evict_cb is not None \
                else [victim]
            for p in pages:
                self._reclaim(p)
        if n > len(self._free):
            raise OutOfPages(
                f"requested {n} KV pages with {len(self._free)} free "
                f"(pool: {self.pages_total}, cached: {len(self._cached)}); "
                f"admission should have back-pressured or preempted first")
        pages = [self._free.pop() for _ in range(n)]
        self._free_set.difference_update(pages)
        for p in pages:
            self._rc[p] = 1
        self.pages_allocated += len(pages)
        self._min_free = min(self._min_free, len(self._free))
        return pages

    def retain(self, pages) -> None:
        """Add a reference to already-allocated pages (block-table fork /
        prefix-cache hit). A parked cached page leaves the evictable set —
        it is live again."""
        for p in pages:
            if not (0 < p < self.geometry.num_pages):
                raise ValueError(f"retaining invalid page id {p}")
            if p in self._free_set:
                raise ValueError(f"retain of free page {p}")
            if self._rc[p] == 0:
                self._cached.pop(p, None)    # parked -> live
            self._rc[p] += 1

    def free(self, pages) -> None:
        """Drop one reference per page. A page whose last reference drops
        returns to the free list — unless the prefix cache registered it,
        in which case it parks in the cached set with its K/V intact."""
        drops = Counter(pages)
        for p, n in drops.items():
            if not (0 < p < self.geometry.num_pages):
                raise ValueError(f"freeing invalid page id {p}")
            if p in self._free_set or self._rc[p] < n:
                raise ValueError(
                    f"double free of page {p} ({n} drops against "
                    f"{self._rc[p]} held references)")
        for p in pages:
            self._rc[p] -= 1
            if self._rc[p] > 0:
                continue                     # another block table still holds it
            if p in self._registered:
                self._cached[p] = None       # park for future prefix hits
            else:
                self._free.append(p)
                self._free_set.add(p)

    # -- copy-on-write forks ------------------------------------------------
    def fork(self, pages: list[int], length: int) -> list[int]:
        """Fork a block table covering ``length`` context tokens: full
        pages are SHARED (refcount bump, zero bytes moved) and only the
        partial tail page — the one the fork will keep writing into — is
        copied onto a fresh page. Page-aligned contexts fork with no copy
        at all (the next append opens a fresh page anyway). Raises
        ``OutOfPages`` if the tail copy can't allocate (after cached-page
        reclaim); the caller falls back to an ordinary re-prefill."""
        ps = self.geometry.page_size
        if length < 1:
            raise ValueError(f"cannot fork an empty context ({length=})")
        n_ctx = -(-length // ps)
        if len(pages) < n_ctx:
            raise ValueError(
                f"fork needs {n_ctx} pages for {length} tokens, got {len(pages)}")
        tail_partial = (length % ps) != 0
        shared = pages[:n_ctx - 1] if tail_partial else pages[:n_ctx]
        self.retain(shared)
        forked = list(shared)
        if tail_partial:
            try:
                [tail] = self.alloc(1)
            except OutOfPages:
                self.free(shared)            # undo: fork must be atomic
                raise
            self.copy_page(pages[n_ctx - 1], tail)
            self.cow_copies += 1
            forked.append(tail)
        return forked

    def copy_page(self, src: int, dst: int) -> None:
        """Copy one page's K/V across every layer (the COW tail copy —
        rare host-side path, one fork at a time, never in the compiled
        step). The update runs through one jitted dynamic-update-slice
        with the pool DONATED, so on backends with buffer donation the
        copy really is one page's bytes in place; without donation (CPU)
        XLA falls back to a pool copy, which only the toy smoke pays.
        Page ids ride in as traced scalars — one compile covers every
        (src, dst) pair."""
        import jax
        import jax.numpy as jnp

        fn = _page_copy_fn(jax.default_backend())
        for kv in self.pools:
            for key in ("k", "v"):
                kv[key] = fn(kv[key], jnp.int32(src), jnp.int32(dst))

    # -- prefix-cache registration ------------------------------------------
    def register_cached(self, page: int) -> None:
        """Mark a page as held by the prefix cache: when its refcount
        drops to zero it parks (K/V preserved, evictable) instead of
        returning to the free list."""
        if not (0 < page < self.geometry.num_pages):
            raise ValueError(f"registering invalid page id {page}")
        if page in self._free_set:
            raise ValueError(f"registering free page {page}")
        self._registered.add(page)
        if self._rc[page] == 0:
            self._cached[page] = None

    def unregister_cached(self, page: int) -> None:
        """Drop a page's prefix-cache registration (trie reset): a parked
        page returns to the free list immediately; a live page simply
        stops parking when its last reference drops."""
        self._registered.discard(page)
        if page in self._cached:
            del self._cached[page]
            self._free.append(page)
            self._free_set.add(page)

    def _reclaim(self, page: int) -> None:
        """Eviction: un-register a parked rc-0 page and return it to the
        free list (allocator pressure path; the trie entry is already
        gone)."""
        if self._rc[page] != 0 or page not in self._registered:
            raise ValueError(
                f"reclaiming page {page} that is live (rc={self._rc[page]}) "
                f"or unregistered")
        self._registered.discard(page)
        self._cached.pop(page, None)
        self._free.append(page)
        self._free_set.add(page)

    def assert_quiescent(self, block_tables=None) -> None:
        """Leak audit for an idle pool, refcount-aware: every allocatable
        page is either on the free list or parked at refcount 0 by the
        prefix cache (its K/V deliberately preserved for future hits), no
        page holds a live reference, the free-list mirror set agrees with
        the list exactly, every listed page id is a valid non-scratch pool
        index, and (when the engine hands its block tables over) no table
        entry references anything but the reserved scratch page 0. Raises
        ``AssertionError`` naming the violation — the chaos-soak /
        eviction / supervisor-restart tests call this after every run, so
        a single leaked page or refcount, or a diverged mirror, fails
        loudly instead of surfacing later as an allocator mystery."""
        live = [p for p in range(1, self.geometry.num_pages) if self._rc[p]]
        if live:
            raise AssertionError(
                f"KV page leak: {len(live)} pages still hold live "
                f"references on an idle pool (first ids: {live[:8]}, "
                f"refcounts: {[self._rc[p] for p in live[:8]]})")
        accounted = len(self._free) + len(self._cached)
        if accounted != self.pages_total:
            raise AssertionError(
                f"KV page leak: free ({len(self._free)}) + cached "
                f"({len(self._cached)}) != allocatable ({self.pages_total})")
        stray = sorted(set(self._free) & set(self._cached))
        if stray:
            raise AssertionError(
                f"pages on the free list AND in the cached set: {stray}")
        if len(self._free) != len(self._free_set) or \
                set(self._free) != self._free_set:
            raise AssertionError(
                f"free-list/mirror-set divergence: list holds "
                f"{len(self._free)} entries ({len(set(self._free))} unique), "
                f"mirror holds {len(self._free_set)}")
        bad = sorted(p for p in self._free
                     if not (0 < p < self.geometry.num_pages))
        if bad:
            raise AssertionError(f"free list holds invalid page ids {bad} "
                                 f"(pool has {self.geometry.num_pages} pages, "
                                 f"page 0 reserved)")
        if block_tables is not None:
            import numpy as np

            nz = np.flatnonzero(np.asarray(block_tables))
            if nz.size:
                raise AssertionError(
                    f"{nz.size} block-table entries still reference "
                    f"non-scratch pages on an idle engine (first flat "
                    f"indices: {nz[:8].tolist()})")


_PAGE_COPY_FNS: dict = {}


def _page_copy_fn(backend: str):
    """Jitted single-page pool copy, donated where the backend supports
    aliasing (donating on CPU only buys a warning per call)."""
    fn = _PAGE_COPY_FNS.get(backend)
    if fn is None:
        import jax

        def _copy(pool, src, dst):
            page = jax.lax.dynamic_index_in_dim(pool, src, axis=1)
            return jax.lax.dynamic_update_slice_in_dim(pool, page, dst, axis=1)

        donate = () if backend == "cpu" else (0,)
        fn = jax.jit(_copy, donate_argnums=donate)
        _PAGE_COPY_FNS[backend] = fn
    return fn


class OutOfPages(RuntimeError):
    """The page pool cannot satisfy an allocation; scheduler-level signal."""

"""Refcounting page allocator for the paged KV cache.

One ``PagePool`` instance governs one cache pytree's page id space: the
ids it hands out index the page axis of every layer's pool array (page
assignment is layer-uniform, exactly like the per-slot ``len`` vector).

Refcount invariants — the ones the eviction test enforces:

  * ``refs[p] == 0``  <=>  ``p`` is on the free list;
  * every holder of a page owns exactly one reference: each slot whose
    page table contains ``p`` holds one, and the prefix tree holds one
    for each tree node caching ``p``;
  * a page is reclaimed only by its refcount reaching zero — there is no
    other path back to the free list, so a page referenced by any active
    slot (refcount > the tree's one) can never be evicted out from under
    it.
"""
from __future__ import annotations

import numpy as np

__all__ = ["PagePool"]


class PagePool:
    """Fixed pool of ``n_pages`` KV pages of ``page_size`` tokens each.

    ``record=True`` keeps an operation trace — tuples of
    ``("alloc", pages)``, ``("retain", pages, owner)``, and
    ``("release", pages, owner, evict)`` — that the serving-invariant
    checker (``repro.analysis.serving``) abstractly interprets to prove
    refcount discipline (no leaks, no double-release, no eviction of a
    page an active slot still references).  ``owner`` partitions the
    refcount between the two holder kinds: ``"slot"`` (a request's page
    table, including match()-retained prefixes held on the caller's
    behalf) and ``"tree"`` (prefix-tree nodes).  ``note()`` interleaves
    annotation-only ``("event", tag, info)`` entries — e.g. the server's
    fault-recovery markers — which the checker accepts and skips, so a
    verified trace also documents *why* its releases happened.
    """

    def __init__(self, n_pages: int, page_size: int, *,
                 record: bool = False):
        if n_pages < 1 or page_size < 1:
            raise ValueError(f"bad pool shape ({n_pages=}, {page_size=})")
        self.n_pages = n_pages
        self.page_size = page_size
        self.refs = np.zeros(n_pages, np.int32)
        # LIFO free list: recently-freed pages are reused first, which
        # keeps the working set of pool pages small
        self._free = list(range(n_pages - 1, -1, -1))
        self.trace: list[tuple] | None = [] if record else None

    # ------------------------------------------------------------ alloc
    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def used_pages(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self, n: int) -> list[int] | None:
        """Take ``n`` fresh pages (refcount 1 each), all-or-nothing.

        Returns None when the pool cannot satisfy the request — the
        caller decides whether to evict cached prefixes and retry or to
        defer admission."""
        if n > len(self._free):
            return None
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self.refs[p] = 1
        if self.trace is not None:
            self.trace.append(("alloc", tuple(pages)))
        return pages

    # ------------------------------------------------------------ events
    def note(self, tag: str, **info) -> None:
        """Append an annotation-only ``("event", tag, info)`` entry to the
        trace (no-op when not recording).  Events carry no refcount
        semantics — the serving checker skips them — but they anchor the
        surrounding alloc/release ops to a cause (e.g. the server notes
        ``fault_recovery`` before releasing a quarantined slot's pages,
        so a trace dump reads as a causal story, not bare arithmetic)."""
        if self.trace is not None:
            self.trace.append(
                ("event", tag, tuple(sorted(info.items()))))

    # ---------------------------------------------------------- refcount
    def retain(self, pages, *, owner: str = "slot") -> None:
        """Add one reference to each page (duplicates counted per entry)."""
        for p in pages:
            if self.refs[p] <= 0:
                raise ValueError(f"retain of unreferenced page {p}")
            self.refs[p] += 1
        if self.trace is not None and len(pages):
            self.trace.append(("retain", tuple(int(p) for p in pages),
                               owner))

    def release(self, pages, *, owner: str = "slot",
                evict: bool = False) -> int:
        """Drop one reference per page; pages reaching zero return to the
        free list.  Returns how many pages were actually freed."""
        if self.trace is not None and len(pages):
            self.trace.append(("release", tuple(int(p) for p in pages),
                               owner, evict))
        freed = 0
        for p in pages:
            if self.refs[p] <= 0:
                raise ValueError(f"release of unreferenced page {p}")
            self.refs[p] -= 1
            if self.refs[p] == 0:
                self._free.append(p)
                freed += 1
        return freed

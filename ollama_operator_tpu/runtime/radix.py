"""Radix-tree prefix cache over physical KV pages.

SGLang's RadixAttention adapted to the paged pool (``runtime/paged.py``):
one tree node = one ``page_size``-aligned token chunk backed by exactly
ONE physical page, so matching, insertion and eviction are all
page-granular. The tree stores only page *ids* plus an LRU stamp — the
KV bytes live in the device pool and refcounts live in the PageTable
(each resident node holds one ``pin`` on its page).

Ownership protocol (driven by Engine.stitch/donate_prefix/radix_evict):

- ``match`` is read-only: the longest cached chunk path for a token
  sequence, plus at most one *partial* boundary node whose first ``q``
  tokens match (the engine copies that page before the new slot writes
  its tail into it — copy-on-write).
- ``insert`` walks/creates nodes for a finished request's full-page
  chunks and returns the nodes it newly created; the engine pins those
  nodes' pages (chunks already present keep the tree's original page and
  the donor's duplicate page is simply freed by its release).
- ``evict`` pops least-recently-used LEAF nodes one page at a time —
  children always leave before parents, so every resident path stays
  contiguous from the root — skipping pages some slot still maps.

**Tiered residency** (ISSUE 18): a node's KV may live in HBM
(``tier == 0``, ``page`` is a live pool page) or in the host-RAM arena
(``tier == 1``, ``page == -1`` and ``host`` holds the spilled bytes —
``runtime/host_cache.py``).  The path invariant generalises: every
root→node path is a run of tier-0 nodes followed by a run of tier-1
nodes (never tier-0 below tier-1), because spilling takes the deepest
tier-0 node first (``spill_lru``) and tier-1 pressure drops leaves
first (``drop_host_lru``).  ``match`` is tier-agnostic — the engine
splits the matched path into the shareable tier-0 run and the
restitchable tier-1 run.  Entries imported from a tier-2 fleet snapshot
are ordinary tier-1 nodes whose ``host.snapshot`` flag attributes their
hits to tier 2 in the metrics.

A logical clock (bumped per match/insert) orders recency; no wall time,
so multi-host replays stay deterministic.

Epoch-fence interplay (ISSUE 5): the tree itself never frees a page —
eviction hands page ids back to the engine, whose ``unpin`` routes any
refcount-zero page through the PageTable's epoch fence. The fence holds
a page by when its last SLOT mapping went (the tree's pin puts a page in
no block table): a leaf no slot has mapped since the retired epoch is
free the moment it is evicted, with a program in flight too, and any
other sits in quarantine until the decode dispatch whose block tables
captured it materialises, so LRU eviction is safe to run with a program
in flight; under sync dispatch the fence is pass-through and eviction
frees immediately, exactly as before.
Spilling is stricter: the engine only gathers a page's bytes while the
fence is fully quiescent (no launched dispatch un-retired), so the
host copy can never capture a page an in-flight program still writes.
"""

from __future__ import annotations

from typing import Callable, Dict, List, Optional, Sequence, Tuple


class _Node:
    __slots__ = ("chunk", "page", "parent", "children", "stamp", "tier",
                 "host")

    def __init__(self, chunk: Tuple[int, ...], page: int,
                 parent: Optional["_Node"], stamp: int):
        self.chunk = chunk
        self.page = page
        self.parent = parent
        self.children: Dict[Tuple[int, ...], "_Node"] = {}
        self.stamp = stamp
        self.tier = 0
        self.host = None  # HostEntry when tier == 1


class RadixCache:
    """Trie keyed on page_size token chunks; nodes hold physical pages."""

    def __init__(self, page_size: int):
        assert page_size >= 1
        self.page_size = page_size
        self._root = _Node((), -1, None, 0)
        self._clock = 0
        self._n = 0       # all resident nodes (any tier)
        self._n_t0 = 0    # tier-0 nodes == pages the tree pins in HBM
        # host entries orphaned by insert() promotions, drained by the
        # engine (take_dropped_hosts) so the arena accounting stays exact
        self._dropped_hosts: List[object] = []

    @property
    def n_nodes(self) -> int:
        """Resident nodes across all tiers."""
        return self._n

    @property
    def n_pages(self) -> int:
        """Tier-0 nodes == physical pages the tree pins (one each)."""
        return self._n_t0

    @property
    def n_hosted(self) -> int:
        """Tier-1 nodes (KV spilled to the host arena)."""
        return self._n - self._n_t0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, ids: Sequence[int], limit: int, bump: bool = True
              ) -> Tuple[List[_Node], Optional[_Node], int]:
        """Longest cached prefix of ``ids`` within ``limit`` tokens:
        ``(full_nodes, partial_node, partial_len)`` — full-chunk path
        nodes in order, then optionally ONE boundary node whose first
        ``partial_len`` (1 ≤ q < page_size) tokens extend the match.
        Nodes of any tier are returned; the caller splits by ``tier``.
        ``bump=False`` probes without touching LRU recency."""
        ps = self.page_size
        limit = min(limit, len(ids))
        node = self._root
        full: List[_Node] = []
        pos = 0
        while pos + ps <= limit:
            child = node.children.get(tuple(int(t) for t in ids[pos:pos + ps]))
            if child is None:
                break
            full.append(child)
            node = child
            pos += ps
        part, part_q = None, 0
        room = min(ps, limit - pos)
        if room > 0:
            head = [int(t) for t in ids[pos:pos + room]]
            for chunk, child in node.children.items():
                q = 0
                while q < room and chunk[q] == head[q]:
                    q += 1
                if q > part_q:
                    part, part_q = child, q
        if bump and (full or part is not None):
            stamp = self._tick()
            for n in full:
                n.stamp = stamp
            if part is not None:
                part.stamp = stamp
        return full, part, part_q

    def insert(self, ids: Sequence[int], pages: Sequence[int]) -> List[_Node]:
        """Walk/create the chunk path for ``ids`` (page-aligned,
        ``len(pages)`` chunks); chunk ``i`` is backed by ``pages[i]`` when
        newly created. Returns the nodes that ADOPTED the donor's page —
        the caller must pin those pages. Chunks already resident at
        tier 0 keep the tree's existing page; a chunk resident at
        tier 1 is *promoted*: it adopts the donor's page (also returned
        for pinning) and its host entry lands in ``take_dropped_hosts``
        for the engine to release from the arena."""
        ps = self.page_size
        assert len(ids) >= len(pages) * ps
        node = self._root
        stamp = self._tick()
        adopted: List[_Node] = []
        for i, pg in enumerate(pages):
            chunk = tuple(int(t) for t in ids[i * ps:(i + 1) * ps])
            child = node.children.get(chunk)
            if child is None:
                child = _Node(chunk, int(pg), node, stamp)
                node.children[chunk] = child
                self._n += 1
                self._n_t0 += 1
                adopted.append(child)
            elif child.tier != 0:
                # promotion: the donor hands the tree a live HBM copy of
                # a chunk currently spilled — adopt the page, retire the
                # host bytes (donor path visits parents first, so the
                # tier0*-then-tier1* path invariant is preserved)
                child.page = int(pg)
                child.tier = 0
                self._n_t0 += 1
                if child.host is not None:
                    self._dropped_hosts.append(child.host)
                    child.host = None
                adopted.append(child)
            child.stamp = stamp
            node = child
        return adopted

    def take_dropped_hosts(self) -> List[object]:
        """Host entries orphaned since the last call (insert promotions);
        the engine frees them from the arena."""
        dropped, self._dropped_hosts = self._dropped_hosts, []
        return dropped

    def evict(self, n_pages: int, evictable: Callable[[int], bool]
              ) -> List[int]:
        """Pop up to ``n_pages`` least-recently-used tier-0 leaves whose
        page satisfies ``evictable`` (e.g. no slot maps it). Page-by-page:
        each removal may expose its parent as the next leaf. Returns the
        evicted page ids (caller unpins them). Used on the tierless path
        (host arena off) — with the arena on the engine drives
        ``spill_lru`` instead."""
        freed: List[int] = []
        while len(freed) < n_pages:
            lru: Optional[_Node] = None
            stack = [self._root]
            while stack:
                node = stack.pop()
                for child in node.children.values():
                    if child.children:
                        stack.append(child)
                    elif child.tier == 0 and evictable(child.page) and (
                            lru is None or child.stamp < lru.stamp):
                        lru = child
            if lru is None:
                break
            del lru.parent.children[lru.chunk]
            self._n -= 1
            self._n_t0 -= 1
            freed.append(lru.page)
        return freed

    # ------------------------------------------------------------------
    # tiered residency (host arena)
    # ------------------------------------------------------------------
    def spill_lru(self, evictable: Callable[[int], bool]
                  ) -> Optional[_Node]:
        """The least-recently-used spill candidate: a tier-0 node with NO
        tier-0 children (tier-1 children are fine — they already left
        HBM) whose page satisfies ``evictable``. Deepest-first by
        construction, so spilling keeps every path tier-0-then-tier-1
        contiguous. None when nothing is spillable."""
        lru: Optional[_Node] = None
        stack = [self._root]
        while stack:
            node = stack.pop()
            for child in node.children.values():
                if child.tier != 0:
                    continue
                if any(c.tier == 0 for c in child.children.values()):
                    stack.append(child)
                elif evictable(child.page) and (
                        lru is None or child.stamp < lru.stamp):
                    lru = child
        return lru

    def mark_spilled(self, node: _Node, entry) -> int:
        """Transition ``node`` tier 0 → 1: returns its page (the engine
        unpins it) and attaches the arena entry."""
        assert node.tier == 0 and node.page >= 0
        pg, node.page = node.page, -1
        node.tier = 1
        node.host = entry
        self._n_t0 -= 1
        return pg

    def mark_promoted(self, node: _Node, page: int):
        """Transition ``node`` tier 1 → 0 onto a freshly uploaded page
        (the engine pins it); returns the retired host entry for the
        arena to free."""
        assert node.tier != 0 and node.page < 0
        node.page = int(page)
        node.tier = 0
        self._n_t0 += 1
        entry, node.host = node.host, None
        return entry

    def remove(self, node: _Node) -> Tuple[List[int], List[object]]:
        """Remove ``node`` and its whole subtree (eviction fallback when
        a spill is not possible: pruning the subtree keeps paths rooted).
        Returns (tier-0 pages to unpin, host entries to free)."""
        pages: List[int] = []
        hosts: List[object] = []
        stack = [node]
        while stack:
            cur = stack.pop()
            if cur.tier == 0:
                pages.append(cur.page)
                self._n_t0 -= 1
            elif cur.host is not None:
                hosts.append(cur.host)
            self._n -= 1
            stack.extend(cur.children.values())
        del node.parent.children[node.chunk]
        return pages, hosts

    def drop_host_lru(self, n: int = 1) -> List[object]:
        """Drop up to ``n`` least-recently-used tier-1 LEAF nodes (arena
        pressure); returns their host entries for the arena to free.
        Leaf-first keeps tier-1 runs contiguous under their tier-0
        ancestors."""
        dropped: List[object] = []
        while len(dropped) < n:
            lru: Optional[_Node] = None
            stack = [self._root]
            while stack:
                node = stack.pop()
                for child in node.children.values():
                    if child.children:
                        stack.append(child)
                    elif child.tier != 0 and (
                            lru is None or child.stamp < lru.stamp):
                        lru = child
            if lru is None:
                break
            del lru.parent.children[lru.chunk]
            self._n -= 1
            if lru.host is not None:
                dropped.append(lru.host)
        return dropped

    def child(self, parent: Optional[_Node], chunk: Tuple[int, ...]
              ) -> Optional[_Node]:
        """Lookup helper for snapshot import: the existing child of
        ``parent`` (None = root) keyed by ``chunk``."""
        return (parent or self._root).children.get(chunk)

    def insert_host(self, parent: Optional[_Node], chunk: Tuple[int, ...],
                    entry) -> _Node:
        """Attach a NEW tier-1 node under ``parent`` (None = root) —
        tier-2 snapshot import. The caller must have checked ``child``
        first; double-insert is a bug (the arena entry would leak)."""
        node = parent or self._root
        assert chunk not in node.children, "insert_host over existing node"
        stamp = self._tick()
        nn = _Node(chunk, -1, node, stamp)
        nn.tier = 1
        nn.host = entry
        node.children[chunk] = nn
        self._n += 1
        return nn

    def insert_page(self, parent: Optional[_Node], chunk: Tuple[int, ...],
                    page: int) -> _Node:
        """Attach (or promote) ONE tier-0 node under ``parent`` (None =
        root) backed by ``page`` — the disagg KV import's graft. The
        caller already holds the page's pin (``alloc_pinned``). An
        existing tier-1 child is promoted onto ``page`` and its host
        entry lands in ``take_dropped_hosts`` (the import walks parents
        first, so the tier0*-then-tier1* path invariant is preserved);
        an existing tier-0 child is a caller bug — the fresh page would
        leak its pin."""
        node = parent or self._root
        stamp = self._tick()
        child = node.children.get(chunk)
        if child is None:
            child = _Node(chunk, int(page), node, stamp)
            node.children[chunk] = child
            self._n += 1
            self._n_t0 += 1
            return child
        assert child.tier != 0, "insert_page over a tier-0 node"
        child.page = int(page)
        child.tier = 0
        self._n_t0 += 1
        if child.host is not None:
            self._dropped_hosts.append(child.host)
            child.host = None
        child.stamp = stamp
        return child

    def walk(self) -> List[_Node]:
        """Every resident node, parents strictly before children (BFS) —
        the snapshot exporter's traversal order."""
        out: List[_Node] = []
        queue = list(self._root.children.values())
        while queue:
            node = queue.pop(0)
            out.append(node)
            queue.extend(node.children.values())
        return out

    def reset(self) -> List[int]:
        """Drop every node; returns all resident TIER-0 pages (caller
        unpins). Tier-1 host entries die with their nodes — the engine
        clears the arena's accounting wholesale."""
        pages: List[int] = []
        stack = list(self._root.children.values())
        while stack:
            node = stack.pop()
            if node.tier == 0:
                pages.append(node.page)
            stack.extend(node.children.values())
        self._root.children.clear()
        self._n = 0
        self._n_t0 = 0
        self._dropped_hosts = []
        return pages

"""Host-side paged-KV bookkeeping for the serving engine
(``docs/serving.md``, "KV cache").

The device holds one page POOL (``Transformer.init_paged_cache``:
``[L, num_pages, page_size, KVH*D]``) shared by every slot; which
physical page backs which virtual position of which request is decided
HERE, on the host, and shipped to the device as a traced ``[num_slots,
pages_per_slot]`` page-table argument on every dispatch — page churn
never changes a program shape (vLLM's PagedAttention block tables, Kwon
et al. SOSP'23, under this framework's one-executable constraint).

The pieces:

* :class:`PagePool` — the refcounted free-list mirror of the device
  pool.  Page 0 is the reserved TRASH page: never allocated, and every
  unmapped/retired table entry points at it, so zombie lanes (retired
  on the host, still decoding masked no-ops on the device) scatter
  their garbage there instead of into reclaimed pages.
* :class:`PrefixIndex` — copy-on-write prefix sharing (SGLang's
  RadixAttention, Zheng et al. 2023, at page granularity): a hash-CHAIN
  index over page-aligned token blocks.  Requests whose leading blocks
  match map those table entries to the SAME physical pages (refcounted);
  the first token past the shared region lands in a private page, so a
  divergent write never touches a shared page — "copy"-on-write is
  realized as recompute-on-divergence of at most one page of tokens
  (cheaper than a dedicated device copy program, and it keeps the
  one-executable invariant).  Unreferenced entries evict LRU, leaves
  first (an interior chain node with live children never evicts — a
  broken chain would strand its descendants' refcounts).
* :class:`SlotPages` — how the two combine: the serving engine's ONE
  cache manager.  It owns the pool mirror, the prefix index, the page
  table, the slot -> pages map and the pool's donated device buffer
  (with the same dead-after-failed-dispatch liveness check
  ``KVCacheWorkspace`` does); the scheduler
  asks it to back a slot (:meth:`SlotPages.reserve`), to free one
  (:meth:`SlotPages.release`) and for the table to ship
  (:meth:`SlotPages.table`), and holds no page arithmetic of its own.
  Beside pages it owns a second KIND of state: for a model whose layers
  keep a FIXED-SIZE state a slot and no row a position (``state_kinds``,
  ``models/lfm2.py``'s short-convolution layers), one state ROW a slot in
  a pool of ``1 + num_slots`` rows — row 0 the trash row, as page 0 is
  the trash page — shipped as the LAST entry of the slot's table row.
  And a lane need not hold a row a POSITION: a model whose lane pages hold
  one row a stride of positions (``lane_stride``, ``models/evabyte.py``:
  one pooled K/V row a 16-byte chunk) has every page count here reckoned
  in ROWS, ``ceil(positions / stride)``; ``serving.max_cache_len`` and
  every position the scheduler speaks of stay positions.
"""

import hashlib
import math
from collections import deque

import numpy as np

import jax

from deepspeed_tpu.ops.transformer.paged_attention import _decode_block_pages

TRASH_PAGE = 0


def page_rows(page_size):
    """``serving.page_size`` as the pool is built: a multiple of 8 (the
    fused decode write's stripes are 8-sublane-aligned), at least 8."""
    return max(8, -(-int(page_size) // 8) * 8)


def pages_for(virtual_len, page_size):
    """Physical pages needed to back ``virtual_len`` cache positions."""
    return -(-int(virtual_len) // int(page_size))


def compact_page_str(pages):
    """Range-compressed page list: ``[4,5,6,9,2]`` → ``"4-6,9,2"`` —
    the serving snapshot stores page tables this way instead of one JSON
    int per entry (a 4k-position slot at page 16 is 256 entries; the
    compact form is a few bytes for the common contiguous case)."""
    pages = [int(p) for p in pages]
    if not pages:
        return ""
    parts, lo, prev = [], pages[0], pages[0]
    for p in pages[1:]:
        if p == prev + 1:
            prev = p
            continue
        parts.append(f"{lo}-{prev}" if prev > lo else f"{lo}")
        lo = prev = p
    parts.append(f"{lo}-{prev}" if prev > lo else f"{lo}")
    return ",".join(parts)


def expand_page_str(s):
    """Inverse of :func:`compact_page_str` (diagnostics / tests)."""
    if not s:
        return []
    out = []
    for part in s.split(","):
        if "-" in part:
            lo, hi = part.split("-")
            out.extend(range(int(lo), int(hi) + 1))
        else:
            out.append(int(part))
    return out


class PagePool:
    """Refcounted free-list mirror of the device page pool.  Allocation
    and free run at host-scheduler time, one event behind the device by
    design (the serving engine's lag-one bookkeeping): a page is freed
    only when the retirement that releases it has been PROCESSED, and
    every dispatch after that carries a table that no longer maps it."""

    def __init__(self, num_pages):
        self.num_pages = int(num_pages)
        if self.num_pages < 2:
            raise ValueError(f"page pool needs >= 2 pages (1 trash + 1 "
                             f"allocatable), got {num_pages}")
        self._ref = np.zeros((self.num_pages,), np.int32)
        self._ref[TRASH_PAGE] = 1           # pinned forever
        self._free = deque(range(1, self.num_pages))

    @property
    def allocatable(self):
        """Pages a single request could ever hold (trash excluded)."""
        return self.num_pages - 1

    @property
    def free_count(self):
        return len(self._free)

    @property
    def in_use(self):
        return self.allocatable - len(self._free)

    def utilization(self):
        return self.in_use / max(self.allocatable, 1)

    def alloc(self, n):
        """``n`` fresh pages at refcount 1, or ``None`` when the free
        list is short (caller evicts/waits — never a partial grab)."""
        if n > len(self._free):
            return None
        got = [self._free.popleft() for _ in range(n)]
        for p in got:
            self._ref[p] = 1
        return got

    def incref(self, page):
        assert self._ref[page] > 0, f"incref on free page {page}"
        self._ref[page] += 1

    def decref(self, page):
        p = int(page)
        if p == TRASH_PAGE:
            return
        assert self._ref[p] > 0, f"decref on free page {p}"
        self._ref[p] -= 1
        if self._ref[p] == 0:
            self._free.append(p)

    def refcount(self, page):
        return int(self._ref[int(page)])

    def reset(self):
        """All pages free (the pool buffer was dropped/reallocated)."""
        self._ref[:] = 0
        self._ref[TRASH_PAGE] = 1
        self._free = deque(range(1, self.num_pages))


class _PrefixEntry:
    __slots__ = ("page", "parent", "children", "last_use", "depth")

    def __init__(self, page, parent, depth):
        self.page = int(page)
        self.parent = parent                # key of the parent entry
        self.children = 0
        self.last_use = 0
        self.depth = depth


class PrefixIndex:
    """Hash-chain prefix index at page granularity.

    Key ``i`` of a token sequence is ``H(key_{i-1}, tokens[i*page :
    (i+1)*page])`` — a chain, so block ``i`` only ever matches behind an
    identical prefix (no cross-request aliasing of same-content blocks
    at different positions).  Entries hold one pool reference each; a
    lookup increfs every matched page for the requesting slot.  Eviction
    is LRU over LEAF entries whose page nobody else references."""

    def __init__(self):
        self._entries = {}                  # key -> _PrefixEntry
        self._clock = 0

    def __len__(self):
        return len(self._entries)

    @staticmethod
    def _chain(tokens, page_size, upto_blocks):
        key = b"prefix"
        tokens = np.ascontiguousarray(np.asarray(tokens, np.int32))
        for i in range(upto_blocks):
            block = tokens[i * page_size:(i + 1) * page_size]
            key = hashlib.sha1(key + block.tobytes()).digest()
            yield key

    def lookup(self, tokens, page_size, pool, max_blocks):
        """The longest indexed chain matching ``tokens``' leading full
        blocks (capped at ``max_blocks``); increfs and returns the
        matched physical pages (possibly empty)."""
        self._clock += 1
        matched = []
        full = min(len(tokens) // page_size, max_blocks)
        for key in self._chain(tokens, page_size, full):
            ent = self._entries.get(key)
            if ent is None:
                break
            ent.last_use = self._clock
            pool.incref(ent.page)
            matched.append(ent.page)
        return matched

    def register(self, tokens, page_size, row_pages, pool, upto_blocks):
        """Index ``tokens``' first ``upto_blocks`` full blocks as
        sharable, backed by ``row_pages`` (the slot's table row, whose
        prefill just wrote them).  Blocks already indexed keep their
        existing entry (same content; the slot may be holding either
        copy).  Each NEW entry takes one pool reference."""
        self._clock += 1
        parent = None
        registered = 0
        for i, key in enumerate(self._chain(tokens, page_size,
                                            upto_blocks)):
            ent = self._entries.get(key)
            if ent is None:
                ent = _PrefixEntry(row_pages[i], parent, i)
                pool.incref(ent.page)
                self._entries[key] = ent
                if parent is not None:
                    self._entries[parent].children += 1
                registered += 1
            ent.last_use = self._clock
            parent = key
        return registered

    def evict(self, pool, need_pages):
        """Free index references LRU-leaf-first until ``need_pages``
        pages would land on the free list (entries whose page is still
        referenced elsewhere release the index ref without freeing the
        page).  Returns the number of pages actually freed."""
        freed = 0
        while freed < need_pages:
            victim_key, victim = None, None
            for key, ent in self._entries.items():
                if ent.children:
                    continue
                if victim is None or ent.last_use < victim.last_use:
                    victim_key, victim = key, ent
            if victim is None:
                break
            if pool.refcount(victim.page) == 1:
                freed += 1
            pool.decref(victim.page)
            if victim.parent is not None:
                self._entries[victim.parent].children -= 1
            del self._entries[victim_key]
        return freed

    def clear(self, pool):
        """Drop every entry (and its pool reference) — the pool buffer
        died or the server is retiring."""
        for ent in self._entries.values():
            pool.decref(ent.page)
        self._entries.clear()


class SlotPages:
    """Which physical pages back which slot — the serving engine's cache
    manager.  Host-side only; it takes NO lock of its own: every call
    runs under the engine's ``_lock`` (the engine's ``_pages`` field is
    the one guarded paging field, ``serving/concurrency.py``).

    ``page_size`` is rounded up to a multiple of 8 (sublane alignment,
    floor 8) and the slot's virtual lane, ``cache_len``, up to a whole
    number of pages.  ``num_pages`` INCLUDES the reserved trash page 0;
    0 = auto, the full worst case (every slot at ``cache_len`` — no HBM
    savings, no pool pressure).  ``share_prefixes`` turns the prefix
    index on.  ``stats`` is the dict (the engine's) whose
    ``prefix_lookups`` / ``prefix_hits`` / ``prefix_tokens_reused`` /
    ``page_evictions`` entries the admissions are counted into."""

    def __init__(self, module, contract, num_slots, cache_len, page_size,
                 num_pages, chunk, share_prefixes, stats):
        self.page = page_rows(page_size)
        # positions a lane row stands for: all page arithmetic below is in
        # rows
        self.stride = int(contract.lane_stride)
        self.pages_per_slot = self._lane_pages(cache_len)
        self.cache_len = self.pages_per_slot * self.page * self.stride
        self.num_slots = int(num_slots)
        self.num_pages = int(num_pages) \
            or self.num_slots * self.pages_per_slot + 1
        if self.num_pages < 2:
            raise ValueError(f"serving.num_pages={num_pages}: "
                             f"need >= 2 (trash + 1 allocatable)")
        self.chunk = int(chunk)
        self.share_prefixes = bool(share_prefixes)
        self._stats = stats
        self._module = module
        self._contract = contract
        self._buffer = None              # the device pool, between uses
        self._pool = PagePool(self.num_pages)
        self._prefix = PrefixIndex()
        # a model with window layers (the contract's ``ring_pages``) keeps
        # their rows in a pool of its own: every slot owns that many pages
        # there for good — a ring over the window's positions, whatever
        # the prompt's length — behind one trash page; the slot's table
        # row carries them after its lane pages
        self.ring_pages = int(contract.ring_pages(self.page))
        self.window_pages = 1 + self.num_slots * self.ring_pages \
            if self.ring_pages else 0
        # a model whose layers keep a fixed-size state a slot names the
        # pools that hold it (``state_kinds``: keys of its cache, each
        # ``[layers, state rows, ...]``): slot ``s`` owns row ``1 + s`` of
        # them for as long as it is reserved, row 0 is the trash row, and
        # the row's index is the LAST entry of the slot's table row — so
        # whatever sends a dead lane's pages to the trash page sends its
        # state writes to the trash row
        self.state_kinds = tuple(contract.state_kinds)
        self.state_rows = 1 + self.num_slots if self.state_kinds else 0
        self.state_row_bytes = 0         # known once the pools are made
        self.state_kind_bytes = {}       # ... a row's bytes in each kind
        self.page_bytes = 0
        # ... and a model that names its ring pools (``ring_kinds``) has the
        # rings' bytes counted beside the pages'
        self.ring_kinds = tuple(contract.ring_kinds)
        self.ring_slot_bytes = 0
        self.fold_pages = 1              # (their dtype decides it)
        self.table_width = self.pages_per_slot + self.ring_pages \
            + bool(self.state_kinds)
        if (self.ring_pages or self.state_kinds or self.stride > 1) \
                and self.share_prefixes:
            # a shared prefix's pages hold the positional rows of the lane
            # pools only: the sharer's rings would miss the window's rows
            # before its first private position, and its state row the
            # state at the shared boundary (and a strided lane's page
            # boundary is no position the prefix index hashes to)
            self.share_prefixes = False
            stats["prefix_sharing_refused"] = 1
        if self.state_kinds:
            stats.update(state_rows_live=0, state_bytes=0)
        # shipped as a traced arg on every dispatch; 0 = the trash page
        self._table = np.zeros((self.num_slots, self.table_width),
                               np.int32)
        self._rows = {}                  # slot -> [page ids]

    # ---- the device buffer ----
    def take(self, dtype):
        """The pool buffer for the next dispatches: donated into every
        slot program and reclaimed from its output, reallocated only
        when a failed dispatch left the returned buffers dead — with
        every mapping dropped, so the host mirror matches it: everything
        free, nothing indexed."""
        pool, self._buffer = self._buffer, None
        if pool is None or any(getattr(l, "is_deleted", lambda: False)()
                               for l in jax.tree.leaves(pool)):
            pool = self.new_pools(dtype)
        self.reset()
        return pool

    def new_pools(self, dtype):
        """The model's zero pool(s) at this manager's sizes — one ``k`` /
        ``v`` pair, pools by row kind (``models/dots3.py``), page pools
        beside state pools (``models/lfm2.py``), or K/V page pools for some
        layers beside K/V ring pools for the others
        (``models/trinity.py``)."""
        kinds = {"window_pages": self.window_pages} if self.ring_pages \
            else {}
        if self.state_kinds:
            kinds["state_rows"] = self.state_rows
        pools = self._module.init_paged_cache(self.num_pages, self.page,
                                              dtype=dtype, **kinds)
        self.fold_pages = _decode_block_pages(
            self.page, self.pages_per_slot, jax.numpy.dtype(dtype).itemsize)
        nbytes = lambda keys: sum(pools[k].size * pools[k].dtype.itemsize
                                  for k in keys)
        if self.state_kinds:
            # a kind's pool has its own dtype and shape (``models/
            # solar_open2.py``: bfloat16 conv rows beside a float32 matrix
            # state whatever ``dtype`` is): a row is counted kind by kind
            self.state_kind_bytes = {k: nbytes([k]) // self.state_rows
                                     for k in self.state_kinds}
            self.state_row_bytes = sum(self.state_kind_bytes.values())
        if self.ring_kinds:
            self.ring_slot_bytes = nbytes(self.ring_kinds) \
                // self.window_pages * self.ring_pages
        if self.state_kinds or self.ring_kinds:
            self.page_bytes = nbytes(
                set(pools) - set(self.state_kinds) - set(self.ring_kinds)) \
                // self.num_pages
        return pools

    def pool_bytes(self, pools):
        """``pools``' size on the device: ``{"bytes": all of it}`` and, of
        a model whose pools are of several row kinds, ``bytes_pages`` /
        ``bytes_ring`` / ``bytes_state`` beside it (what
        ``dstpu.setup.pools`` carries)."""
        nbytes = lambda keys: sum(int(pools[k].nbytes) for k in keys)
        out = {"bytes": nbytes(pools)}
        if self.state_kinds or self.ring_kinds:
            out["bytes_ring"] = nbytes(self.ring_kinds) or None
            out["bytes_state"] = nbytes(self.state_kinds) or None
            out["bytes_pages"] = out["bytes"] - nbytes(
                self.ring_kinds + self.state_kinds)
        return out

    def give_back(self, pool):
        self._buffer = pool

    def drop_buffer(self):
        """Forget the held buffer (the server is retiring)."""
        self._buffer = None

    # ---- slots ----
    def _lane_pages(self, positions):
        """Lane pages that back ``positions`` cache positions: a row a
        ``stride`` of them."""
        return pages_for(-(-int(positions) // self.stride), self.page)

    def cannot_hold(self, positions):
        """Why the pool can NEVER back a request of ``positions`` cache
        positions, or ``None`` when it can: such a request must not
        enter the queue — with every other slot drained it would still
        stall admission forever."""
        n = self._lane_pages(positions)
        if n <= self._pool.allocatable:
            return None
        return (f"{n} pages ({positions} positions at page_size="
                f"{self.page}"
                + (f", a row a {self.stride} positions"
                   if self.stride > 1 else "")
                + f") but the pool holds "
                f"{self._pool.allocatable} allocatable pages "
                f"(num_pages={self.num_pages} incl. trash)")

    def reserve(self, slot, fill, max_new):
        """Back ``slot`` for a request that prefills ``fill`` tokens and
        then decodes ``max_new``: map the longest indexed prefix (full
        pages, refcounted — prefilled ONCE per unique prefix) and
        allocate private pages for the rest of the virtual lane.
        Returns ``(row pages, prefill start)`` — prefill runs from the
        shared boundary on — or ``None``, with nothing allocated, when
        the pool cannot back the request yet even after evicting
        unreferenced prefix pages."""
        P, page, chunk, pool = len(fill), self.page, self.chunk, self._pool
        matched = []
        if self.share_prefixes:
            # cap the match so the block holding the LAST prompt position
            # is always recomputed: admission samples the first token
            # from that position's logits, so at least one chunk must run
            matched = self._prefix.lookup(fill, page, pool, (P - 1) // page)
        m = len(matched)
        # the prefill start must be CHUNK-aligned, not just page-aligned:
        # chunk ci writes the full padded span [s0+ci*C, s0+(ci+1)*C),
        # and only a chunk-aligned s0 keeps the padded end at
        # ceil(P/C)*C — the bound submit() already checked against the
        # lane.  A page-aligned-only start can pad PAST the table row
        # (page 16, chunk 64, P=120, m=7: 112+64=176 > 8-page lane)
        g = chunk // math.gcd(page, chunk)
        if m % g:
            for pg in matched[(m // g) * g:]:
                pool.decref(pg)
            matched = matched[:(m // g) * g]
            m = len(matched)
        s0 = m * page                    # prefill start
        n_chunks = -(-(P - s0) // chunk)
        # the slot's virtual extent: decode writes through P+max_new-1,
        # the padded last chunk writes through s0+n_chunks*C-1
        virt = max(P + max_new, s0 + n_chunks * chunk)
        need_private = self._lane_pages(virt) - m
        got = pool.alloc(need_private)
        if got is None and self.share_prefixes:
            self._stats["page_evictions"] += self._prefix.evict(
                pool, need_private - pool.free_count)
            got = pool.alloc(need_private)
        if got is None:
            for pg in matched:
                pool.decref(pg)
            return None
        if self.share_prefixes:
            # stats count ADMISSIONS, not stalled retries of the same
            # request (a 50-step stall must not record 50 lookups/hits)
            self._stats["prefix_lookups"] += 1
            if matched:
                self._stats["prefix_hits"] += 1
                self._stats["prefix_tokens_reused"] += s0
        row = matched + got
        self._rows[int(slot)] = row
        self._table[slot, :] = TRASH_PAGE
        self._table[slot, :len(row)] = row
        # the slot's own ring: pages 1 + slot * ring .. of the window pool
        ring_end = self.pages_per_slot + self.ring_pages
        self._table[slot, self.pages_per_slot:ring_end] = \
            1 + int(slot) * self.ring_pages + np.arange(self.ring_pages)
        if self.state_kinds:
            # the slot's own state row; whatever its last occupant left
            # there, the request's first chunk starts from zeros (it starts
            # at position 0: prefix sharing is off)
            self._table[slot, -1] = 1 + int(slot)
            self._count_state()
        return row, s0

    def share(self, slot, fill):
        """Index ``slot``'s full pages of ``fill`` as sharable — its
        prefill writes are complete (dispatched before the admit) and
        nothing ever writes them again (the slot's own writes land at
        positions >= ``len(fill)``)."""
        if self.share_prefixes:
            self._prefix.register(fill, self.page, self._rows[int(slot)],
                                  self._pool, len(fill) // self.page)

    def release(self, slot):
        """Return a retired slot's pages to the pool (shared prefix
        pages just drop one reference) and point its table row at the
        trash page — the NEXT dispatch's table redirects the zombie
        lane's masked writes there, so a freed page can be reallocated
        immediately (any write the zombie already has in flight executes
        in device order BEFORE the new occupant's prefill and is either
        overwritten or masked — docs/serving.md "KV cache")."""
        for pg in self._rows.pop(int(slot), ()):
            self._pool.decref(pg)
        self._table[int(slot), :] = TRASH_PAGE
        self._count_state()

    def _state_bytes(self):
        return len(self._rows) * self.state_row_bytes

    def _count_state(self):
        """``stats``' levels of the state kind: rows that slots hold, and
        their bytes."""
        if self.state_kinds:
            self._stats["state_rows_live"] = len(self._rows)
            self._stats["state_bytes"] = self._state_bytes()

    def reset(self):
        """Drop EVERY mapping (pool bookkeeping, prefix index, all table
        rows) — the pool buffer died with a failed dispatch or was just
        (re)allocated, so no indexed content survives."""
        self._prefix.clear(self._pool)
        self._pool.reset()
        self._table[:] = TRASH_PAGE
        self._rows.clear()
        self._count_state()

    # ---- what the dispatches ship ----
    def table(self):
        """``[num_slots, table_width]`` int32, every slot's row: its lane
        pages, then (a model with window layers) its ring pages, then (a
        model with a fixed-size state a slot) its state row."""
        return self._table

    def row(self, slot):
        """``[1, table_width]`` int32, one slot's row."""
        return self._table[slot:slot + 1]

    # ---- observability ----
    @property
    def in_use(self):
        return self._pool.in_use

    @property
    def utilization(self):
        """Allocated fraction of the pool."""
        return self._pool.utilization()

    def describe(self):
        text = (f"page pool: {self._pool.in_use}/{self._pool.allocatable} "
                f"in use, {len(self._prefix)} prefix entries")
        if self.ring_pages:
            held = int((self._table[:, self.pages_per_slot]
                        != TRASH_PAGE).sum())
            lane_rows, ring_rows = self._contract.row_kinds
            text += (f"; by row kind: {lane_rows} "
                     f"{self._pool.in_use} pages, {ring_rows} "
                     f"{held * self.ring_pages}/{self.window_pages - 1} "
                     f"pages ({self.ring_pages} a slot, a ring)")
            if self.ring_kinds:
                text += (f"; bytes held: {lane_rows} "
                         f"{self._pool.in_use * self.page_bytes}, "
                         f"{ring_rows} {held * self.ring_slot_bytes}")
        if self.stride > 1:
            text += f"; a lane row a {self.stride} positions"
        if self.state_kinds:
            text += (f"; state ({', '.join(self.state_kinds)}): "
                     f"state_rows_live {len(self._rows)}/"
                     f"{self.state_rows - 1} (one a slot, row 0 trash), "
                     f"state_bytes {self._state_bytes()}")
            if len(self.state_kinds) > 1:
                text += " (" + ", ".join(
                    f"{k} {len(self._rows) * n}"
                    for k, n in self.state_kind_bytes.items()) + ")"
        return text

    def slot_pages_str(self, slot):
        """``slot``'s pages, range-compressed (:func:`compact_page_str`)
        — ``None`` for a slot that holds none."""
        row = self._rows.get(int(slot))
        return None if row is None else compact_page_str(row)

    def chunk_reach(self, layers, end, live_end=None):
        """What a prefill chunk writing through position ``end - 1``
        attends, as its dispatch span's args: ``kv_pages`` — the pages
        its layers fetch, every page of the slot's table up to the
        chunk's furthest position, which is the paged chunk-prefill
        kernel's block loop — and ``kv_pages_table``, pages a slot x
        layers, what a walk over the whole table would take.  A model
        that counts its own attention work (the contract's ``chunk_work``,
        the names its own) adds it, over the chunk's REAL positions —
        through ``live_end - 1``, the padded tail left out."""
        reach = self._lane_pages(end)
        work = self._contract.chunk_work
        return {"kv_pages": layers * min(reach, self.pages_per_slot),
                "kv_pages_table": layers * self.pages_per_slot,
                **({"state_rows": 1} if self.state_kinds else {}),
                **(work(end - self.chunk, min(end, live_end or end),
                        self.page, self.ring_pages, layers)
                   if work else {})}

    def block_reach(self, layers, live, block):
        """What a decode block of ``block`` steps walks, as its dispatch
        span's args, from ``live`` — ``(context, steps)`` per live slot,
        the positions its first step attends and the steps it takes
        (``layers``: the layers the dispatch runs, what the model's own
        ``block_work`` sums over):
        ``kv_pages``, ``ceil(context / page_size)`` a live slot and
        step, which is the paged-decode kernel's page loop;
        ``kv_folds``, the online-softmax updates that loop makes of
        them, ``ceil(pages / pages a block)`` a live slot and step by
        the kernel's own rule (``kv_pages / kv_folds`` is the pages an
        update really carried); and ``kv_pages_table``, the slots x
        pages-a-slot x steps a walk over the whole table would take —
        ``kv_pages`` over it is the share of the table that is live."""
        work = self._contract.block_work
        pages = [self._lane_pages(first + i)
                 for first, steps in live for i in range(steps)]
        return {"kv_pages": sum(pages),
                "kv_folds": sum(-(-n // self.fold_pages) for n in pages),
                "kv_pages_table":
                    self.num_slots * self.pages_per_slot * block,
                **(self._state_reach(live) if self.state_kinds else {}),
                **(self._ring_reach() if self.ring_kinds else {}),
                **(work(live, self.ring_pages, layers) if work else {})}

    def _ring_reach(self):
        """The cache's split by kind, as a decode block's span args:
        ``ring_bytes_held`` — the rings of the slots that are reserved,
        held whole whatever the context — and ``kv_bytes_mapped`` — the
        lane pages slots hold."""
        return {"ring_bytes_held": len(self._rows) * self.ring_slot_bytes,
                "kv_bytes_mapped": self._pool.in_use * self.page_bytes}

    def _state_reach(self, live):
        """A decode block's state work and the cache's split, as span
        args: ``state_rows`` — state rows read and written, one a live
        slot and step —, ``state_bytes`` — the state rows slots hold, every
        kind's summed, and of a model with several kinds
        ``state_bytes_<kind>`` each — and ``kv_bytes_mapped`` — the pages
        slots hold, over every pool the page table indexes."""
        by_kind = {f"state_bytes_{k}": len(self._rows) * n
                   for k, n in self.state_kind_bytes.items()} \
            if len(self.state_kinds) > 1 else {}
        return {"state_rows": sum(steps for _, steps in live),
                "state_bytes": self._state_bytes(), **by_kind,
                "kv_bytes_mapped": self._pool.in_use * self.page_bytes}

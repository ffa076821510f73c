"""Block-allocated KV-cache pool with per-sequence page tables.

The serving memory manager (the vLLM/Orca idea restated TPU-first): the
KV cache for all concurrent sequences of one GROUP of layers lives in ONE
pair of device arrays

    k, v : (num_layers, num_pages, page_size, kv_heads, head_dim)

(``kv_heads`` are the heads a cached token holds: the queries may have a
multiple of them) and each sequence owns an ordered list of physical pages
(its *page table*).  Sequences grow a page at a time, free their pages the
moment they finish, and never copy — admission capacity is bounded by free
pages, not by worst-case padded sequences.

**Groups of layers** (:class:`GroupedKVCachePool`): a model whose layers
are of several kinds states one :class:`~hetu_tpu.layers.cache.CacheSpec` a
group (``GroupedCacheSpec``), and each group gets a :class:`KVCachePool` of
its own: its own arrays, pages, free list and one page table a sequence.  A
group with a ``window`` (sliding-window attention) holds at most ``window /
page_size + 1`` pages a sequence, a RING: logical page ``p`` lives in slot
``p mod ring`` of the table, so a sequence that outgrows the window
overwrites its own oldest page and takes nothing from the free list
(``stats()["pages_overwritten"]``).  Admission asks every group
(``needed_by_group`` against ``free_by_group``); :func:`make_pool` builds
the one or the other from a model's spec.  What reads whole prefixes out of
pages (prefix sharing, page export and import, copy-on-write, speculative
decoding) is refused on a ring and on a grouped pool by
:exc:`UnsupportedCacheLayout`.

XLA, however, wants static shapes.  The bridge is the *bucketed view*:
``gather_indices(seq_ids)`` pads every page table to the same
``pages_per_seq`` with the reserved scratch page 0, so the jitted decode
step always sees

    page_idx : (batch, pages_per_seq)                       — int32
    view     : k[:, page_idx] -> (L, batch, max_len, H, D)  — one gather

and writes back with one scatter.  Shapes depend only on (batch bucket,
length bucket), so XLA compiles ONE decode program and one prefill
program per bucket, ever.  Page 0 is never allocated to a sequence:
padded table entries read (masked) garbage from it and scatter their
dead rows back into it, keeping both directions legal without per-row
conditionals.

Host-side management (alloc/grow/free/defrag) is plain Python over a
sorted free list — deterministic: the same request schedule produces the
same physical placement, which the bitwise-replay acceptance tests rely
on.  ``defrag()`` compacts live pages toward low indices (the long-lived
server shape: after hours of ragged arrivals, a fresh long request needs
contiguous-ish headroom only the compactor can guarantee).

**Copy-on-write prefix sharing** (the fleet tier, serve/fleet/prefix.py):
pages are REFCOUNTED.  ``alloc(..., shared_pages=)`` returns a table
whose leading entries alias already-written pages of an identical prompt
prefix (each alias is a refcount, not a copy — the fleet stops re-storing
the same system prompt per request); :meth:`~KVCachePool.retain` lets the
prefix trie keep a page alive after its publishing sequence retires;
``free`` only returns a page to the free list when its last reference
drops, and a second ``free`` of the same sequence raises the NAMED
:class:`DoubleFree` instead of silently corrupting the free list.
:meth:`~KVCachePool.copy_on_write` un-shares a page the moment a
sequence needs to WRITE into it, and ``defrag`` treats every shared or
trie-cached page as pinned-by-refcount (moving a page another table or
the trie also points at would corrupt them all).  :meth:`stats` is the
supported introspection surface — pages by class, the refcount
histogram, and an alloc/free balance invariant asserted on every call.

**KV-page migration** (the disaggregated tier, serve/fleet/disagg.py):
:meth:`~KVCachePool.export_pages` snapshots one sequence's pages into a
self-describing, CRC- and fingerprint-verified record
(serve/fleet/migrate.py) and places an EXPORT HOLD (one extra refcount
per page) so that ``free()`` of the exporting sequence cannot recycle
the pages until :meth:`~KVCachePool.ack_export` /
:meth:`~KVCachePool.cancel_export` settles the handoff;
:meth:`~KVCachePool.import_pages` re-verifies the record (torn / CRC /
fingerprint / geometry, each a named diagnosis) before a single byte is
admitted into the destination pool.  ``stats()`` carries the
``exported_pages`` / ``imported_pages`` / ``pages_export_held``
counters and asserts the hold-backed-by-refcount invariant.
"""

from __future__ import annotations

import bisect
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.layers.cache import (CacheSpec, GroupedCacheSpec,
                                   gather_view_count,
                                   reset_gather_view_count)
from hetu_tpu.obs import memledger as _memledger

__all__ = ["KVCachePool", "GroupedKVCachePool", "make_pool", "CacheSpec",
           "GroupedCacheSpec", "PageTable", "GroupedPageTable", "OutOfPages",
           "DoubleFree", "UnsupportedCacheLayout", "SCRATCH_PAGE",
           "gather_view_count", "reset_gather_view_count",
           "pages_written_count", "reset_pages_written_count",
           "note_pages_written"]

# Second counting seam, same style: how many KV pages were freshly
# COMPUTED-AND-WRITTEN by prefill (the engine notes them after each
# prefill step).  A shared-prefix prefill aliases its prefix pages
# instead of recomputing them, so the acceptance test can prove that an
# identical-prefix request writes ZERO duplicate prefix pages — the
# whole point of copy-on-write sharing.
_pages_written = 0


def pages_written_count() -> int:
    """Pages freshly written by prefill since the last reset (aliased
    shared-prefix pages are never counted — they were not recomputed)."""
    return _pages_written


def note_pages_written(n: int) -> None:
    global _pages_written
    _pages_written += int(n)


def reset_pages_written_count() -> None:
    global _pages_written
    _pages_written = 0

# Physical page 0 is reserved: page-table padding points at it, and the
# scatter of a padded decode batch dumps dead rows into it.  Never
# allocated, never trusted.
SCRATCH_PAGE = 0


class OutOfPages(RuntimeError):
    """The pool cannot satisfy an allocation — admission control should
    hold the request in the queue until sequences retire."""


class DoubleFree(RuntimeError):
    """A sequence (or page) was freed twice.  Raised by ``free`` for an
    unknown sequence id and by ``release`` for a page already on the
    free list — NAMED, so the bug surfaces at the second free instead of
    corrupting the free list and handing one physical page to two
    sequences steps later."""


class UnsupportedCacheLayout(ValueError):
    """A feature that reads keys and values of whole prefixes out of one
    pool's pages was asked of a pool that holds something else: a latent, a
    window's ring, or several groups of layers.  Page export and import
    (migration, disaggregated roles, KV salvage on failover), prefix
    sharing, copy-on-write and speculative decoding.  Raised where the
    feature is built or first called, by name, so that none of them is
    silently wrong."""


@dataclasses.dataclass
class PageTable:
    """One sequence's allocation: ordered physical pages + token length."""

    seq_id: int
    pages: list
    length: int = 0  # valid tokens written so far
    reach: int = 0   # tokens the allocation was last asked to cover

    def capacity(self, page_size: int) -> int:
        return len(self.pages) * page_size


class GroupedPageTable:
    """One sequence's allocation in a :class:`GroupedKVCachePool`: one
    :class:`PageTable` a group under one ``length``."""

    def __init__(self, seq_id: int, tables: dict):
        self.seq_id, self.tables = seq_id, tables

    @property
    def length(self) -> int:
        return next(iter(self.tables.values())).length

    @length.setter
    def length(self, n: int) -> None:
        for pt in self.tables.values():
            pt.length = n

    @property
    def pages(self) -> list:
        """Every page the sequence holds, the groups' one after another
        (for counting: an index means something only within its group)."""
        return [p for pt in self.tables.values() for p in pt.pages]


@functools.lru_cache(maxsize=None)
def _page_writer(n: int):
    """The pool's own small donated program for a pool of ``n`` arrays:
    ``write(*arrays, idx, *pages)`` writes whole pages at the physical
    indices ``idx`` in place (copy-on-write, page import)."""
    def write(*args):
        arrays, idx, pages = args[:n], args[n], args[n + 1:]
        return tuple(a.at[:, idx].set(p) for a, p in zip(arrays, pages))
    return jax.jit(write, donate_argnums=tuple(range(n)))


class KVCachePool:
    """Paged KV storage for all layers of one model + its allocator.

    What a page holds comes from the model's :class:`CacheSpec` (``spec``,
    of ``hetu_tpu.layers``).  ``arrays`` holds one device array an entry of
    the spec; for keys and values ``k`` and ``v`` name the two, and on any
    other pool they raise :exc:`UnsupportedCacheLayout`.

    The pool itself stays a plain host-side object (no tracers); ``k``
    and ``v`` are the one copy of the cache on the device, and every
    program that updates them takes them DONATED and writes in place.
    So an array handed to a step is consumed: the only valid arrays are
    the ones last given to :meth:`commit`.  Read ``pool.k``/``pool.v``
    fresh, between steps, under the owner's lock, and hold no reference
    to either across a step — :meth:`step` is the one way the serving
    programs get them.  If a program fails after it consumed the arrays
    the pool is lost with it; there is no second copy to fall back on.
    """

    def __init__(self, *, spec: CacheSpec, num_pages: int, page_size: int,
                 max_seq_len: int):
        if num_pages < 2:
            raise ValueError("need >= 2 pages (page 0 is the reserved "
                             "scratch page)")
        if max_seq_len % page_size:
            raise ValueError(f"max_seq_len {max_seq_len} must be a "
                             f"multiple of page_size {page_size}")
        self.spec = spec
        self.num_layers = spec.num_layers
        # of a pool of keys and values; None on any other
        self.num_heads, self.head_dim = (spec.entries[0][1] if spec.holds_kv
                                         else (None, None))
        self.num_pages = num_pages
        self.page_size = page_size
        self.max_seq_len = max_seq_len
        # a window group's sequence holds a ring of pages (module docstring)
        self.window = spec.window
        self.pages_per_seq = spec.pages_per_seq(page_size, max_seq_len)
        self._overwritten = 0      # ring slots taken again by a later page
        self.arrays = tuple(
            jnp.zeros((spec.num_layers, num_pages)
                      + spec.page_shape(shape, page_size), spec.dtype)
            for _, shape in spec.entries)
        # ascending free list => lowest-index-first placement, deterministic
        self._free: list = list(range(1, num_pages))
        self._tables: dict = {}
        # page -> reference count (tables aliasing it + trie retains +
        # export holds); absent == on the free list.  A page leaves the
        # free list with rc 1 and returns only when its LAST reference
        # drops.
        self._refcount: dict = {}
        # alloc/free balance for the stats() invariant
        self._allocs = 0
        self._frees = 0
        # outstanding KV-page exports (disaggregated serving): seq_id ->
        # the pages snapshotted into a MigrationRecord, each holding one
        # extra reference until the import acks or the export is
        # cancelled — free() of an exporting sequence must never recycle
        # a page an in-flight migration may still need
        self._exports: dict = {}
        self._exported_pages = 0   # cumulative pages exported
        self._imported_pages = 0   # cumulative pages imported
        # seq_id -> owner (tenant id) for the per-tenant ledger view;
        # absent == unowned (stats report it under "-")
        self._owners: dict = {}

    def require_kv(self, what: str) -> None:
        """Raises unless the pool holds keys and values of whole
        sequences."""
        if not self.spec.holds_kv:
            raise UnsupportedCacheLayout(
                f"{what} reads keys and values; this pool holds "
                f"{[n for n, _ in self.spec.entries]} "
                f"({self.spec.values_per_token} values a token a layer)")
        self._require_whole(what)
        if self.spec.head_major:
            raise UnsupportedCacheLayout(
                f"{what} reads token-major pages (page_size, heads, "
                f"head_dim); this pool's are head-major")

    def _require_whole(self, what: str) -> None:
        """Raises on a ring: its table's entries are slots, not the
        sequence's pages from the first on (aliasing or copying one as a
        prefix's would be wrong whatever the pages hold)."""
        if self.window is not None:
            raise UnsupportedCacheLayout(
                f"{what} reads a sequence's pages from its first token on; "
                f"group {self.spec.name!r} holds the last {self.window} "
                f"tokens in a ring of {self.pages_per_seq} pages")

    @property
    def k(self):
        self.require_kv("pool.k")
        return self.arrays[0]

    @property
    def v(self):
        self.require_kv("pool.v")
        return self.arrays[1]

    @property
    def nbytes(self) -> int:
        """Bytes of the pool's arrays: pages x page x layers x the spec's
        bytes a token."""
        return sum(int(a.nbytes) for a in self.arrays)

    # -- allocator ----------------------------------------------------------

    @property
    def free_pages(self) -> int:
        return len(self._free)

    @property
    def live_sequences(self) -> int:
        return len(self._tables)

    def pages_needed(self, n_tokens: int) -> int:
        return min(self._logical_pages(n_tokens), self.pages_per_seq)

    def _logical_pages(self, n_tokens: int) -> int:
        return -(-max(n_tokens, 1) // self.page_size)

    def can_admit(self, n_tokens: int) -> bool:
        return self.pages_needed(n_tokens) <= len(self._free)

    def by_group(self) -> dict:
        """Group name -> the pool of its pages (this one)."""
        return {self.spec.name: self}

    @property
    def pages_overwritten(self) -> int:
        """Ring slots a later page of the same sequence took again."""
        return self._overwritten

    def free_by_group(self) -> list:
        """Free pages of each group: what admission budgets against."""
        return [len(self._free)]

    def needed_by_group(self, n_tokens: int) -> list:
        return [self.pages_needed(n_tokens)]

    def table_shapes(self, rows: int):
        """What :meth:`gather_indices` returns for ``rows`` sequences, as
        shapes (for lowering a step program without running it)."""
        return jax.ShapeDtypeStruct((rows, self.pages_per_seq), jnp.int32)

    def alloc(self, seq_id: int, n_tokens: int,
              shared_pages=(), owner=None) -> PageTable:
        """Reserve capacity for ``n_tokens`` (>=1 page).  Raises
        :exc:`OutOfPages` without side effects when the pool is short.

        ``shared_pages`` are already-allocated pages holding an identical
        prompt prefix (the prefix trie's match): the returned table's
        leading entries ALIAS them — each gains a refcount, no K/V bytes
        move — and only the remainder is freshly allocated.  ``owner``
        (a tenant id) tags the sequence for the per-tenant ledger/stats
        view; it never affects placement."""
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        need = self.pages_needed(n_tokens)
        if n_tokens > self.max_seq_len:
            raise ValueError(f"sequence of {n_tokens} tokens exceeds "
                             f"max_seq_len {self.max_seq_len}")
        shared = list(shared_pages)
        if shared:
            self._require_whole("alloc(shared_pages=) (prefix sharing)")
        if len(shared) > need:
            raise ValueError(f"{len(shared)} shared prefix pages exceed "
                             f"the {need} pages {n_tokens} tokens need")
        for p in shared:
            if self._refcount.get(p, 0) < 1:
                raise ValueError(f"shared page {p} is not allocated")
        fresh = need - len(shared)
        if fresh > len(self._free):
            raise OutOfPages(f"need {fresh} pages, {len(self._free)} free")
        for p in shared:
            self._refcount[p] += 1
        pages = shared + [self._free.pop(0) for _ in range(fresh)]
        for p in pages[len(shared):]:
            self._refcount[p] = 1
        pt = PageTable(seq_id, pages, reach=n_tokens)
        self._tables[seq_id] = pt
        self._allocs += 1
        if owner is not None:
            self._owners[seq_id] = str(owner)
        _memledger.note_kv(self, alloc=1)
        return pt

    def ensure(self, seq_id: int, n_tokens: int) -> PageTable:
        """Grow ``seq_id``'s allocation to cover ``n_tokens`` (the
        one-page-at-a-time growth of a decoding sequence)."""
        pt = self._tables[seq_id]
        if n_tokens > self.max_seq_len:
            raise ValueError(f"sequence {seq_id} would exceed max_seq_len "
                             f"{self.max_seq_len}")
        while len(pt.pages) < self.pages_needed(n_tokens):
            if not self._free:
                raise OutOfPages(f"growing sequence {seq_id}: no free pages")
            p = self._free.pop(0)
            self._refcount[p] = 1
            pt.pages.append(p)
        # logical pages past the ring take the slot of the page that fell
        # out of the window
        self._overwritten += max(0, self._logical_pages(n_tokens) - max(
            self._logical_pages(pt.reach), self.pages_per_seq))
        pt.reach = max(pt.reach, n_tokens)
        _memledger.note_kv(self)
        return pt

    def retain(self, page: int) -> None:
        """Add one reference to an allocated page (the prefix trie's hold:
        a published prefix outlives the sequence that computed it)."""
        if self._refcount.get(page, 0) < 1:
            raise ValueError(f"retain of unallocated page {page}")
        self._refcount[page] += 1
        _memledger.note_kv(self)

    def release(self, page: int) -> None:
        """Drop one reference; the page returns to the free list only at
        zero (sorted insert keeps placement deterministic)."""
        rc = self._refcount.get(page)
        if rc is None:
            raise DoubleFree(f"page {page} is already on the free list")
        if rc == 1:
            del self._refcount[page]
            bisect.insort(self._free, page)
        else:
            self._refcount[page] = rc - 1
        _memledger.note_kv(self)

    def free(self, seq_id: int) -> None:
        """Drop the sequence's reference on each of its pages; pages whose
        last reference this was return to the pool.  A second ``free`` of
        the same sequence raises :exc:`DoubleFree`."""
        pt = self._tables.pop(seq_id, None)
        if pt is None:
            raise DoubleFree(f"sequence {seq_id} already freed (or never "
                             f"allocated)")
        for p in pt.pages:
            self.release(p)
        self._frees += 1
        self._owners.pop(seq_id, None)
        _memledger.note_kv(self, free=1)

    def copy_on_write(self, seq_id: int, token_index: int) -> bool:
        """Un-share before a write: if the page holding ``token_index``
        is aliased (refcount > 1), copy its K/V rows into a fresh private
        page, point this sequence's table at the copy, and drop the
        reference on the original — the other aliases keep the original
        bytes.  Returns True when a copy happened (refcount-1 pages are
        already private: no copy, False)."""
        self._require_whole("copy_on_write")
        pt = self._tables[seq_id]
        i = token_index // self.page_size
        old = pt.pages[i]
        if self._refcount[old] == 1:
            return False
        if not self._free:
            raise OutOfPages(f"copy-on-write for sequence {seq_id}: "
                             f"no free page for the private copy")
        new = self._free.pop(0)
        src = jnp.asarray([old], jnp.int32)
        self.arrays = _page_writer(len(self.arrays))(
            *self.arrays, jnp.asarray([new], jnp.int32),
            *(a[:, src] for a in self.arrays))
        self._refcount[new] = 1
        pt.pages[i] = new
        self.release(old)
        _memledger.note_kv(self)
        return True

    # -- KV-page migration (disaggregated serving) --------------------------

    def export_pages(self, seq_id: int):
        """Snapshot ``seq_id``'s pages into a self-describing, verifiable
        :class:`~hetu_tpu.serve.fleet.migrate.MigrationRecord` (payload +
        page order + length + per-page CRC32 + the PR 10 content
        fingerprint) and place an EXPORT HOLD on every page: a
        subsequent ``free()`` of the sequence keeps the pages off the
        free list until :meth:`ack_export` (the import landed) or
        :meth:`cancel_export` (the handoff was abandoned) settles the
        hold — closing the export/free race that would otherwise hand an
        in-flight migration's physical pages to a new sequence."""
        from hetu_tpu.serve.fleet.migrate import build_record
        self.require_kv("export_pages (a migration record)")
        pt = self._tables[seq_id]
        if seq_id in self._exports:
            raise ValueError(f"sequence {seq_id} already has an "
                             f"outstanding export")
        pages = list(pt.pages)
        idx = jnp.asarray(pages, jnp.int32)
        k = np.asarray(self.k[:, idx])   # (L, n_pages, page, H, D) copies
        v = np.asarray(self.v[:, idx])
        for p in pages:
            self._refcount[p] += 1       # the export hold
        self._exports[seq_id] = pages
        self._exported_pages += len(pages)
        _memledger.note_kv(self)
        return build_record(seq_id=seq_id, length=pt.length,
                            page_size=self.page_size, k_pages=k, v_pages=v)

    def _settle_export(self, seq_id: int) -> None:
        pages = self._exports.pop(seq_id, None)
        if pages is None:
            raise DoubleFree(f"export of sequence {seq_id} already "
                             f"settled (or never exported)")
        for p in pages:
            self.release(p)
        _memledger.note_kv(self)

    def ack_export(self, seq_id: int) -> None:
        """The importer admitted (or terminally resolved) the migrated
        sequence: drop the export hold; pages whose last reference this
        was return to the free list.  A second settle of the same export
        raises :exc:`DoubleFree` — the same named-at-the-bug contract as
        a double ``free``."""
        self._settle_export(seq_id)

    def cancel_export(self, seq_id: int) -> None:
        """The handoff was abandoned (every decode worker shed, or the
        exporter is shutting down): identical mechanics to
        :meth:`ack_export`, kept as its own name so call sites read as
        what happened."""
        self._settle_export(seq_id)

    def import_pages(self, record, *, seq_id=None, owner=None) -> PageTable:
        """Verify and admit a migrated sequence: re-check the record
        (``verify_record`` — torn payloads, per-page CRCs, the content
        fingerprint) and the pool geometry BEFORE allocating, then write
        the page payloads into freshly allocated private pages and set
        the table's ``length`` to the record's decode cursor.  Raises the
        named :exc:`~hetu_tpu.serve.fleet.migrate.MigrationIntegrityError`
        without side effects when anything disagrees — corrupt KV is
        never admitted."""
        from hetu_tpu.serve.fleet.migrate import (MigrationIntegrityError,
                                                  verify_record)
        self.require_kv("import_pages (a migration record)")
        verify_record(record)
        L, n, page, H, D = record.k_pages.shape
        mine = (self.num_layers, self.page_size, self.num_heads,
                self.head_dim)
        theirs = (L, page, H, D)
        if mine != theirs:
            raise MigrationIntegrityError(
                "geometry", f"record pages are (layers, page, heads, "
                            f"head_dim)={theirs}, this pool is {mine}")
        if str(record.dtype) != str(self.k.dtype):
            raise MigrationIntegrityError(
                "geometry", f"record dtype {record.dtype} != pool dtype "
                            f"{self.k.dtype}")
        if n * self.page_size > self.max_seq_len:
            raise MigrationIntegrityError(
                "geometry", f"{n} pages exceed this pool's max_seq_len "
                            f"{self.max_seq_len}")
        sid = record.seq_id if seq_id is None else seq_id
        pt = self.alloc(sid, n * self.page_size, owner=owner)
        self.arrays = _page_writer(2)(
            self.k, self.v, jnp.asarray(pt.pages, jnp.int32),
            jnp.asarray(record.k_pages), jnp.asarray(record.v_pages))
        pt.length = record.length
        self._imported_pages += n
        return pt

    def table(self, seq_id: int) -> PageTable:
        return self._tables[seq_id]

    def refcount(self, page: int) -> int:
        """Current reference count (0 == on the free list)."""
        return self._refcount.get(page, 0)

    def shared_pages_count(self) -> int:
        """Pages with more than one reference — the hot-path form of
        ``stats()['pages_shared']`` (no invariant sweep)."""
        return sum(1 for rc in self._refcount.values() if rc > 1)

    def owner(self, seq_id: int):
        """The tenant id ``alloc(owner=)`` tagged this sequence with
        (None when untagged)."""
        return self._owners.get(seq_id)

    def page_classes(self) -> dict:
        """The EXACT page partition the memory ledger attributes bytes
        by: every physical page lands in exactly one class —

        - ``scratch``: the reserved page 0;
        - ``export_hold``: under an unsettled export hold (an in-flight
          migration may still need the bytes);
        - ``shared_prefix``: aliased by several tables (refcount > 1) or
          held only by the prefix trie / a hold with no table (allocated
          but in no table);
        - ``active``: privately held by exactly one live sequence;
        - ``free``: on the free list.

        Counts sum to ``num_pages`` (asserted by ``_check_invariants``
        on every ``stats()`` call and by every ledger snapshot)."""
        held_by_table = set()
        for pt in self._tables.values():
            held_by_table.update(pt.pages)
        export_held = set()
        for pages in self._exports.values():
            export_held.update(pages)
        classes = {"active": 0, "shared_prefix": 0, "export_hold": 0,
                   "scratch": 1, "free": len(self._free)}
        for p, rc in self._refcount.items():
            if p in export_held:
                classes["export_hold"] += 1
            elif rc > 1 or p not in held_by_table:
                classes["shared_prefix"] += 1
            else:
                classes["active"] += 1
        return classes

    def pages_by_tenant(self) -> dict:
        """Table-page holds per owner (untagged sequences under ``"-"``),
        sorted by tenant.  A page aliased by two tenants' tables counts
        once per holder — this is the billing-shaped view, NOT the exact
        physical partition (that is :meth:`page_classes`)."""
        out: dict = {}
        for sid, pt in self._tables.items():
            t = self._owners.get(sid, "-")
            out[t] = out.get(t, 0) + len(pt.pages)
        return {t: out[t] for t in sorted(out)}

    def stats(self) -> dict:
        """The supported introspection surface: page classes, the
        refcount histogram, and the alloc/free balance — with the pool's
        accounting invariants ASSERTED on every call (a violation here is
        a double-free/leak caught at the scrape, not at the much-later
        wrong-answer)."""
        self._check_invariants()
        hist: dict = {}
        for rc in self._refcount.values():
            hist[rc] = hist.get(rc, 0) + 1
        shared = sum(1 for rc in self._refcount.values() if rc > 1)
        classes = self.page_classes()
        return {
            "pages_total": self.num_pages - 1,
            "pages_free": len(self._free),
            "pages_private": len(self._refcount) - shared,
            "pages_shared": shared,
            # the ledger's exact partition (classes sum to num_pages)
            # and the per-tenant table-page holds (PR 16 identity)
            "pages_by_class": classes,
            "pages_by_tenant": self.pages_by_tenant(),
            "refcount_histogram": {str(k): hist[k] for k in sorted(hist)},
            "sequences": len(self._tables),
            "allocs": self._allocs,
            "frees": self._frees,
            "page_size": self.page_size,
            # of a window group: ring slots a later page took again
            "pages_overwritten": self._overwritten,
            # KV-page migration accounting (disaggregated serving):
            # cumulative export/import totals plus the pages currently
            # pinned by an unsettled export hold
            "exported_pages": self._exported_pages,
            "imported_pages": self._imported_pages,
            "pages_export_held": sum(len(p)
                                     for p in self._exports.values()),
            "exports_outstanding": len(self._exports),
        }

    def _check_invariants(self) -> None:
        free = set(self._free)
        assert len(free) == len(self._free), \
            f"free list holds duplicates: {sorted(self._free)}"
        assert SCRATCH_PAGE not in free and \
            SCRATCH_PAGE not in self._refcount, "scratch page was allocated"
        overlap = free & set(self._refcount)
        assert not overlap, \
            f"pages {sorted(overlap)} are both free and refcounted"
        assert len(free) + len(self._refcount) == self.num_pages - 1, \
            (f"page accounting leak: {len(free)} free + "
             f"{len(self._refcount)} allocated != {self.num_pages - 1}")
        # every table reference AND export hold must be backed by at
        # least that many refs — the export/free-race invariant: a page
        # under an unsettled export hold can never be on the free list
        held: dict = {}
        for pt in self._tables.values():
            for p in pt.pages:
                held[p] = held.get(p, 0) + 1
        for pages in self._exports.values():
            for p in pages:
                held[p] = held.get(p, 0) + 1
        for p, n in held.items():
            assert self._refcount.get(p, 0) >= n, \
                (f"page {p} referenced by {n} table entries / export "
                 f"holds but refcount is {self._refcount.get(p, 0)}")
        assert self._allocs - self._frees == len(self._tables), \
            (f"alloc/free imbalance: {self._allocs} allocs - "
             f"{self._frees} frees != {len(self._tables)} live sequences")
        # the ledger partition must be exact: every physical page in
        # exactly one class (a page double-classed or dropped here would
        # make the memory ledger mis-attribute bytes silently)
        classes = self.page_classes()
        assert sum(classes.values()) == self.num_pages, \
            (f"page classes {classes} sum to {sum(classes.values())}, "
             f"not num_pages {self.num_pages}")
        assert not (set(self._owners) - set(self._tables)), \
            (f"owner tags for dead sequences: "
             f"{sorted(set(self._owners) - set(self._tables))}")

    def defrag(self) -> int:
        """Compact movable live pages into the lowest physical indices,
        moving the K/V rows along (one permutation gather per array) and
        rewriting the page tables.  Returns the number of pages moved.
        Call between steps — the arrays are replaced, so in-flight views
        are stale.  A permutation cannot be written in place: while it
        runs, the old and the new arrays are both alive, so it needs a
        second pool's worth of device memory.

        Pages are PINNED-BY-REFCOUNT: a page aliased by several tables
        (refcount > 1) or held only by the prefix trie or an unsettled
        export hold (allocated but in no table) stays at its physical
        index — moving it would require rewriting every alias
        atomically, and the trie's/export's references are not table
        entries this compactor can see.  Only single-reference,
        single-table pages move; the compaction target slots skip the
        pinned indices."""
        held_by_table = set()
        for pt in self._tables.values():
            held_by_table.update(pt.pages)
        pinned = {p for p, rc in self._refcount.items()
                  if rc > 1 or p not in held_by_table}
        movable = [p for pt in sorted(self._tables.values(),
                                      key=lambda t: t.seq_id)
                   for p in pt.pages if p not in pinned]
        # target layout: scratch, then (skipping pinned slots) movable
        # pages packed in (seq, pos) order, then the free pages
        slots = [s for s in range(1, self.num_pages) if s not in pinned]
        mapping = dict(zip(movable, slots))
        moved = sum(1 for old, new in mapping.items() if old != new)
        if moved == 0:
            return 0
        perm = list(range(self.num_pages))  # perm[new] = old
        for old, new in mapping.items():
            perm[new] = old
        moved_from = set(mapping)  # old indices already placed
        spare = iter(p for p in slots if p not in moved_from)
        for new in slots[len(movable):]:
            perm[new] = next(spare)
        perm_arr = jnp.asarray(perm, jnp.int32)
        self.arrays = tuple(jnp.take(a, perm_arr, axis=1)
                            for a in self.arrays)
        for pt in self._tables.values():
            pt.pages = [mapping.get(p, p) for p in pt.pages]
        self._refcount = {mapping.get(p, p): rc
                          for p, rc in self._refcount.items()}
        self._free = sorted(slots[len(movable):])
        _memledger.note_kv(self)
        return moved

    # -- the static-shape bridge -------------------------------------------

    def gather_indices(self, seq_ids) -> jnp.ndarray:
        """(batch, pages_per_seq) int32 page-table matrix for the jitted
        step, padded with the scratch page.  ``None`` entries (idle slots)
        become all-scratch rows."""
        rows = []
        for sid in seq_ids:
            pages = [] if sid is None else self._tables[sid].pages
            rows.append(pages + [SCRATCH_PAGE] *
                        (self.pages_per_seq - len(pages)))
        # through numpy: jax converts nested lists an element at a time
        # (3.4 ms for 64 x 108 entries where this takes 0.3, every tick)
        return jnp.asarray(np.asarray(rows, np.int32))

    def step(self, fn, model, *args):
        """Run one serving program ``fn(model, *arrays, *args) -> (out,
        *arrays)`` (for keys and values ``fn(model, k, v, *args) -> (out,
        k, v)``) that takes the pool donated, adopt the arrays it returns
        and hand back ``out``.  The arrays passed in are consumed by the
        call; nothing else may still hold them."""
        out, *arrays = fn(model, *self.arrays, *args)
        self.commit(*arrays)
        return out

    def commit(self, *arrays) -> None:
        """Adopt the updated arrays a jitted step returned: from here on
        they are the pool, and the ones the step was given are gone."""
        if len(arrays) != len(self.arrays):
            raise ValueError(f"the pool holds {len(self.arrays)} arrays, "
                             f"got {len(arrays)}")
        self.arrays = tuple(arrays)

    def utilization(self) -> dict:
        used = self.num_pages - 1 - len(self._free)
        return {"pages_total": self.num_pages - 1, "pages_used": used,
                "sequences": len(self._tables),
                "page_size": self.page_size}

    def cache_stats(self) -> dict:
        """The cache spec the pool was built from and the bytes it holds
        (``stats()["cache"]`` of the engine)."""
        return {**self.spec.describe(), "pool_bytes": self.nbytes,
                "pages": self.num_pages, "page_size": self.page_size,
                "pages_per_seq": self.pages_per_seq}


class GroupedKVCachePool:
    """One :class:`KVCachePool` a group of layers behind the one pool's
    interface (module docstring): a sequence is allocated, grown and freed
    in every group at once, ``arrays`` are the groups' arrays one after
    another, and :meth:`gather_indices` returns one page-table matrix a
    group.  ``num_pages`` maps a group's name to its pages (scratch page
    included).  The pool's own ``num_pages`` and ``free_pages`` count the
    pages of all groups that sequences can hold (plus one, as a single
    pool's ``num_pages`` counts its scratch page)."""

    def __init__(self, *, spec: GroupedCacheSpec, num_pages: dict,
                 page_size: int, max_seq_len: int):
        self.spec = spec
        self.page_size, self.max_seq_len = page_size, max_seq_len
        self.num_layers = spec.num_layers
        self.groups = {g.name: KVCachePool(
            spec=g, num_pages=int(num_pages[g.name]), page_size=page_size,
            max_seq_len=max_seq_len) for g in spec.groups}
        self._tables: dict = {}

    def _each(self):
        return self.groups.values()

    def by_group(self) -> dict:
        return self.groups

    # -- the arrays ---------------------------------------------------------

    @property
    def arrays(self) -> tuple:
        return tuple(a for g in self._each() for a in g.arrays)

    @property
    def nbytes(self) -> int:
        return sum(g.nbytes for g in self._each())

    def require_kv(self, what: str) -> None:
        raise UnsupportedCacheLayout(
            f"{what} reads one pool of keys and values; this model caches "
            f"{len(self.groups)} groups of layers "
            f"({', '.join(self.groups)}), each with pages and tables of "
            f"its own")

    @property
    def k(self):
        self.require_kv("pool.k")

    @property
    def v(self):
        self.require_kv("pool.v")

    def step(self, fn, model, *args):
        out, *arrays = fn(model, *self.arrays, *args)
        self.commit(*arrays)
        return out

    def commit(self, *arrays) -> None:
        if len(arrays) != len(self.arrays):
            raise ValueError(f"the pool holds {len(self.arrays)} arrays, "
                             f"got {len(arrays)}")
        i = 0
        for g in self._each():
            n = len(g.arrays)
            g.commit(*arrays[i:i + n])
            i += n

    # -- allocator ----------------------------------------------------------

    @property
    def num_pages(self) -> int:
        return 1 + sum(g.num_pages - 1 for g in self._each())

    @property
    def free_pages(self) -> int:
        return sum(g.free_pages for g in self._each())

    @property
    def live_sequences(self) -> int:
        return len(self._tables)

    def pages_needed(self, n_tokens: int) -> int:
        return sum(self.needed_by_group(n_tokens))

    def free_by_group(self) -> list:
        return [g.free_pages for g in self._each()]

    def needed_by_group(self, n_tokens: int) -> list:
        return [g.pages_needed(n_tokens) for g in self._each()]

    def can_admit(self, n_tokens: int) -> bool:
        return all(g.can_admit(n_tokens) for g in self._each())

    def table_shapes(self, rows: int):
        return tuple(g.table_shapes(rows) for g in self._each())

    def _short(self, seq_id, n_tokens: int):
        """Raises :exc:`OutOfPages`, before anything is taken, if a group
        cannot cover ``n_tokens`` of the sequence."""
        for name, g in self.groups.items():
            held = (len(g.table(seq_id).pages) if seq_id in self._tables
                    else 0)
            need = g.pages_needed(n_tokens) - held
            if need > g.free_pages:
                raise OutOfPages(f"group {name!r}: need {need} pages, "
                                 f"{g.free_pages} free")

    def alloc(self, seq_id: int, n_tokens: int, shared_pages=(),
              owner=None) -> GroupedPageTable:
        if seq_id in self._tables:
            raise ValueError(f"sequence {seq_id} already allocated")
        if tuple(shared_pages):
            self.require_kv("alloc(shared_pages=) (prefix sharing)")
        if n_tokens > self.max_seq_len:
            raise ValueError(f"sequence of {n_tokens} tokens exceeds "
                             f"max_seq_len {self.max_seq_len}")
        self._short(seq_id, n_tokens)
        pt = GroupedPageTable(seq_id, {
            name: g.alloc(seq_id, n_tokens, owner=owner)
            for name, g in self.groups.items()})
        self._tables[seq_id] = pt
        return pt

    def ensure(self, seq_id: int, n_tokens: int) -> GroupedPageTable:
        if n_tokens > self.max_seq_len:
            raise ValueError(f"sequence {seq_id} would exceed max_seq_len "
                             f"{self.max_seq_len}")
        self._short(seq_id, n_tokens)
        for g in self._each():
            g.ensure(seq_id, n_tokens)
        return self._tables[seq_id]

    def free(self, seq_id: int) -> None:
        if self._tables.pop(seq_id, None) is None:
            raise DoubleFree(f"sequence {seq_id} already freed (or never "
                             f"allocated)")
        for g in self._each():
            g.free(seq_id)

    def table(self, seq_id: int) -> GroupedPageTable:
        return self._tables[seq_id]

    def owner(self, seq_id: int):
        return next(iter(self._each())).owner(seq_id)

    def shared_pages_count(self) -> int:
        return 0

    def defrag(self) -> int:
        """Each group compacted on its own (a ring's slots are table
        entries like any other)."""
        return sum(g.defrag() for g in self._each())

    def gather_indices(self, seq_ids) -> tuple:
        """One ``(batch, pages_per_seq of the group)`` matrix a group, in
        the groups' order; a window group's in ring-slot order."""
        return tuple(g.gather_indices(seq_ids) for g in self._each())

    # -- what reads one pool of keys and values -----------------------------

    def copy_on_write(self, seq_id: int, token_index: int) -> bool:
        self.require_kv("copy_on_write")

    def export_pages(self, seq_id: int):
        self.require_kv("export_pages (a migration record)")

    def import_pages(self, record, *, seq_id=None, owner=None):
        self.require_kv("import_pages (a migration record)")

    def retain(self, page: int) -> None:
        self.require_kv("retain (the prefix trie's hold)")

    # -- introspection ------------------------------------------------------

    def page_classes(self) -> dict:
        out: dict = {}
        for g in self._each():
            for k, n in g.page_classes().items():
                out[k] = out.get(k, 0) + n
        return out

    def pages_by_tenant(self) -> dict:
        out: dict = {}
        for g in self._each():
            for t, n in g.pages_by_tenant().items():
                out[t] = out.get(t, 0) + n
        return {t: out[t] for t in sorted(out)}

    def stats(self) -> dict:
        """Every group's :meth:`KVCachePool.stats` under ``groups`` (each
        asserts its own invariants), the sums of what adds up at the top
        level under the single pool's names, and the one invariant of the
        whole: every group holds the same sequences."""
        groups = {name: g.stats() for name, g in self.groups.items()}
        for name, g in self.groups.items():
            assert set(g._tables) == set(self._tables), \
                (f"group {name!r} holds sequences {sorted(g._tables)}, the "
                 f"pool {sorted(self._tables)}")
        summed = ("pages_total", "pages_free", "pages_private",
                  "pages_shared", "pages_overwritten", "exported_pages",
                  "imported_pages", "pages_export_held",
                  "exports_outstanding")
        first = next(iter(groups.values()))
        return {
            **{k: sum(s[k] for s in groups.values()) for k in summed},
            "pages_by_class": self.page_classes(),
            "pages_by_tenant": self.pages_by_tenant(),
            "refcount_histogram": {"1": sum(
                s["pages_private"] for s in groups.values())},
            "sequences": len(self._tables), "allocs": first["allocs"],
            "frees": first["frees"], "page_size": self.page_size,
            "groups": groups}

    def utilization(self) -> dict:
        groups = {name: g.utilization() for name, g in self.groups.items()}
        return {"pages_total": sum(u["pages_total"] for u in groups.values()),
                "pages_used": sum(u["pages_used"] for u in groups.values()),
                "sequences": len(self._tables), "page_size": self.page_size,
                "groups": groups}

    def cache_stats(self) -> dict:
        return {"groups": {name: g.cache_stats()
                           for name, g in self.groups.items()},
                "layers": self.num_layers, "pool_bytes": self.nbytes,
                "pages": self.num_pages, "page_size": self.page_size}


def make_pool(spec, *, num_slots: int, page_size: int, max_seq_len: int,
              num_pages=None):
    """The pool a model's ``cache_spec()`` asks for.  ``num_pages`` is a
    number for one group, a mapping from the groups' names for several, or
    ``None``: every slot's whole allocation and the scratch page, a group
    (``1 + num_slots * pages a sequence holds at most``), so that nothing is
    overcommitted."""
    def pages_of(g):
        given = (num_pages.get(g.name) if isinstance(num_pages, dict)
                 else num_pages)
        if given is not None:
            return int(given)
        return 1 + num_slots * g.pages_per_seq(page_size, max_seq_len)

    if isinstance(spec, GroupedCacheSpec):
        if num_pages is not None and not isinstance(num_pages, dict):
            raise ValueError(
                f"a model with groups {[g.name for g in spec.groups]} takes "
                f"num_pages as a mapping from those names, got {num_pages!r}")
        return GroupedKVCachePool(
            spec=spec, num_pages={g.name: pages_of(g) for g in spec.groups},
            page_size=page_size, max_seq_len=max_seq_len)
    return KVCachePool(spec=spec, num_pages=pages_of(spec),
                       page_size=page_size, max_seq_len=max_seq_len)

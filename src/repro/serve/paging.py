"""Paged KV-cache pool: prefix sharing, preemption, admission control
(DESIGN.md §15).

The contiguous ``SlotKVPool`` (DESIGN.md §11.1) commits
``n_slots x max_len`` self-KV plus ``n_slots x n_frames`` cross-KV up
front: short utterances pay for the longest, identical utterances (hot
audio preambles) duplicate their cross-KV wholesale, and the scheduler can
never admit more requests than physical slots. This module replaces that
with vLLM-style paging restated under the repo's zero-retrace discipline
(DESIGN.md §10): all KV lives in ONE fixed-shape page arena per kind
(self/cross), each slot reaches its pages through a per-slot int32 block
table gathered inside the jitted step (``attention.PagedKVCache``), and
every admission/eviction/preemption is a host-side table edit plus at most
one pre-traced splice — the compiled decode step sees one shape forever.
The splices (``paged_insert``, ``paged_attach``, ``paged_copy_page``) are
pure functions; the pool's jits donate the state, so XLA writes a
request's pages in place instead of copying both arenas first, and a
state handed to a splice must not be read again.

Pieces (DESIGN.md §15.1-§15.5):

  ``PageAllocator``     refcounted physical pages, host side. Page 0 is
                        reserved as the trash page free slots write/read
                        through; per-shard free ranges give shard-aware
                        placement under a serving mesh.
  ``PagedKVPool``       the two arenas + block tables + allocators.
                        Prefix sharing: identical padded utterances hash
                        to the same cross-KV page list (whole-utterance
                        identity — whisper's encoder is bidirectional, so
                        a *partial* mel prefix does not determine any
                        cross-KV prefix; token-prefix sharing for LM
                        families plugs in through the same refcount +
                        ``ensure_private`` copy-on-write machinery, which
                        is why self pages carry refcounts at all).
  ``PagedScheduler``    ``ContinuousBatchingScheduler`` with admission
                        control against pages instead of slots: logical
                        slots oversubscribe the arena, a pre-step capacity
                        pass allocates page-boundary crossings (CoW-
                        splitting shared pages before any write), and
                        exhaustion preempts the victim losing the fewest
                        pages — preempt-and-recompute replays its tokens
                        through the batch-1 decode (greedy decode is
                        deterministic, so the replay is token-exact), with
                        the replay's plan commits and wall time attributed
                        to that request so PDP stays exact-by-steps-lived
                        (DESIGN.md §11.3).

Gates: ``benchmarks/paged_serving.py`` holds the paged path to token-exact
parity with the contiguous scheduler, zero step retraces after warmup, and
>=2x admitted-requests-per-GB on a shared-prefix trace (DESIGN.md §15.4).
"""
from __future__ import annotations

import hashlib
import time
from bisect import insort
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.models import model as model_lib
from repro.models.model import ServeState
from repro.models.whisper import WhisperPagedDecodeState
from repro.serve.scheduler import (ContinuousBatchingScheduler, _ActiveSlot,
                                   _QueuedRequest)
from repro.sharding import rules as shard_rules


class PagesExhausted(RuntimeError):
    """Arena out of free pages — the scheduler's cue to preempt."""


class PageAllocator:
    """Refcounted physical-page allocator (host side, DESIGN.md §15.1).

    The first ``reserve`` pages are never handed out — page 0 is the trash
    page every freed slot's table row points back to, so garbage rows of
    the fixed-shape batch write into memory nobody owns. ``n_shards``
    partitions the allocatable pages into contiguous ranges so a sharded
    arena can prefer device-local pages (DESIGN.md §15.3); allocation
    picks the preferred shard when it has a free page, else the shard with
    the most free pages (ties -> lowest), lowest page index within it —
    deterministic for a deterministic op sequence.

    Invariants (property-tested in tests/test_paging_properties.py):
    ``alloc`` never returns a page with refcount > 0; free + allocated
    always equals the allocatable arena size; ``release`` to refcount 0
    returns the page to the free list.
    """

    def __init__(self, n_pages: int, n_shards: int = 1, reserve: int = 1):
        if n_pages <= reserve:
            raise ValueError(f"arena of {n_pages} pages leaves nothing to "
                             f"allocate past the {reserve} reserved")
        if n_shards < 1 or n_pages % n_shards:
            n_shards = 1
        self.n_pages = n_pages
        self.reserve = reserve
        self.n_shards = n_shards
        self._shard_size = n_pages // n_shards
        self.refcount = np.zeros(n_pages, np.int64)
        self._free: List[List[int]] = [
            [p for p in range(s * self._shard_size,
                              (s + 1) * self._shard_size) if p >= reserve]
            for s in range(n_shards)]
        self._n_free = n_pages - reserve

    @property
    def n_allocatable(self) -> int:
        return self.n_pages - self.reserve

    @property
    def n_free(self) -> int:
        return self._n_free

    @property
    def n_allocated(self) -> int:
        return self.n_allocatable - self._n_free

    def page_shard(self, page: int) -> int:
        return page // self._shard_size

    def can_alloc(self, n: int) -> bool:
        return self._n_free >= n

    def alloc(self, prefer: Optional[int] = None) -> int:
        """Claim a free page at refcount 1; raises ``PagesExhausted`` when
        the arena is dry (never resizes — fixed shapes are the law)."""
        if self._n_free == 0:
            raise PagesExhausted(
                f"all {self.n_allocatable} pages allocated")
        if prefer is not None and self._free[prefer % self.n_shards]:
            shard = prefer % self.n_shards
        else:
            shard = max(range(self.n_shards),
                        key=lambda s: (len(self._free[s]), -s))
        page = self._free[shard].pop(0)
        assert self.refcount[page] == 0
        self.refcount[page] = 1
        self._n_free -= 1
        return page

    def retain(self, page: int) -> None:
        """Add a reference (prefix sharing / page aliasing)."""
        if self.refcount[page] <= 0:
            raise ValueError(f"retain of unallocated page {page}")
        self.refcount[page] += 1

    def release(self, page: int) -> bool:
        """Drop a reference; at refcount 0 the page returns to its shard's
        free list immediately (a just-evicted request's pages are
        admissible in the same scheduler pass — ISSUE 7 satellite).
        Returns True when the page was actually freed."""
        if self.refcount[page] <= 0:
            raise ValueError(f"release of unallocated page {page}")
        self.refcount[page] -= 1
        if self.refcount[page]:
            return False
        insort(self._free[self.page_shard(page)], page)
        self._n_free += 1
        return True


# ---------------------------------------------------------------------------
# Jitted arena ops (module-level: shared across pools of one geometry)
# ---------------------------------------------------------------------------
def paged_insert(state: ServeState, slot, bt_row, ct_row, req: ServeState,
                 *, write_cross: bool) -> ServeState:
    """Splice a batch-1 contiguous prefill/replay state into the arenas at
    ``slot``'s pages (DESIGN.md §15.2). Self-KV copies page-sized chunks
    of the request's contiguous cache into ``bt_row``'s physical pages
    (rows past the allocation point at trash page 0 absorb the copy
    harmlessly); ``write_cross`` statically gates the cross-KV copy —
    False on a prefix-share hit, whose pages are already populated."""
    ls = state.layer_states
    wd = req.layer_states
    sk, sv = ls.self_k, ls.self_v
    ps = sk.shape[2]
    src_k, src_v = wd.self_kv.k, wd.self_kv.v          # (R, 1, S, Hkv, hd)
    s_req = src_k.shape[2]
    for lp in range(min(bt_row.shape[0], -(-s_req // ps))):
        end = min((lp + 1) * ps, s_req)
        ck_ = src_k[:, 0, lp * ps:end]
        cv_ = src_v[:, 0, lp * ps:end]
        if end - lp * ps < ps:
            pad = ((0, 0), (0, ps - (end - lp * ps)), (0, 0), (0, 0))
            ck_, cv_ = jnp.pad(ck_, pad), jnp.pad(cv_, pad)
        sk = sk.at[:, bt_row[lp]].set(ck_.astype(sk.dtype))
        sv = sv.at[:, bt_row[lp]].set(cv_.astype(sv.dtype))
    xk, xv = ls.cross_k, ls.cross_v
    if write_cross:
        cps = xk.shape[2]
        csrc_k, csrc_v = wd.cross_kv                   # (R, 1, F, Hkv, hd)
        for cp in range(ct_row.shape[0]):
            xk = xk.at[:, ct_row[cp]].set(
                csrc_k[:, 0, cp * cps:(cp + 1) * cps].astype(xk.dtype))
            xv = xv.at[:, ct_row[cp]].set(
                csrc_v[:, 0, cp * cps:(cp + 1) * cps].astype(xv.dtype))
    lsrc = wd.self_kv.length
    l0 = lsrc[0] if lsrc.ndim else lsrc                # stacked (R,) -> ()
    length = ls.length.at[:, slot].set(l0.astype(ls.length.dtype))
    step = state.step.at[slot].set(req.step.astype(state.step.dtype))
    return ServeState(ls._replace(self_k=sk, self_v=sv, cross_k=xk,
                                  cross_v=xv, length=length), step)


def paged_attach(state: ServeState, slot) -> ServeState:
    """Zero ``slot``'s length/step counters — the whole device-side cost
    of admitting a prefix-share hit (its cross pages already hold the
    right values; its first self page starts empty)."""
    ls = state.layer_states
    return ServeState(ls._replace(length=ls.length.at[:, slot].set(0)),
                      state.step.at[slot].set(0))


def paged_copy_page(state: ServeState, src, dst) -> ServeState:
    """Copy-on-write split: duplicate self-KV physical page ``src`` into
    ``dst`` (all layers, K and V) so the writer's table can repoint to a
    private page while every other referent keeps reading ``src``."""
    ls = state.layer_states
    return ServeState(ls._replace(
        self_k=ls.self_k.at[:, dst].set(ls.self_k[:, src]),
        self_v=ls.self_v.at[:, dst].set(ls.self_v[:, src])), state.step)


# every splice jit donates the state (argument 0): the arenas update in
# place
_INSERT_JIT = jax.jit(paged_insert, static_argnames=("write_cross",),
                      donate_argnums=0)
_ATTACH_JIT = jax.jit(paged_attach, donate_argnums=0)
_COPY_JIT = jax.jit(paged_copy_page, donate_argnums=0)


def _mel_digest(payload: np.ndarray) -> str:
    """Identity hash of one padded utterance — the prefix-sharing key
    (whole-utterance: see the module docstring on why audio cannot share
    partial prefixes)."""
    return hashlib.blake2b(np.ascontiguousarray(payload).tobytes(),
                           digest_size=16).hexdigest()


class PagedKVPool:
    """Fixed-shape paged arenas + host-side page/table bookkeeping
    (DESIGN.md §15.2).

    Self-KV arena: ``(R, n_pages, page_size, Hkv, hd)`` x2, one block
    table row of ``max_pages = ceil(max_len/page_size)`` logical pages per
    slot. Cross-KV arena: ``(R, n_cross_pages, cross_page_size, ...)`` x2
    with ``n_frames/cross_page_size`` pages per distinct utterance —
    identical utterances share one page list by content hash. Block
    tables are host-authoritative numpy; ``sync()`` uploads them (dirty-
    flagged) before each decode step, so evictions and preemptions are
    pure host edits. Under a mesh the arenas shard their page axis and the
    tables their slot axis per ``sharding/rules.paged_state_specs``
    (DESIGN.md §15.3), and every splice jit pins ``out_shardings``.

    Only the audio family is implemented: whisper is the paper's workload
    and the only family with the fixed per-request cross-KV block that
    makes whole-utterance sharing pay; LM families keep the contiguous
    ``SlotKVPool`` until a token-prefix front-end lands on the same
    allocator/CoW machinery (the §15 generalization hook).
    """

    def __init__(self, cfg, params, n_slots: int, max_len: int,
                 n_frames: Optional[int] = None, *, page_size: int = 8,
                 n_pages: Optional[int] = None,
                 cross_page_size: Optional[int] = None,
                 n_cross_pages: Optional[int] = None, mesh=None):
        model_lib.family_of(cfg).require("paged")
        if n_frames is None:
            raise ValueError("audio paged pool needs a fixed n_frames "
                             "capacity (utterances are padded to it)")
        if page_size < 1 or page_size & (page_size - 1):
            raise ValueError(f"page_size must be a power of two, got "
                             f"{page_size}")
        cross_page_size = (n_frames if cross_page_size is None
                           else cross_page_size)
        if n_frames % cross_page_size:
            # an inexact split would leave a ragged tail page whose
            # gathered view shifts cross positions — parity would break
            raise ValueError(f"cross_page_size {cross_page_size} must "
                             f"divide n_frames {n_frames}")
        self.cfg = cfg
        self.n_slots = n_slots
        self.max_len = max_len
        self.n_frames = n_frames
        self.page_size = page_size
        self.cross_page_size = cross_page_size
        self.max_pages = -(-max_len // page_size)
        self.n_cross_per_req = n_frames // cross_page_size
        if n_pages is None:
            n_pages = 1 + n_slots * self.max_pages     # no oversubscription
        if n_cross_pages is None:
            n_cross_pages = 1 + n_slots * self.n_cross_per_req
        self.n_pages = n_pages
        self.n_cross_pages = n_cross_pages
        self.mesh = mesh

        r, hkv, hd = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
        dtype = model_lib._dtype(cfg)
        ls = WhisperPagedDecodeState(
            self_k=jnp.zeros((r, n_pages, page_size, hkv, hd), dtype),
            self_v=jnp.zeros((r, n_pages, page_size, hkv, hd), dtype),
            cross_k=jnp.zeros((r, n_cross_pages, cross_page_size, hkv, hd),
                              dtype),
            cross_v=jnp.zeros((r, n_cross_pages, cross_page_size, hkv, hd),
                              dtype),
            block_table=jnp.zeros((n_slots, self.max_pages), jnp.int32),
            cross_table=jnp.zeros((n_slots, self.n_cross_per_req),
                                  jnp.int32),
            length=jnp.zeros((r, n_slots), jnp.int32))
        self.state = ServeState(ls, jnp.zeros((n_slots,), jnp.int32))
        itemsize = jnp.zeros((), dtype).dtype.itemsize
        self.page_bytes = 2 * r * page_size * hkv * hd * itemsize
        self.cross_page_bytes = 2 * r * cross_page_size * hkv * hd * itemsize

        # slot + page shard geometry (DESIGN.md §15.3)
        self.n_shards = 1
        page_shards = cross_shards = 1
        self._insert_jit, self._attach_jit = _INSERT_JIT, _ATTACH_JIT
        self._copy_jit = _COPY_JIT
        self._table_shardings = None
        if mesh is not None:
            specs = shard_rules.paged_state_specs(self.state, mesh)
            shardings = shard_rules.named(mesh, specs)
            self.state = jax.device_put(self.state, shardings)
            self._insert_jit = jax.jit(paged_insert, out_shardings=shardings,
                                       static_argnames=("write_cross",),
                                       donate_argnums=0)
            self._attach_jit = jax.jit(paged_attach, out_shardings=shardings,
                                       donate_argnums=0)
            self._copy_jit = jax.jit(paged_copy_page, out_shardings=shardings,
                                     donate_argnums=0)
            ls_sh = shardings.layer_states
            self._table_shardings = (ls_sh.block_table, ls_sh.cross_table)
            dsize = (mesh.shape["data"] if "data" in mesh.axis_names else 1)
            if dsize > 1 and n_slots % dsize == 0:
                self.n_shards = dsize
            if dsize > 1 and n_pages % dsize == 0:
                page_shards = dsize
            if dsize > 1 and n_cross_pages % dsize == 0:
                cross_shards = dsize
        self.shard_size = n_slots // self.n_shards

        # host-authoritative bookkeeping
        self._slots = PageAllocator(n_slots, self.n_shards, reserve=0)
        self.self_alloc = PageAllocator(n_pages, page_shards, reserve=1)
        self.cross_alloc = PageAllocator(n_cross_pages, cross_shards,
                                         reserve=1)
        self._bt = np.zeros((n_slots, self.max_pages), np.int32)
        self._ct = np.zeros((n_slots, self.n_cross_per_req), np.int32)
        self._slot_pages: List[List[int]] = [[] for _ in range(n_slots)]
        self._slot_cross: List[Optional[Tuple[str, List[int]]]] = (
            [None] * n_slots)
        self._shared: Dict[str, List[int]] = {}
        self._dirty = False
        # nullable telemetry (DESIGN.md §16.2): the owning PagedScheduler
        # hands down its handle so page-level events (cow_split) record
        self.telemetry = None

    @property
    def plan_geometry(self) -> Tuple[int, int, int, int]:
        """The page-shape component of this pool's plan keys — paged and
        contiguous programs never share a ``PlanCache`` entry."""
        return (self.page_size, self.n_pages, self.cross_page_size,
                self.n_cross_pages)

    # -- slot free list (same pick order as SlotKVPool.acquire) ---------
    @property
    def n_free(self) -> int:
        return self._slots.n_free

    def slot_shard(self, slot: int) -> int:
        return slot // self.shard_size

    def acquire(self) -> int:
        return self._slots.alloc()

    # -- admission-control surface (DESIGN.md §15.5) --------------------
    def has_shared(self, digest: str) -> bool:
        return digest in self._shared

    def can_alloc(self, n_self: int, n_cross: int) -> bool:
        return (self.self_alloc.can_alloc(n_self)
                and self.cross_alloc.can_alloc(n_cross))

    def slot_pages(self, slot: int) -> List[int]:
        return list(self._slot_pages[slot])

    def alloc_self_page(self, slot: int) -> int:
        """Append the next logical page for ``slot`` (shard-local when the
        arena is sharded). Raises ``PagesExhausted`` when dry."""
        page = self.self_alloc.alloc(prefer=self.slot_shard(slot))
        lp = len(self._slot_pages[slot])
        if lp >= self.max_pages:
            self.self_alloc.release(page)
            raise ValueError(f"slot {slot} already at max_pages")
        self._slot_pages[slot].append(page)
        self._bt[slot, lp] = page
        self._dirty = True
        return page

    def alias_self_page(self, dst: int, src: int, lp: int) -> int:
        """Map ``dst``'s next logical page onto ``src``'s physical page at
        ``lp`` (refcount++) — the token-prefix sharing hook; writes split
        via ``ensure_private`` before touching the shared page."""
        if len(self._slot_pages[dst]) != lp:
            raise ValueError("alias must extend dst's table contiguously")
        page = self._slot_pages[src][lp]
        self.self_alloc.retain(page)
        self._slot_pages[dst].append(page)
        self._bt[dst, lp] = page
        self._dirty = True
        return page

    def ensure_private(self, slot: int, lp: int) -> int:
        """Copy-on-write: if ``slot``'s page at logical index ``lp`` is
        shared (refcount > 1), copy it into a fresh private page and
        repoint only this slot's table — the shared page is never mutated
        (property-tested). No-op on already-private pages."""
        page = self._slot_pages[slot][lp]
        if self.self_alloc.refcount[page] <= 1:
            return page
        fresh = self.self_alloc.alloc(prefer=self.slot_shard(slot))
        self.state = self._copy_jit(self.state, page, fresh)
        self.self_alloc.release(page)
        self._slot_pages[slot][lp] = fresh
        self._bt[slot, lp] = fresh
        self._dirty = True
        if self.telemetry is not None:
            self.telemetry.instant("cow_split", slot=slot, lp=lp,
                                   src=int(page), dst=int(fresh))
            self.telemetry.inc("repro_cow_splits_total")
        return fresh

    def attach_shared(self, slot: int, digest: str) -> None:
        """Prefix-share hit: point ``slot``'s cross table at the existing
        page list (refcount++ each) — no encoder run, no copies."""
        pages = self._shared[digest]
        for p in pages:
            self.cross_alloc.retain(p)
        self._slot_cross[slot] = (digest, list(pages))
        self._ct[slot, :] = pages
        self._dirty = True

    def alloc_cross_pages(self, slot: int, digest: str) -> List[int]:
        """First sight of ``digest``: allocate its cross pages and publish
        them for sharing. Raises ``PagesExhausted`` when dry."""
        pages: List[int] = []
        try:
            for _ in range(self.n_cross_per_req):
                pages.append(self.cross_alloc.alloc(
                    prefer=self.slot_shard(slot)))
        except PagesExhausted:
            for p in pages:
                self.cross_alloc.release(p)
            raise
        self._shared[digest] = list(pages)
        self._slot_cross[slot] = (digest, list(pages))
        self._ct[slot, :] = pages
        self._dirty = True
        return pages

    def release(self, slot: int, reset: bool = False) -> None:
        """Evict ``slot``: every page reference returns to its allocator
        BEFORE this call returns, so the same scheduler pass can admit a
        queued request into the freed pages (ISSUE 7 satellite). The
        slot's table rows repoint to the trash page so its garbage decode
        rows stop referencing (and scatter-writing!) memory that may be
        reallocated — synced to device before the next step."""
        del reset                                      # row zeroing is the reset
        for p in self._slot_pages[slot]:
            self.self_alloc.release(p)
        self._slot_pages[slot] = []
        entry = self._slot_cross[slot]
        if entry is not None:
            digest, pages = entry
            for p in pages:
                self.cross_alloc.release(p)
            if self.cross_alloc.refcount[pages[0]] == 0:
                self._shared.pop(digest, None)
            self._slot_cross[slot] = None
        self._bt[slot, :] = 0
        self._ct[slot, :] = 0
        self._dirty = True
        self._slots.release(slot)

    def trim_self_pages(self, slot: int, n_keep: int) -> int:
        """Release ``slot``'s self pages past logical index ``n_keep - 1``
        — the paged half of the speculative rollback (DESIGN.md §17.4).
        A rejected verify suffix may have crossed into pages the pre-round
        capacity pass allocated; after the splice rewinds ``length``, any
        page whose first position ``lp * page_size`` is at or past the
        spliced length holds only dead entries, so it returns to the
        allocator here (trash-pointing the table row like ``release``).
        Shared (aliased) pages just drop a refcount. Returns the number of
        references released."""
        dropped = self._slot_pages[slot][n_keep:]
        if not dropped:
            return 0
        del self._slot_pages[slot][n_keep:]
        for p in dropped:
            self.self_alloc.release(p)
        self._bt[slot, n_keep:] = 0
        self._dirty = True
        return len(dropped)

    # -- device sync ----------------------------------------------------
    def sync(self) -> None:
        """Upload the host block tables when dirty — called once before
        each decode step, so any number of admissions/evictions between
        steps costs at most one table upload."""
        if not self._dirty:
            return
        bt, ct = jnp.asarray(self._bt), jnp.asarray(self._ct)
        if self._table_shardings is not None:
            bt = jax.device_put(bt, self._table_shardings[0])
            ct = jax.device_put(ct, self._table_shardings[1])
        ls = self.state.layer_states._replace(block_table=bt, cross_table=ct)
        self.state = ServeState(ls, self.state.step)
        self._dirty = False

    def insert(self, slot: int, req_state: ServeState,
               write_cross: bool = True) -> None:
        """Splice a batch-1 contiguous prefill/replay state into the
        arenas at ``slot``'s allocated pages, in place (jitted, the state
        donated; sharded pools keep their sharding via pinned
        out_shardings)."""
        self.state = self._insert_jit(
            self.state, slot, jnp.asarray(self._bt[slot]),
            jnp.asarray(self._ct[slot]), req_state, write_cross=write_cross)

    def splice_writes(self, req_state: ServeState) -> tuple:
        """The shapes ``insert`` of ``req_state`` writes on a prefix miss:
        the request's self-KV pages, its cross-KV pages and its counters
        (``paged_insert``'s updates). The rest of the arenas stays where
        it is (the jit donates the state)."""
        ls = self.state.layer_states
        s_req = req_state.layer_states.self_kv.k.shape[2]
        n_self = min(self.max_pages, -(-s_req // self.page_size))

        def pages(arena, n):
            return jax.ShapeDtypeStruct((arena.shape[0], n) + arena.shape[2:],
                                        arena.dtype)
        return (pages(ls.self_k, n_self), pages(ls.self_v, n_self),
                pages(ls.cross_k, self.n_cross_per_req),
                pages(ls.cross_v, self.n_cross_per_req),
                jax.ShapeDtypeStruct(ls.length.shape[:1], ls.length.dtype),
                jax.ShapeDtypeStruct((), self.state.step.dtype))

    def attach_reset(self, slot: int) -> None:
        """Device-side half of a share-hit admission: zero the slot's
        counters (its tables were set on the host)."""
        self.state = self._attach_jit(self.state, slot)

    # -- memory accounting (DESIGN.md §15.4) ----------------------------
    def committed_kv_bytes(self) -> int:
        return model_lib.state_kv_bytes(self.state)

    def used_kv_bytes(self, lengths=None) -> int:
        """Allocated pages x page bytes — exact by construction (the
        contiguous pool's length-proportional estimate becomes a count of
        real allocations here). ``lengths`` accepted for interface parity
        with ``SlotKVPool`` and ignored."""
        del lengths
        return (self.self_alloc.n_allocated * self.page_bytes
                + self.cross_alloc.n_allocated * self.cross_page_bytes)


# ---------------------------------------------------------------------------
# Scheduler
# ---------------------------------------------------------------------------
@dataclass
class _PreemptedRequest(_QueuedRequest):
    """A preempted request back at the head of the queue: carries its
    already-streamed tokens for the deterministic replay, and the wall
    time already attributed to it (PDP attribution survives preemption
    exact-by-steps-lived, DESIGN.md §11.3)."""
    tokens: List[int] = field(default_factory=list)
    prefill_s: float = 0.0
    decode_s: float = 0.0
    # lifecycle carry-through (DESIGN.md §16.1): queue wait accumulates
    # across preemption rounds (requeue_t is the wait base for THIS round;
    # submit_t stays the original submit for TTFT), TTFT survives as-is
    queue_wait_s: float = 0.0
    ttft_s: float = 0.0
    requeue_t: float = 0.0


class PagedScheduler(ContinuousBatchingScheduler):
    """Continuous batching over a ``PagedKVPool`` (DESIGN.md §15.5).

    Inherits the whole decode/evict/attribution loop — the jitted step is
    the engine's same ``step_fn`` at pool width, just traced over the
    paged state (its plan key carries the page geometry, so paged and
    contiguous programs never share ``PlanCache`` entries). What changes:

      admission  gates on free PAGES, not free slots: a logical slot is
                 admitted only when its first self page plus (on a prefix
                 miss) its cross pages fit the arenas. A prefix HIT skips
                 the encoder entirely and attaches the shared pages.
      pre-step   slots crossing a page boundary get their next page
                 allocated (CoW-splitting shared pages first); exhaustion
                 preempts the active slot losing the fewest pages —
                 requeued at the FRONT with its tokens for replay.
      evict      pages return to the allocators before the next admit
                 pass, so an EOS mid-burst immediately admits the queue
                 head (regression-tested).
    """

    def __init__(self, engine, n_slots: int = 4,
                 n_frames: Optional[int] = None, *, page_size: int = 8,
                 n_pages: Optional[int] = None,
                 cross_page_size: Optional[int] = None,
                 n_cross_pages: Optional[int] = None):
        self._page_cfg = dict(page_size=page_size, n_pages=n_pages,
                              cross_page_size=cross_page_size,
                              n_cross_pages=n_cross_pages)
        super().__init__(engine, n_slots=n_slots, n_frames=n_frames)
        self.pool.telemetry = self.telemetry
        self._kv_gauge_state = None
        self.preemptions = 0
        self.shared_hits = 0
        # padded payloads of in-flight requests, kept for the replay a
        # preemption may later need; dropped when the request finishes
        self._payloads: Dict[int, np.ndarray] = {}

    def _make_pool(self):
        eng = self.engine
        return PagedKVPool(eng.cfg, eng._serve_params, self.n_slots,
                           eng.max_len, n_frames=self.n_frames,
                           mesh=eng.mesh, **self._page_cfg)

    # -- admission ------------------------------------------------------
    def admit(self) -> List[int]:
        admitted = []
        pool = self.pool
        tele = self.telemetry
        with obs.maybe_span(tele, "admit", cat="sched"):
            while self.queue and pool.n_free:
                req = self.queue[0]
                digest = _mel_digest(req.payload)
                replay = isinstance(req, _PreemptedRequest)
                ntok = len(req.tokens) if replay else 0
                need_self = min(ntok // pool.page_size + 1, pool.max_pages)
                shared = pool.has_shared(digest)
                need_cross = 0 if shared else pool.n_cross_per_req
                if not pool.can_alloc(need_self, need_cross):
                    if not self._active:
                        raise RuntimeError(
                            f"arena too small: request {req.rid} needs "
                            f"{need_self} self + {need_cross} cross pages "
                            f"with nothing left to preempt "
                            f"(free: {pool.self_alloc.n_free}/"
                            f"{pool.cross_alloc.n_free})")
                    break                                  # wait for evictions
                self.queue.popleft()
                # queue wait accumulates across preemption rounds: a replayed
                # request's base is its requeue time, not the original submit
                wait_base = req.requeue_t if replay else req.submit_t
                queue_wait = (req.queue_wait_s if replay else 0.0) + (
                    time.perf_counter() - wait_base if wait_base else 0.0)
                if tele is not None:
                    tele.end(req.rid, "queued", wait_s=queue_wait)
                    tele.observe("repro_queue_wait_seconds", queue_wait)
                slot = pool.acquire()
                tokens = list(req.tokens) if replay else []
                decode_s = 0.0
                if shared and not replay:
                    # prefix hit: no encoder, no prefill — attach the shared
                    # cross pages and zero the slot's counters. No ledger
                    # commit either: no GEMM ran, so attributing plan work
                    # here would break the PDP invariant. The ledger span's
                    # zero FLOP delta is the checkable form of that claim.
                    self.shared_hits += 1
                    if tele is not None:
                        tele.instant("prefix_hit", rid=req.rid)
                        tele.inc("repro_prefix_hits_total")
                    with obs.maybe_span(tele, "attach", cat="lifecycle",
                                        track=obs.request_track(req.rid),
                                        rid=req.rid, ledger=True):
                        t0 = time.perf_counter()
                        pool.attach_shared(slot, digest)
                        for _ in range(need_self):
                            pool.alloc_self_page(slot)
                        pool.attach_reset(slot)
                        prefill_s = time.perf_counter() - t0
                        self._busy_s += prefill_s
                    first = req.sot_id
                else:
                    state, first, prefill_s = self._prefill(req)
                    if shared:
                        pool.attach_shared(slot, digest)
                    else:
                        pool.alloc_cross_pages(slot, digest)
                    for _ in range(need_self):
                        pool.alloc_self_page(slot)
                    if tokens:
                        state, decode_s = self._replay(state, req)
                        first = tokens[-1]
                    with obs.maybe_span(tele, "splice", cat="lifecycle",
                                        track=obs.request_track(req.rid),
                                        rid=req.rid):
                        pool.insert(slot, state, write_cross=not shared)
                active = _ActiveSlot(
                    rid=req.rid, max_new=req.max_new, tokens=tokens,
                    steps=ntok,
                    prefill_s=prefill_s + (req.prefill_s if replay else 0.0),
                    decode_s=decode_s + (req.decode_s if replay else 0.0),
                    submit_t=req.submit_t, queue_wait_s=queue_wait,
                    ttft_s=req.ttft_s if replay else 0.0)
                if tele is not None:
                    tele.begin(req.rid, "decode")
                self._tokens = self._tokens.at[slot, 0].set(int(first))
                self._active[slot] = active
                admitted.append(req.rid)
        if admitted:
            self._note_kv_usage()
        return admitted

    def _replay(self, state: ServeState, req: _PreemptedRequest):
        """Preempt-and-recompute (DESIGN.md §15.5): rebuild the evicted
        request's self-KV by feeding its SOT + all-but-last streamed
        tokens through the batch-1 contiguous decode. Greedy decode is
        deterministic, so the rebuilt state continues token-exactly; the
        replay's wall time and its per-step plan commits land on THIS
        request, keeping PDP attribution exact-by-steps-lived."""
        tele = self.telemetry
        inputs = [req.sot_id] + req.tokens[:-1]
        with obs.maybe_span(tele, "replay", cat="lifecycle",
                            track=obs.request_track(req.rid), rid=req.rid,
                            ledger=True, args={"tokens": len(inputs)}):
            t0 = time.perf_counter()
            state = jax.block_until_ready(
                self.engine.replay(state, inputs, self.n_frames))
            replay_s = time.perf_counter() - t0
            self._busy_s += replay_s
        if tele is not None:
            tele.instant("replay", rid=req.rid, tokens=len(inputs))
            tele.inc("repro_replays_total")
            tele.observe("repro_replay_seconds", replay_s)
        return state, replay_s

    # -- pre-step capacity pass (DESIGN.md §15.5) -----------------------
    def _pick_victim(self) -> int:
        """Preemption victim: the active slot losing the fewest pages
        (least recompute work thrown away), ties -> lowest slot."""
        return min(self._active,
                   key=lambda s: (len(self.pool._slot_pages[s]), s))

    def _preempt(self, slot: int) -> None:
        a = self._active.pop(slot)
        self.preemptions += 1
        tele = self.telemetry
        if tele is not None:
            tele.instant("preempt", rid=a.rid)
            tele.inc("repro_preemptions_total")
            tele.end(a.rid, "decode", preempted=True, steps=a.steps)
            tele.begin(a.rid, "queued")
        # FRONT of the queue: a preempted request outranks every waiter
        # (it already holds streamed-token obligations)
        # payload stays in _payloads: the request may be preempted again
        self.queue.appendleft(_PreemptedRequest(
            rid=a.rid, payload=self._payloads[a.rid], max_new=a.max_new,
            submit_t=a.submit_t, tokens=list(a.tokens),
            prefill_s=a.prefill_s, decode_s=a.decode_s,
            queue_wait_s=a.queue_wait_s, ttft_s=a.ttft_s,
            requeue_t=time.perf_counter()))
        self.pool.release(slot)

    def submit(self, payload, max_new: int = 32, sot_id: int = 1) -> int:
        rid = super().submit(payload, max_new=max_new, sot_id=sot_id)
        if self.queue and self.queue[-1].rid == rid:
            # keep the padded payload for preempt-and-recompute
            self._payloads[rid] = self.queue[-1].payload
        return rid

    def _page_capacity_pass(self, w: int = 1) -> None:
        """Ensure every active slot owns private pages for the next ``w``
        write positions (``w == 1`` is the plain decode step; ``w == k+1``
        is a speculative round's verify window, which may straddle a page
        boundary — the crossing page allocates here, CoW-first, same as
        the single-step path). Exhaustion preempts the victim losing the
        fewest pages until the remaining actives fit."""
        pool = self.pool
        for slot in sorted(self._active):
            if slot not in self._active:
                continue                               # preempted below
            a = self._active[slot]
            lp0 = a.steps // pool.page_size            # first page written
            lp1 = min((a.steps + w - 1) // pool.page_size,
                      pool.max_pages - 1)              # writes clamp past cap
            for lp in range(lp0, lp1 + 1):
                while slot in self._active:
                    try:
                        if len(pool._slot_pages[slot]) <= lp:
                            pool.alloc_self_page(slot)
                            continue
                        pool.ensure_private(slot, lp)  # CoW before the write
                        break
                    except PagesExhausted:
                        self._preempt(self._pick_victim())
                if slot not in self._active:
                    break

    def decode_step(self):
        if not self._active:
            return []
        self._page_capacity_pass()
        self.pool.sync()
        events = super().decode_step()
        for ev in events:
            if ev.done:                   # finished: replay no longer possible
                self._payloads.pop(ev.rid, None)
        tele = self.telemetry
        if tele is not None:
            pool = self.pool
            g = (pool.self_alloc.n_free, pool.cross_alloc.n_free,
                 pool.self_alloc.n_allocated, pool.cross_alloc.n_allocated,
                 int(np.count_nonzero(pool.self_alloc.refcount > 1)),
                 int(np.count_nonzero(pool.cross_alloc.refcount > 1)))
            if g != self._kv_gauge_state:  # page counts move on admit/
                self._kv_gauge_state = g   # evict, not every step
                tele.gauge("repro_kv_pages_free", g[0], kind="self")
                tele.gauge("repro_kv_pages_free", g[1], kind="cross")
                tele.gauge("repro_kv_pages_used", g[2], kind="self")
                tele.gauge("repro_kv_pages_used", g[3], kind="cross")
                tele.gauge("repro_kv_pages_shared", g[4], kind="self")
                tele.gauge("repro_kv_pages_shared", g[5], kind="cross")
        return events

"""Fixed-shape slot KV-cache pool for continuous batching (DESIGN.md §11.1).

The pool preallocates ONE slot-layout decode state of static width
``n_slots`` (and, for whisper, static frame capacity ``n_frames``) at
construction, and never reshapes it: admission and eviction are pure
``jax.lax.dynamic_update_*`` splices along the batch axis, so the jitted
decode ``step_fn`` of serve/engine.py keeps seeing one shape forever —
zero retraces across any admission/eviction schedule (the property
tests/test_scheduler.py regression-gates, in the style of
tests/test_plan.py).

Ops (pure functions; the pool jit-compiles each once per pool shape,
in shared module-level caches):

  slot_insert(pool, slot, req)  splice a single-request prefill state
                                (whisper encoder + cross-KV, or LM prompt
                                scan — standard layout, batch 1) into live
                                slot ``slot``; counters land as per-slot
                                vectors via ``model.slot_layout``.
  slot_reset(pool, slot)        zero the slot row (KV buffers + counters)
                                on eviction, bounding the free slot's
                                counter drift between occupants.

Free slots keep decoding garbage — that is the fixed-shape contract (the
batch always computes all ``n_slots`` rows; the paper's CGLA keeps its
lanes busy the same way) — and every insert overwrites the entire slot
row, so stale state can never leak into a new request.

The splices stay pure, but the pool's jits donate the pool (argument 0):
XLA aliases the output to the input and writes the one row in place,
instead of copying every leaf of the pool into a fresh output first. A
pool state handed to ``insert`` or ``release`` is deleted by the call, so
nothing may read it afterwards; the pool keeps the state it gets back.

Sharded pools (DESIGN.md §13): with a serving mesh attached, the slot
axis shards over the mesh's "data" axis (``model.slot_state_specs``) and
the pool becomes the data axis of sharded serving. The splice jits get
``out_shardings`` pinned to the pool's sharding, so admission/eviction
never un-shards the state and nothing is gathered to the host between
steps; ``acquire`` becomes shard-aware — it admits into the slot range of
the least-loaded device so active slots spread across the mesh.
"""
from __future__ import annotations

import bisect
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp

from repro.models import model as model_lib
from repro.models.model import ServeState
from repro.sharding import rules as shard_rules


def slot_insert(pool: ServeState, slot: jax.Array,
                req: ServeState) -> ServeState:
    """Pure slot splice: write single-request decode state ``req``
    (standard layout, batch 1) into row ``slot`` of the slot-layout
    ``pool``. jit-safe: ``slot`` may be traced; every leaf updates via
    ``jax.lax.dynamic_update_slice_in_dim`` on its batch axis
    (``model.slot_batch_axis``)."""
    req = model_lib.slot_layout(req, 1)

    def upd(p, r):
        return jax.lax.dynamic_update_slice_in_dim(
            p, r.astype(p.dtype), slot, axis=model_lib.slot_batch_axis(False))

    step = jax.lax.dynamic_update_slice_in_dim(
        pool.step, req.step.astype(pool.step.dtype), slot,
        axis=model_lib.slot_batch_axis(True))
    return ServeState(
        layer_states=jax.tree_util.tree_map(upd, pool.layer_states,
                                            req.layer_states),
        step=step)


def slot_reset(pool: ServeState, slot: jax.Array) -> ServeState:
    """Pure slot clear: zero row ``slot`` of every leaf (KV buffers,
    counters, step). Not required for correctness — ``slot_insert``
    overwrites the whole row — but it pins freed slots' per-slot counters
    back to 0 so an idle slot's position never drifts toward the cache
    horizon between occupants."""
    def zero(p):
        ax = model_lib.slot_batch_axis(False)
        shape = p.shape[:ax] + (1,) + p.shape[ax + 1:]
        return jax.lax.dynamic_update_slice_in_dim(
            p, jnp.zeros(shape, p.dtype), slot, axis=ax)

    step = jax.lax.dynamic_update_slice_in_dim(
        pool.step, jnp.zeros((1,), pool.step.dtype), slot,
        axis=model_lib.slot_batch_axis(True))
    return ServeState(
        layer_states=jax.tree_util.tree_map(zero, pool.layer_states),
        step=step)


# Module-level jits: shared across every pool instance, so repeatedly
# constructing schedulers (tests, benchmarks) re-traces only on a genuinely
# new pool shape. Both donate the pool, so the row is written in place.
_INSERT_JIT = jax.jit(slot_insert, donate_argnums=0)
_RESET_JIT = jax.jit(slot_reset, donate_argnums=0)


class SlotKVPool:
    """The preallocated slot pool + host-side free-slot bookkeeping.

    ``state`` is a slot-layout ``ServeState`` of static shape
    ``(n_slots, max_len, ...)`` built once at construction (for whisper,
    the cross-KV rows are sized to the fixed ``n_frames`` capacity every
    admitted utterance is padded to). ``acquire``/``release`` manage the
    free list; ``insert`` is the splice a scheduler calls on admission.
    ``mesh`` shards the slot axis over the mesh's "data" axis
    (DESIGN.md §13); slots then partition into ``n_shards`` device-local
    ranges of ``shard_size`` and ``acquire`` balances admission across
    them.
    """
    plan_geometry = None    # contiguous: the step's plan key has no pages

    def __init__(self, cfg, params, n_slots: int, max_len: int,
                 n_frames: Optional[int] = None, mesh=None):
        self.n_slots = n_slots
        self.max_len = max_len
        self.n_frames = n_frames
        st = model_lib.family_of(cfg).empty_state(params, cfg, n_slots,
                                                  max_len, n_frames)
        self.state: ServeState = model_lib.slot_layout(st, n_slots)
        self.mesh = mesh
        self.n_shards = 1
        self._insert_jit = _INSERT_JIT
        self._reset_jit = _RESET_JIT
        if mesh is not None:
            specs = model_lib.slot_state_specs(self.state, mesh)
            shardings = shard_rules.named(mesh, specs)
            self.state = jax.device_put(self.state, shardings)
            # per-pool jits with out_shardings pinned: the splice can
            # never silently un-shard the pool, whatever GSPMD would
            # propagate from the batch-1 request operand
            self._insert_jit = jax.jit(slot_insert, out_shardings=shardings,
                                       donate_argnums=0)
            self._reset_jit = jax.jit(slot_reset, out_shardings=shardings,
                                      donate_argnums=0)
            dsize = (mesh.shape["data"]
                     if "data" in mesh.axis_names else 1)
            if dsize > 1 and n_slots % dsize == 0:
                self.n_shards = dsize
        self.shard_size = n_slots // self.n_shards
        self._init_free()

    # -- free-slot bookkeeping (host side) -----------------------------
    def _init_free(self) -> None:
        """Per-shard sorted free lists — occupancy is maintained
        incrementally, so ``acquire`` is O(n_shards) instead of the old
        per-call scan over every free slot (ISSUE 7: the oversubscribing
        paged scheduler multiplies admission passes, so admission cost
        must not grow with pool width)."""
        self._free_by_shard: List[List[int]] = [
            list(range(s * self.shard_size, (s + 1) * self.shard_size))
            for s in range(self.n_shards)]
        self._n_free = self.n_slots

    @property
    def n_free(self) -> int:
        return self._n_free

    def slot_shard(self, slot: int) -> int:
        """Device-shard index owning ``slot`` (0 when unsharded)."""
        return slot // self.shard_size

    def acquire(self) -> int:
        """Claim a free slot (raises when full). Unsharded pools take the
        lowest index; sharded pools admit into the device-local slot range
        with the fewest active occupants (ties -> lowest index), so load
        spreads across the mesh instead of piling onto shard 0
        (DESIGN.md §13). O(n_shards): the per-shard free lists carry the
        occupancy counters, so nothing is scanned per call."""
        if self._n_free == 0:
            raise IndexError("pool full: no free slot")
        # fewest active == most free; prefer the lower shard on ties —
        # identical pick order to the old full-scan implementation
        shard = max(range(self.n_shards),
                    key=lambda s: (len(self._free_by_shard[s]), -s))
        self._n_free -= 1
        return self._free_by_shard[shard].pop(0)

    def release(self, slot: int, reset: bool = True) -> None:
        """Return ``slot`` to the free list. ``reset=False`` skips zeroing
        the row — safe because ``insert`` overwrites the entire slot before
        reuse and freed slots' garbage is never read (the scheduler's hot
        path uses it; the reset is one more dispatch per eviction)."""
        if reset:
            self.state = self._reset_jit(self.state, slot)
        bisect.insort(self._free_by_shard[self.slot_shard(slot)], slot)
        self._n_free += 1

    # -- memory accounting (DESIGN.md §15.4) ----------------------------
    def committed_kv_bytes(self) -> int:
        """Bytes preallocated for the whole pool state — what this
        contiguous layout commits regardless of occupancy."""
        return model_lib.state_kv_bytes(self.state)

    def used_kv_bytes(self, lengths: Dict[int, int]) -> int:
        """Bytes of committed state holding live request data, given the
        active slots' decode lengths: positional KV rows count
        proportionally to their filled length, fixed-size rows (whisper
        cross-KV) count whole per active slot. ``kv_utilization`` in the
        serving benchmarks is used/committed."""
        if not lengths:
            return 0
        n_active = len(lengths)
        frac = sum(min(l, self.max_len)
                   for l in lengths.values()) / self.max_len
        total = 0.0
        for leaf in jax.tree_util.tree_leaves(self.state.layer_states):
            per_slot = leaf.size // leaf.shape[1] * leaf.dtype.itemsize
            if leaf.ndim >= 3 and leaf.shape[2] == self.max_len:
                total += per_slot * frac
            else:
                total += per_slot * n_active
        return int(total)

    # -- state ops ------------------------------------------------------
    def splice_writes(self, req_state: ServeState) -> ServeState:
        """The shapes ``insert`` of ``req_state`` writes: the request's
        state in slot layout and the pool's dtypes, one slot's row. The
        rest of the pool stays where it is (the jit donates the pool)."""
        row = jax.eval_shape(lambda r: model_lib.slot_layout(r, 1),
                             req_state)
        return jax.tree_util.tree_map(
            lambda p, r: jax.ShapeDtypeStruct(r.shape, p.dtype),
            self.state, row)

    def insert(self, slot: int, req_state: ServeState) -> None:
        """Splice a batch-1 prefill state into ``slot`` in place (jitted,
        the pool donated; sharded pools keep their slot-axis sharding via
        pinned out_shardings)."""
        self.state = self._insert_jit(self.state, slot, req_state)

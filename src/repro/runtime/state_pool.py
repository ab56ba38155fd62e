"""Slot-based decode-state pool — MARCA's inter-operation buffer insight
applied at serving scale.

A Mamba sequence's entire decode state is a fixed O(d_inner * d_state)
block per layer (plus the (k-1)-tap conv tail), so unlike a ragged KV
cache it can live in a fixed-shape pool with one slot per in-flight
sequence: admission is a scatter of freshly prefilled state into a free
slot, eviction is a scatter of the init state, and the running decode
batch never changes shape.  The same layout generalizes to the other
registry families (KV caches are per-slot [max_seq] strips; xLSTM
matrix-memory states are per-slot blocks), which is why the pool is
family-agnostic: all slot knowledge lives in registry.cache_slot_axes.

All device ops are jit'd once with fixed shapes (slot ids are traced
(1,) arrays), so admit/evict/read never recompile.  The free list and
slot accounting are host-side.
"""
from __future__ import annotations

import functools
from typing import Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import registry
from repro.parallel import sharding
from repro.runtime import sampling


# Shared per-(config, shard) jit caches (cfg is frozen/hashable; shard is
# None or a hashable (Mesh, ShardingRules) pair): every pool for a given
# model reuses the same compiled gather/scatter/mask executables, and a
# sharded pool gets its OWN trace — the mesh context and the output
# constraints are baked at trace time, so a single-device pool can never
# alias a sharded compile (or vice versa).  Outputs are constrained to
# the cache's logical axes: a slot op's output sharding equals its input
# sharding, so admission/eviction/fork chains introduce zero resharding.
@functools.lru_cache(maxsize=None)
def _jit_gather(cfg, shard=None):
    cax = registry.cache_axes(cfg) if shard is not None else None

    def pool_gather(c, i):
        with sharding.shard_ctx(shard):
            out = registry.gather_slots(cfg, c, i)
            if shard is not None:
                out = sharding.constrain_tree(out, cax)
        return out
    return jax.jit(pool_gather)


@functools.lru_cache(maxsize=None)
def _jit_scatter(cfg, shard=None):
    cax = registry.cache_axes(cfg) if shard is not None else None

    def pool_scatter(c, s, i):
        with sharding.shard_ctx(shard):
            out = registry.scatter_slots(cfg, c, s, i)
            if shard is not None:
                out = sharding.constrain_tree(out, cax)
        return out
    return jax.jit(pool_scatter)


@functools.lru_cache(maxsize=None)
def _jit_mask(cfg, shard=None):
    cax = registry.cache_axes(cfg) if shard is not None else None

    def pool_mask(o, n, m):
        with sharding.shard_ctx(shard):
            out = registry.mask_slots(cfg, o, n, m)
            if shard is not None:
                out = sharding.constrain_tree(out, cax)
        return out
    return jax.jit(pool_mask)


@functools.lru_cache(maxsize=None)
def _jit_fork(cfg, shard=None):
    """Fork = gather(src) + scatter(dst) fused into one dispatch.  Every
    cache leaf — quantized payloads AND their absmax scales — moves in
    the same op, so a fork can never tear payload from scale."""
    cax = registry.cache_axes(cfg) if shard is not None else None

    def pool_fork(c, src, dst):
        with sharding.shard_ctx(shard):
            out = registry.scatter_slots(
                cfg, c, registry.gather_slots(cfg, c, src), dst)
            if shard is not None:
                out = sharding.constrain_tree(out, cax)
        return out
    return jax.jit(pool_fork)


class SlotStatePool:
    """Fixed-capacity pool of per-slot decode state for one model config.

    ``cache`` is a plain-value pytree (Param wrappers stripped) whose every
    leaf has ``n_total = n_slots + n_scratch`` entries along its slot
    axis: ``n_slots`` live slots (request state) plus ``n_scratch``
    scratch slots leased transiently for speculative-decode draft forks.
    Mutation is functional: admit/evict/commit/fork rebind ``self.cache``.
    """

    def __init__(self, cfg, n_slots: int, max_seq: int, dtype=None,
                 n_scratch: int = 0, mesh=None, rules=None):
        if n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if n_scratch < 0:
            raise ValueError("n_scratch must be >= 0")
        self.cfg = cfg
        self.n_slots = n_slots
        self.n_scratch = n_scratch
        self.n_total = n_slots + n_scratch
        self.max_seq = max_seq
        cache_p = registry.init_cache(cfg, self.n_total, max_seq, dtype)
        fresh_p = registry.init_cache(cfg, 1, max_seq, dtype)
        self.cache = sharding.tree_values(cache_p)
        # the init state of a single slot — eviction scatters this (NOT
        # zeros: e.g. xLSTM stabilizer state m inits to -1e30)
        self._fresh = sharding.tree_values(fresh_p)
        # tensor-parallel pool: place every cache leaf (payloads, absmax
        # scales, KV strips, positions) on the mesh by its logical axes
        # — TP-interior axes (act_ffn/act_heads) shard, slot axes stay
        # replicated — so all the jit'd slot ops below run on sharded
        # arrays in place.  mesh=None is the bitwise-unchanged
        # single-device path.
        self.mesh = mesh
        self.rules = ((rules if rules is not None else
                       sharding.ShardingRules())
                      if mesh is not None else None)
        self._shard = (mesh, self.rules) if mesh is not None else None
        if mesh is not None:
            self.cache = jax.device_put(
                self.cache,
                sharding.tree_shardings(cache_p, mesh, self.rules))
            self._fresh = jax.device_put(
                self._fresh,
                sharding.tree_shardings(fresh_p, mesh, self.rules))
        self._gather_fn = _jit_gather(cfg, self._shard)
        self._scatter_fn = _jit_scatter(cfg, self._shard)
        self._mask_fn = _jit_mask(cfg, self._shard)
        self._fork_fn = _jit_fork(cfg, self._shard)
        # per-slot sampling parameters (temperature/top-k/top-p/key) ride
        # with the slot: set on admission, copied on fork, reset on
        # eviction — the engine passes params.device() into the jit'd
        # steps as traced arrays, so heterogeneous values never retrace
        self.params = sampling.SlotParams(self.n_total)
        self._free: list[int] = list(range(n_slots))
        # scratch ids live in [n_slots, n_total): the ranges are disjoint
        # by construction, so a scratch lease can never collide with a
        # live slot no matter how admission/eviction interleave.
        self._scratch_free: list[int] = list(range(n_slots, self.n_total))
        self._active: list[bool] = [False] * self.n_total
        # eviction-free leases (infinite-stream sessions): a pinned slot
        # is active state under an open-ended lease — evicting it is a
        # bug, not a policy choice, so evict() refuses until unpin.
        self._pinned: list[bool] = [False] * self.n_total

    @property
    def fresh(self):
        """The (batch-1) init-state cache — reusable prefill scratch."""
        return self._fresh

    # -- host-side slot accounting ------------------------------------------

    @property
    def n_free(self) -> int:
        return len(self._free)

    @property
    def n_active(self) -> int:
        return self.n_slots - len(self._free)

    def active_slots(self) -> list[int]:
        return [i for i, a in enumerate(self._active) if a]

    def active_mask(self) -> np.ndarray:
        return np.asarray(self._active, bool)

    def alloc(self) -> Optional[int]:
        """Reserve a free slot id (lowest first), or None when full."""
        if not self._free:
            return None
        slot = min(self._free)
        self._free.remove(slot)
        self._active[slot] = True
        return slot

    # -- eviction-free leases (infinite-stream sessions) --------------------

    @property
    def n_pinned(self) -> int:
        return sum(self._pinned)

    def pin(self, slot: int) -> None:
        """Mark an active slot as holding an open-ended lease: evict()
        refuses it until unpin().  The scheduler subtracts pinned slots
        from its effective capacity, so admission-control projections
        never assume a session slot will free up."""
        assert self._active[slot], f"slot {slot} not active"
        self._pinned[slot] = True

    def unpin(self, slot: int) -> None:
        self._pinned[slot] = False

    def is_pinned(self, slot: int) -> bool:
        return self._pinned[slot]

    # -- scratch slots (speculative-decode draft forks) ---------------------
    #
    # Scratch slots are extra pool rows reserved for transient state
    # forks: the spec-decode draft leases one, receives a fork of a live
    # slot's state, runs draft steps on it, and releases it after the
    # verify pass.  They are invisible to the live accounting above
    # (alloc/evict/n_free/active_*), and their id range is disjoint from
    # live ids, so lease/release can interleave arbitrarily with
    # admission/eviction without collisions.

    @property
    def n_scratch_free(self) -> int:
        return len(self._scratch_free)

    def lease_scratch(self) -> Optional[int]:
        """Reserve a scratch slot id (lowest first), or None when none
        are free.  The leased slot's state is whatever the previous
        lease left — callers must fork real state in before reading."""
        if not self._scratch_free:
            return None
        slot = min(self._scratch_free)
        self._scratch_free.remove(slot)
        return slot

    def release_scratch(self, slot: int) -> None:
        """Return a leased scratch slot.  No state reset: unlike evict,
        a scratch slot is only ever read after a fork overwrote every
        leaf (payload and scales move together in fork), so stale state
        cannot leak into the next lease."""
        if not (self.n_slots <= slot < self.n_total):
            raise ValueError(f"{slot} is not a scratch slot id")
        if slot in self._scratch_free:
            raise ValueError(f"scratch slot {slot} is not leased")
        self._scratch_free.append(slot)

    def fork(self, src: Sequence[int], dst: Sequence[int],
             branch_tags: Optional[Sequence[Optional[int]]] = None) -> None:
        """Copy per-slot state src[i] -> dst[i] in one fused
        gather+scatter dispatch.  Quantized payloads and their absmax
        scales are both cache leaves, so they fork together — a forked
        draft can never observe a live slot's payload under a stale
        scale (or vice versa).

        ``branch_tags`` (same length as dst) controls the destination
        key stream.  None / a 0 entry copies the source key verbatim:
        the spec-decode draft contract — the scratch slot continues the
        request's exact key schedule, so the draft's proposals are
        bitwise the tokens the request itself would sample.  A truthy
        tag t folds it into the source key (best-of-n branch b uses
        tag b), so forked "alternatives" draw from genuinely distinct
        streams instead of aliasing the parent's — the fork-seed
        aliasing fix.
        """
        if len(src) != len(dst):
            raise ValueError("fork src/dst length mismatch")
        if branch_tags is not None and len(branch_tags) != len(dst):
            raise ValueError("fork branch_tags/dst length mismatch")
        if not src:
            return
        self.cache = self._fork_fn(self.cache, jnp.asarray(list(src)),
                                   jnp.asarray(list(dst)))
        # the fork's sampling params move with the state: the draft must
        # propose with the request's own temperature/top-k/top-p and key
        self.params.copy(src, dst, tags=branch_tags)

    # -- device-state operations --------------------------------------------

    def admit(self, slot: int, sub_cache) -> None:
        """Scatter a batch-1 prefilled cache into ``slot`` (from alloc)."""
        assert self._active[slot], f"slot {slot} not allocated"
        self.cache = self._scatter_fn(self.cache, sub_cache,
                                      jnp.asarray([slot]))

    # -- capacity accounting ------------------------------------------------

    def state_bytes_per_slot(self) -> int:
        """Device bytes one slot occupies across every cache leaf —
        quantized payloads count at their storage width, and the f32
        absmax scales (cache leaves themselves) are included, so the
        number is the honest marginal cost of one more slot."""
        return sum(leaf.nbytes for leaf in jax.tree.leaves(self.cache)
                   ) // self.n_total

    def slots_per_gb(self) -> float:
        """Slot capacity per GB of decode-state memory (the serving
        capacity axis cfg.state_dtype multiplies)."""
        return (1 << 30) / max(1, self.state_bytes_per_slot())

    def device_state_bytes_per_slot(self) -> int:
        """Per-DEVICE bytes one slot occupies.  Under a TP mesh the
        sharded cache leaves split across devices (each holds one shard
        shape's worth), while replicated leaves count in full on every
        device — so this is the honest per-chip marginal cost of a slot
        and the number the sharded slots-per-GB capacity claim gates.
        Without a mesh it equals ``state_bytes_per_slot``."""
        def per_dev(leaf):
            sh = getattr(leaf, "sharding", None)
            if sh is None:
                return leaf.nbytes
            shape = sh.shard_shape(leaf.shape)
            return int(np.prod(shape, dtype=np.int64)) * leaf.dtype.itemsize
        return sum(per_dev(leaf) for leaf in jax.tree.leaves(self.cache)
                   ) // self.n_total

    def device_slots_per_gb(self) -> float:
        """Slot capacity per GB of PER-DEVICE decode-state memory —
        under TP this exceeds ``slots_per_gb`` because sharded leaves
        split across the mesh."""
        return (1 << 30) / max(1, self.device_state_bytes_per_slot())

    def evict(self, slot: int) -> None:
        """Reset ``slot`` to the init state and return it to the free list.

        The scatter-of-fresh-state is what guarantees no stale-state leak:
        a later admit overwrites the slot again, so even a torn admit can
        never observe a previous request's recurrent state.  With a
        quantized state_dtype the per-slot absmax scales are cache
        leaves, so the same scatter resets them too — a freed slot
        cannot leak a stale scale into the next admitted sequence.
        """
        assert self._active[slot], f"slot {slot} not active"
        if self._pinned[slot]:
            raise RuntimeError(
                f"slot {slot} holds an eviction-free lease (pinned "
                "session) — unpin before evicting")
        self.cache = self._scatter_fn(self.cache, self._fresh,
                                      jnp.asarray([slot]))
        self.params.clear(slot)
        self._active[slot] = False
        self._free.append(slot)

    def read(self, slots: Sequence[int]):
        """Gather a sub-cache for ``slots`` (testing/debug/migration)."""
        return self._gather_fn(self.cache, jnp.asarray(list(slots)))

    def commit(self, new_cache, active: Optional[np.ndarray] = None) -> None:
        """Accept a post-decode cache, keeping inactive slots frozen."""
        if active is None:
            active = self.active_mask()
        self.cache = self._mask_fn(self.cache, new_cache,
                                   jnp.asarray(active))

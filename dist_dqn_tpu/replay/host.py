"""Host-DRAM replay shard: vectorized numpy sum-tree + ring storage.

This is the Ape-X side of the replay story (BASELINE.json:5): each TPU-VM
host holds one replay *shard* in host DRAM, fed by CPU actors over the DCN
transport (actors/). The learner samples batches here and ships them to the
device; priorities flow back after each update.

Two interchangeable tree backends implement the priority mass:

  * NativeSumTree — C++ (replay/_native/sumtree.cc), the default for the
    learner service: delta-propagation writes, per-query descent sampling,
    periodic exact rebuild. This is the native-runtime equivalent of the
    reference family's CUDA/host sum-trees (BASELINE.json:5).
  * SumTree — vectorized numpy twin (no Python-per-item loops: batched
    leaf writes propagate level-by-level over *unique* parents; sampling
    descends all queries in lockstep). Selected only by an explicit
    ``native=False``: the correctness cross-check in tests. A native build
    that fails is an error, not a switch to this tree.

The device-side sampler (replay/prioritized_device.py) is the fused-loop
equivalent; both implement the same P(i) ~ p_i^alpha contract, tested against
each other and against brute-force references.
"""
from __future__ import annotations

import ctypes
import threading
import time
from pathlib import Path
from typing import Dict, Optional, Tuple

import numpy as np

from dist_dqn_tpu.telemetry import collectors as tm

_NATIVE_DIR = Path(__file__).parent / "_native"
_tree_lib = None
_tree_lib_lock = threading.Lock()


def pad_pow2(n: int) -> int:
    """Smallest power of two >= n (tree padding; shared with the batched
    act bucketing in actors/service.py)."""
    padded = 1
    while padded < n:
        padded *= 2
    return padded


def stratified_mass(rng: np.random.Generator, batch_size: int,
                    total: float) -> np.ndarray:
    """One mass value per batch row from equal-width strata:
    u_i ~ U[i, i+1) / S * total. The jitter scheme every host-side PER
    sampler shares (this shard and the host-ring sampler in
    replay/host_ring.py) — stratification bounds the per-draw variance
    the plain-uniform scheme leaves on the table."""
    return (np.arange(batch_size) + rng.uniform(size=batch_size)) \
        / batch_size * total



def _check_tree_idx(idx: np.ndarray, capacity: int) -> np.ndarray:
    """Shared leaf-index validation for both tree backends: negative numpy
    indices would silently wrap onto interior nodes (numpy tree) or write
    out of bounds (C++ tree), so both must raise instead."""
    idx = np.ascontiguousarray(idx, np.int64)
    if idx.size and (idx.min() < 0 or idx.max() >= capacity):
        raise IndexError(f"sum-tree index out of range [0, {capacity}): "
                         f"{idx.min()}..{idx.max()}")
    return idx


# Exact interior-node recompute cadence for the native tree's delta
# propagation (float64 drift bound; see sumtree.cc). Coarse on purpose:
# a rebuild is one O(capacity) pass, ~ms at the 1M-slot Ape-X shard.
_REBUILD_EVERY_WRITES = 1 << 22


def _native_tree_lib() -> ctypes.CDLL:
    """Build (if needed) and load the C++ sum-tree library."""
    global _tree_lib
    with _tree_lib_lock:
        if _tree_lib is None:
            from dist_dqn_tpu.actors.transport import build_native_lib
            lib = ctypes.CDLL(str(build_native_lib(
                "sumtree.cc", "libdqnsumtree.so", directory=_NATIVE_DIR)))
            lib.dqn_tree_create.restype = ctypes.c_void_p
            lib.dqn_tree_create.argtypes = [ctypes.c_int64]
            lib.dqn_tree_destroy.argtypes = [ctypes.c_void_p]
            lib.dqn_tree_total.restype = ctypes.c_double
            lib.dqn_tree_total.argtypes = [ctypes.c_void_p]
            lib.dqn_tree_writes.restype = ctypes.c_uint64
            lib.dqn_tree_writes.argtypes = [ctypes.c_void_p]
            lib.dqn_tree_rebuild.argtypes = [ctypes.c_void_p]
            lib.dqn_tree_dump.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_void_p]
            lib.dqn_tree_load.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                                          ctypes.c_uint64]
            for name in ("dqn_tree_get", "dqn_tree_set", "dqn_tree_sample"):
                getattr(lib, name).argtypes = [
                    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_void_p,
                    ctypes.c_int64]
            _tree_lib = lib
    return _tree_lib


class NativeSumTree:
    """C++ sum-tree (replay/_native/sumtree.cc) with the SumTree interface.

    Same P(i) contract and tie semantics as the numpy tree below; writes use
    delta propagation with a periodic exact rebuild (drift bound). Preferred
    for the learner service's host shard — see PrioritizedHostReplay.
    """

    def __init__(self, capacity: int):
        self._lib = _native_tree_lib()
        self.capacity = pad_pow2(capacity)  # mirrors dqn_tree_create
        self._h = self._lib.dqn_tree_create(capacity)

    def __del__(self):
        h, self._h = getattr(self, "_h", None), None
        if h is not None:
            self._lib.dqn_tree_destroy(h)

    @property
    def total(self) -> float:
        return float(self._lib.dqn_tree_total(self._h))

    def get(self, idx: np.ndarray) -> np.ndarray:
        idx = _check_tree_idx(idx, self.capacity)
        out = np.empty(idx.shape[0], np.float64)
        self._lib.dqn_tree_get(self._h, idx.ctypes.data, out.ctypes.data,
                               idx.shape[0])
        return out

    def set(self, idx: np.ndarray, values: np.ndarray) -> None:
        idx = _check_tree_idx(idx, self.capacity)
        values = np.ascontiguousarray(
            np.broadcast_to(values, idx.shape), np.float64)
        self._lib.dqn_tree_set(self._h, idx.ctypes.data, values.ctypes.data,
                               idx.shape[0])
        if self._lib.dqn_tree_writes(self._h) >= _REBUILD_EVERY_WRITES:
            self._lib.dqn_tree_rebuild(self._h)

    def sample(self, mass: np.ndarray) -> np.ndarray:
        mass = np.ascontiguousarray(mass, np.float64)
        out = np.empty(mass.shape[0], np.int64)
        self._lib.dqn_tree_sample(self._h, mass.ctypes.data, out.ctypes.data,
                                  mass.shape[0])
        return out

    def state_dict(self) -> dict:
        """EXACT tree snapshot (ISSUE 12): the full interior-node heap
        plus the delta-propagation write counter. Interior sums carry
        path-dependent fp drift, so a bit-identical resume must restore
        the heap as-is — a leaf-only rebuild differs in the last ulp."""
        nodes = np.empty(2 * self.capacity, np.float64)
        writes = ctypes.c_uint64(0)
        self._lib.dqn_tree_dump(self._h, nodes.ctypes.data,
                                ctypes.byref(writes))
        return {"backend": np.bytes_(b"native"), "nodes": nodes,
                "writes": np.uint64(writes.value)}

    def load_state_dict(self, state: dict) -> None:
        nodes = np.ascontiguousarray(state["nodes"], np.float64)
        if nodes.shape[0] != 2 * self.capacity:
            raise ValueError(
                f"tree snapshot holds {nodes.shape[0] // 2} padded slots, "
                f"this tree has {self.capacity}")
        self._lib.dqn_tree_load(self._h, nodes.ctypes.data,
                                ctypes.c_uint64(int(state["writes"])))


def make_sum_tree(capacity: int, native: Optional[bool] = None):
    """The native C++ tree, unless ``native=False`` asks for the numpy
    twin (the tests' reference). A native build that fails raises: the
    run does not carry on with a substitute."""
    if native is False:
        return SumTree(capacity)
    return NativeSumTree(capacity)


class SumTree:
    """Flat-array binary sum-tree with vectorized batch set/sample."""

    def __init__(self, capacity: int):
        self.capacity = pad_pow2(capacity)
        self.depth = self.capacity.bit_length() - 1
        self.tree = np.zeros(2 * self.capacity, np.float64)

    @property
    def total(self) -> float:
        return float(self.tree[1])

    def get(self, idx: np.ndarray) -> np.ndarray:
        return self.tree[_check_tree_idx(idx, self.capacity) + self.capacity]

    def set(self, idx: np.ndarray, values: np.ndarray) -> None:
        """Vectorized leaf write + upward propagation."""
        leaf = _check_tree_idx(idx, self.capacity) + self.capacity
        self.tree[leaf] = values
        pos = np.unique(leaf >> 1)
        while pos[0] >= 1:
            self.tree[pos] = self.tree[2 * pos] + self.tree[2 * pos + 1]
            if pos[0] == 1:
                break
            pos = np.unique(pos >> 1)

    def sample(self, mass: np.ndarray) -> np.ndarray:
        """Map mass values in [0, total) to leaf indices, all in lockstep."""
        u = np.asarray(mass, np.float64).copy()
        idx = np.ones(u.shape[0], np.int64)
        for _ in range(self.depth):
            left = 2 * idx
            lmass = self.tree[left]
            go_right = u >= lmass
            u -= lmass * go_right
            idx = left + go_right
        return idx - self.capacity

    def state_dict(self) -> dict:
        """Exact snapshot twin of NativeSumTree.state_dict. The numpy
        tree recomputes parents on every set (order-independent), but
        the heap still rides along so native <-> numpy snapshots share
        one format; ``writes`` is 0 (no delta drift to schedule away)."""
        return {"backend": np.bytes_(b"numpy"), "nodes": self.tree.copy(),
                "writes": np.uint64(0)}

    def load_state_dict(self, state: dict) -> None:
        nodes = np.ascontiguousarray(state["nodes"], np.float64)
        if nodes.shape[0] != 2 * self.capacity:
            raise ValueError(
                f"tree snapshot holds {nodes.shape[0] // 2} padded slots, "
                f"this tree has {self.capacity}")
        np.copyto(self.tree, nodes)


class DevicePrioritySampler:
    """On-device priority sampling for a host-DRAM shard (BASELINE.json:5:
    the buffer shards across TPU-VM host DRAM, priority SAMPLING runs on
    device via Pallas).

    The p^alpha mass plane lives in accelerator memory as [rows, lanes];
    host-side writes buffer as (idx, mass) pairs and apply as one donated
    scatter right before each draw (a few KB per grad step). Draws use the
    shared stratified sampler (ops/pallas_sampler.py) — the Pallas VMEM
    kernel above its crossover on TPU, the XLA path elsewhere — and return
    flat slot indices plus selected masses/total for importance weights.
    The caller gathers the ITEMS from host DRAM; only priorities live on
    device.

    Sharded stores (ISSUE 18): ``device`` pins the plane to one chip of
    the mesh — the initial plane is committed there, and because jax
    computations follow committed data, every subsequent donated scatter
    and draw dispatch runs on that chip with no per-call placement (the
    small uncommitted operands move to it). A host-side float64 MIRROR
    of the plane (updated on every buffered ``set``, duplicate indices
    deduped last-write-wins exactly like the flush scatter) maintains
    ``total`` incrementally, so a cross-shard coordinator can lay its
    global stratified ladder over per-shard totals with ZERO device
    fetches; :meth:`dispatch_at`/:meth:`materialize_at` split the
    explicit-uniform draw so N shards' dispatches enqueue concurrently
    on their own chips before any result is awaited."""

    #: Incremental-total drift bound: every N flushes the mirror is
    #: re-summed exactly (one O(capacity) float64 pass, ~0.5 ms at 1M).
    _TOTAL_RESUM_EVERY = 256

    def __init__(self, capacity: int, lanes: int = 512, seed: int = 0,
                 use_pallas: Optional[bool] = None,
                 interpret: bool = False, device=None,
                 shard: Optional[int] = None):
        import jax
        import jax.numpy as jnp

        from dist_dqn_tpu.loop_common import pallas_routing
        from dist_dqn_tpu.ops.pallas_sampler import (SAMPLE_BLOCK,
                                                     importance_weights,
                                                     stratified_sample_at,
                                                     stratified_sample_rows)
        from dist_dqn_tpu.telemetry import get_registry
        self.jax = jax
        self.capacity = capacity
        self.lanes = lanes
        self.rows = -(-capacity // lanes)
        self.device = device
        self.shard = 0 if shard is None else int(shard)
        if use_pallas is None:
            # Platform-aware default, same crossover story as the fused
            # loop: Pallas on TPU above ~1e5 cells, XLA otherwise.
            use_pallas, interpret = pallas_routing(
                self.rows * lanes >= 100_000)
        self._plane = jnp.zeros((self.rows, lanes), jnp.float32)
        # Incremental block partial sums (ISSUE 18), maintained by the
        # write scatter (touched blocks only), so the XLA draw is the
        # three-level O(rows + S*(NB+BLOCK)) stratified_sample_rows —
        # never an O(rows*lanes) flat cumsum per draw.
        self._blk = SAMPLE_BLOCK if lanes % SAMPLE_BLOCK == 0 else lanes
        nb = lanes // self._blk
        self._blk_sums = jnp.zeros((self.rows, nb), jnp.float32)
        if device is not None:
            self._plane = jax.device_put(self._plane, device)
            self._blk_sums = jax.device_put(self._blk_sums, device)
        self._pending_idx: list = []
        self._pending_val: list = []
        self._rng = jax.random.PRNGKey(seed)
        # Host float64 mirror of the (f32-rounded) plane mass + running
        # total: the coordinator's ladder source. Stored post-f32-round
        # so mirror totals and plane totals agree to reduction order.
        self._mirror = np.zeros(self.rows * lanes, np.float64)
        self._total = 0.0
        self._flushes = 0
        # Dispatch/write-back accounting (ISSUE 18): the dispatch-budget
        # pin counts draws per train event; the rows counter feeds the
        # per-shard write-back telemetry family.
        self.draw_dispatches = 0
        self.writeback_rows = 0
        labels = {"shard": str(self.shard)}
        reg = get_registry()
        self._h_sample = reg.histogram(
            tm.REPLAY_DEVICE_SAMPLE_SECONDS,
            "on-device priority draw wall per shard: write-back flush + "
            "dispatch + host materialization", labels)
        self._c_wb_rows = reg.counter(
            tm.REPLAY_DEVICE_WRITEBACK_ROWS,
            "priority rows scattered into the shard's device plane "
            "(post last-write-wins dedup, pre pow2 padding)", labels)

        blk = self._blk

        def apply_writes(plane, blk_sums, idx, vals, ub):
            plane = plane.at[idx // lanes, idx % lanes].set(vals)
            # Re-sum ONLY the touched SAMPLE_BLOCK blocks (``ub``:
            # unique flat block ids) — O(writes * BLOCK) traffic, never
            # O(writes * lanes). Padded duplicates re-scatter the same
            # recomputed value: idempotent.
            newb = plane.reshape(-1, blk)[ub].sum(axis=1)
            blk_sums = blk_sums.at[ub // nb, ub % nb].set(newb)
            return plane, blk_sums

        self._apply = jax.jit(apply_writes, donate_argnums=(0, 1))

        def select_at(plane, blk_sums, u):
            # Trace-time routing: the Pallas kernel keeps the whole
            # plane in VMEM (TPU / the CPU interpret pin); the XLA path
            # draws three-level off the incremental partial sums.
            if use_pallas:
                return stratified_sample_at(plane.reshape(-1), u, lanes,
                                            use_pallas=True,
                                            interpret=interpret)
            return stratified_sample_rows(plane, blk_sums, u)

        def draw(plane, blk_sums, rng, batch, beta, n_valid):
            u01 = (jnp.arange(batch, dtype=jnp.float32)
                   + jax.random.uniform(rng, (batch,))) / batch
            t, b, mass, total = select_at(plane, blk_sums, u01)
            w = importance_weights(mass, total, n_valid, beta)
            return t * lanes + b, w

        self._draw = jax.jit(draw, static_argnums=3)

        def draw_at(plane, blk_sums, u):
            t, b, mass, _ = select_at(plane, blk_sums, u)
            return t * lanes + b, mass

        self._draw_at_jit = jax.jit(draw_at)

        # Fused write-back + draw: the per-event hot path. One program
        # keeps the event at ONE device dispatch per shard (the
        # dispatch-budget pin's unit) AND spares the donated plane a
        # defensive copy — a standalone scatter donating a plane the
        # still-queued previous draw references must copy all of it.
        def apply_draw_at(plane, blk_sums, idx, vals, ub, u):
            plane, blk_sums = apply_writes(plane, blk_sums, idx, vals,
                                           ub)
            t, b, mass, _ = select_at(plane, blk_sums, u)
            return plane, blk_sums, t * lanes + b, mass

        self._apply_draw_at = jax.jit(apply_draw_at,
                                      donate_argnums=(0, 1))

        def apply_draw(plane, blk_sums, idx, vals, ub, rng, batch, beta,
                       n_valid):
            plane, blk_sums = apply_writes(plane, blk_sums, idx, vals,
                                           ub)
            i, w = draw(plane, blk_sums, rng, batch, beta, n_valid)
            return plane, blk_sums, i, w

        self._apply_draw = jax.jit(apply_draw, static_argnums=6,
                                   donate_argnums=(0, 1))

    @property
    def total(self) -> float:
        """Total plane mass, from the host mirror — no device fetch."""
        return max(self._total, 0.0)

    def set(self, idx: np.ndarray, mass: np.ndarray) -> None:
        """Buffer p^alpha mass writes (applied lazily before the next
        draw). Last write per slot wins, as with the trees."""
        idx = np.asarray(idx, np.int32)
        vals = np.asarray(mass, np.float32)
        # Dedup to last-wins up front (np.unique leaves idx SORTED —
        # _prep_writes relies on that): the mirror delta below must see
        # each slot once or the old mass is subtracted twice (batched
        # write-backs concat several train steps), and XLA scatter
        # order is unspecified for duplicate indices within one call.
        if idx.shape[0] > 1:
            _, last = np.unique(idx[::-1], return_index=True)
            keep = idx.shape[0] - 1 - last
            idx, vals = idx[keep], vals[keep]
        self._pending_idx.append(idx)
        self._pending_val.append(vals)
        m64 = vals.astype(np.float64)
        self._total += float(m64.sum() - self._mirror[idx].sum())
        self._mirror[idx] = m64

    def _prep_writes(self):
        """Pad the pending write batch into the scatter operands
        ``(idx, vals, unique block ids)``, or None when nothing is
        pending. Each :meth:`set` batch arrives deduped AND sorted;
        only a multi-batch flush needs the cross-batch last-wins pass
        (XLA scatter order is unspecified for duplicates)."""
        if not self._pending_idx:
            return None
        if len(self._pending_idx) == 1:
            idx, vals = self._pending_idx[0], self._pending_val[0]
        else:
            idx = np.concatenate(self._pending_idx)
            vals = np.concatenate(self._pending_val)
            _, last = np.unique(idx[::-1], return_index=True)
            keep = idx.shape[0] - 1 - last
            idx, vals = idx[keep], vals[keep]
        self._pending_idx, self._pending_val = [], []
        self.writeback_rows += int(idx.shape[0])
        self._c_wb_rows.inc(idx.shape[0])
        self._flushes += 1
        if self._flushes % self._TOTAL_RESUM_EVERY == 0:
            self._total = float(self._mirror.sum())

        # Pad every operand to a power-of-two bucket (repeat one real
        # entry — both scatters set a recomputed value, so padded
        # duplicates are idempotent) so the donated programs compile
        # O(log) variants, not one per distinct write-batch length.
        def pad(a):
            p = pad_pow2(a.shape[0])
            if p == a.shape[0]:
                return a
            return np.concatenate([a, np.repeat(a[:1], p - a.shape[0])])

        # idx is sorted, so unique touched blocks are a diff away — no
        # second sort.
        blocks = idx // self._blk
        ub = blocks[np.flatnonzero(np.diff(blocks, prepend=-1))]
        return pad(idx), pad(vals), pad(ub.astype(np.int32))

    def _flush_writes(self) -> None:
        w = self._prep_writes()
        if w is not None:
            self._plane, self._blk_sums = self._apply(
                self._plane, self._blk_sums, *w)

    def _fire_draw_seam(self) -> None:
        """Chaos seam (ISSUE 18): the per-shard device draw — exception
        tests the coordinator's failure contract, stall its pipeline
        slack; recovery is anchored at the next draw that MATERIALIZES
        (mark_recovered in :meth:`materialize_at`/:meth:`sample`)."""
        from dist_dqn_tpu import chaos
        cev = chaos.fire("replay.device_sample")
        if cev is not None:
            if cev.fault == "exception":
                raise chaos.ChaosInjectedError("replay.device_sample",
                                               cev.fault)
            chaos.sleep_for(cev)

    def dispatch_at(self, u: np.ndarray):
        """Enqueue one explicit-uniform draw (u [S] in [0, 1)) on the
        plane's device and return the UNMATERIALIZED (idx, mass) device
        arrays — jax dispatch is async, so a coordinator looping over
        shards runs all their draws concurrently before the first
        :meth:`materialize_at` blocks. One jitted program per call: the
        dispatch-budget pin's unit of accounting."""
        self._fire_draw_seam()
        self.draw_dispatches += 1
        u = np.asarray(u, np.float32)
        w = self._prep_writes()
        t0 = time.perf_counter()
        if w is None:
            return (t0, self._draw_at_jit(self._plane, self._blk_sums,
                                          u))
        (self._plane, self._blk_sums, idx,
         mass) = self._apply_draw_at(self._plane, self._blk_sums, *w, u)
        return (t0, (idx, mass))

    def materialize_at(self, handle, size: int
                       ) -> Tuple[np.ndarray, np.ndarray]:
        """Block on a :meth:`dispatch_at` handle -> (flat idx [S] int64,
        selected f64 mass [S] — zeroed where the draw walked onto an
        unwritten/zero-mass cell, so the caller's IS weights zero those
        rows exactly like :meth:`sample` does)."""
        t0, (idx, mass) = handle
        idx = np.asarray(idx, np.int64)
        mass = np.asarray(mass, np.float64)
        bad = (idx >= size) | (mass <= 0.0)
        if bad.any():
            idx = np.minimum(idx, size - 1)
            mass = np.where(bad, 0.0, mass)
        dt = time.perf_counter() - t0
        self._h_sample.observe(dt)
        from dist_dqn_tpu import chaos
        chaos.mark_recovered("replay.device_sample")
        return idx, mass

    def sample_at(self, u: np.ndarray, size: int
                  ) -> Tuple[np.ndarray, np.ndarray]:
        """Synchronous explicit-uniform draw (dispatch + materialize)."""
        return self.materialize_at(self.dispatch_at(u), size)

    def sample(self, batch_size: int, beta: float, size: int
               ) -> Tuple[np.ndarray, np.ndarray]:
        """-> (flat slot indices [S], IS weights [S])."""
        self._fire_draw_seam()
        self.draw_dispatches += 1
        pend = self._prep_writes()
        t0 = time.perf_counter()
        self._rng, k = self.jax.random.split(self._rng)
        if pend is None:
            idx, w = self._draw(self._plane, self._blk_sums, k,
                                batch_size, np.float32(beta),
                                np.float32(size))
        else:
            (self._plane, self._blk_sums, idx,
             w) = self._apply_draw(self._plane, self._blk_sums, *pend,
                                   k, batch_size, np.float32(beta),
                                   np.float32(size))
        idx = np.asarray(idx, np.int64)
        w = np.asarray(w, np.float32)
        dt = time.perf_counter() - t0
        self._h_sample.observe(dt)
        # A draw can land past the written region only through fp boundary
        # pathology on a zero-mass cell. Clamping alone would pair slot
        # size-1 with the OUT-OF-RANGE cell's IS weight; zero the weight
        # too so the substituted item contributes nothing to the loss.
        oob = idx >= size
        if oob.any():
            idx = np.minimum(idx, size - 1)
            w = np.where(oob, np.float32(0.0), w)
        from dist_dqn_tpu import chaos
        chaos.mark_recovered("replay.device_sample")
        return idx, w


class PrioritizedHostReplay:
    """One prioritized replay shard over host DRAM.

    Items are dicts of numpy arrays (already n-step-folded transitions, or
    R2D2 sequences); storage is allocated lazily from the first batch's
    dtypes/shapes. ``alpha`` is folded into stored leaf mass at write time
    (hosts rewrite leaves cheaply, unlike the device path).

    ``sampler="tree"`` (default) draws on the host via the C++/numpy
    sum-tree; ``sampler="device"`` keeps the priority plane in accelerator
    memory and draws with the Pallas/XLA stratified kernel
    (DevicePrioritySampler) — the BASELINE.json:5 wording for the Ape-X
    shard. Item storage stays in host DRAM either way.
    """

    def __init__(self, capacity: int, alpha: float = 0.6,
                 priority_eps: float = 1e-6, seed: int = 0,
                 native: Optional[bool] = None, sampler: str = "tree",
                 sampler_device=None, shard: Optional[int] = None):
        self.capacity = capacity
        self.alpha = alpha
        self.priority_eps = priority_eps
        self.sampler = sampler
        # ``sampler_device``/``shard`` (ISSUE 18): the sharded facade
        # pins each sub-store's plane to its sticky chip and labels its
        # device-sampling telemetry with the shard id.
        self.device_sampler = (
            DevicePrioritySampler(capacity, seed=seed,
                                  device=sampler_device, shard=shard)
            if sampler == "device" else None)
        # Device mode never reads the host tree — don't pay its writes,
        # rebuilds, or the float64 allocation for nothing.
        self.tree = (None if self.device_sampler is not None
                     else make_sum_tree(capacity, native=native))
        self._data: Optional[Dict[str, np.ndarray]] = None
        self._pos = 0
        self._size = 0
        self._max_priority = 1.0
        self._rng = np.random.default_rng(seed)
        # Cumulative counters for metrics (BASELINE.json:2 throughput).
        self.added = 0
        self.sampled = 0
        # Sticky-ingest placement accounting (ISSUE 9): items per
        # routing shard (the sharded facade routes by it; on this
        # single store the tag is placement accounting).
        self.added_by_shard: Dict[int, int] = {}
        # Telemetry (ISSUE 1): occupancy/eviction/priority-distribution
        # for the host shard. Instruments are cached here — the add/
        # sample hot paths pay one attribute op + one locked float add.
        from dist_dqn_tpu.telemetry import get_registry
        reg = get_registry()
        # Every series in a shared family carries the store label, so
        # per-store aggregation (sum by (store)) never drops a shard.
        labels = {"store": "host"}
        self._g_size, self._g_cap, self._g_occ = tm.replay_gauges("host",
                                                                  reg)
        self._g_cap.set(capacity)
        self._c_added = reg.counter(tm.REPLAY_ADDED,
                                    "items written to the host shard",
                                    labels)
        self._c_sampled = reg.counter(tm.REPLAY_SAMPLED,
                                      "items drawn from the host shard",
                                      labels)
        self._c_evicted = reg.counter(
            tm.REPLAY_EVICTED, "ring overwrites of still-live items",
            labels)
        self._g_max_prio = reg.gauge(
            tm.REPLAY_MAX_PRIORITY, "running max |TD| priority", labels)
        self._g_mass = reg.gauge(
            tm.REPLAY_PRIORITY_MASS,
            "total p^alpha mass in the shard's sum-tree", labels)
        # Per-slot write generation: lets async learners (pipelined train
        # steps, actors/service.py) detect that a sampled slot was
        # overwritten before its priority write-back and drop the stale
        # update instead of stamping it onto a different transition.
        self._slot_gen = np.zeros(capacity, np.int64)

    def __len__(self) -> int:
        return self._size

    def _ensure_storage(self, items: Dict[str, np.ndarray]) -> None:
        if self._data is None:
            self._data = {
                k: np.zeros((self.capacity,) + v.shape[1:], v.dtype)
                for k, v in items.items()
            }

    def add(self, items: Dict[str, np.ndarray],
            priorities: Optional[np.ndarray] = None,
            shard: Optional[int] = None) -> None:
        """Ring-write a batch; new items default to the running max priority.

        ``shard`` is the sticky-ingest routing tag (ingest/router.py,
        ISSUE 9): on this single store it is placement accounting
        (``added_by_shard``); the sharded facade
        (replay/sharded.py ShardedPrioritizedReplay, ISSUE 10) routes
        each batch to the sub-store this tag names — the shard that
        will sample it."""
        batch = next(iter(items.values())).shape[0]
        if shard is not None:
            self.added_by_shard[shard] = \
                self.added_by_shard.get(shard, 0) + batch
        self._ensure_storage(items)
        idx = (self._pos + np.arange(batch)) % self.capacity
        for k, v in items.items():
            self._data[k][idx] = v
        if priorities is None:
            p = np.full(batch, self._max_priority)
        else:
            p = np.abs(np.asarray(priorities, np.float64)) \
                + self.priority_eps
            self._max_priority = max(self._max_priority, float(p.max()))
        mass = p ** self.alpha
        if self.device_sampler is not None:
            self.device_sampler.set(idx, mass)
        else:
            self.tree.set(idx, mass)
        self.added += batch
        self._slot_gen[idx] = self.added
        evicted = max(self._size + batch - self.capacity, 0)
        self._pos = int((self._pos + batch) % self.capacity)
        self._size = int(min(self._size + batch, self.capacity))
        self._c_added.inc(batch)
        if evicted:
            self._c_evicted.inc(evicted)
        self._g_size.set(self._size)
        self._g_occ.set(self._size / self.capacity)
        self._g_max_prio.set(self._max_priority)

    def sample(self, batch_size: int, beta: float
               ) -> Tuple[Dict[str, np.ndarray], np.ndarray, np.ndarray]:
        """Stratified prioritized sample -> (items, indices, IS weights)."""
        if self._size == 0:
            raise ValueError("sample() on an empty replay shard")
        if self.device_sampler is not None:
            idx, weights = self.device_sampler.sample(batch_size, beta,
                                                      self._size)
        else:
            total = self.tree.total
            idx = self.tree.sample(
                stratified_mass(self._rng, batch_size, total))
            idx = np.minimum(idx, self._size - 1)
            p_sel = self.tree.get(idx) / total
            weights = (self._size * np.maximum(p_sel, 1e-12)) ** (-beta)
            weights = (weights / weights.max()).astype(np.float32)
        items = {k: v[idx] for k, v in self._data.items()}
        self.sampled += batch_size
        self._c_sampled.inc(batch_size)
        if self.tree is not None:
            self._g_mass.set(self.tree.total)
        return items, idx, weights

    def state_dict(self) -> Dict[str, np.ndarray]:
        """Serializable shard snapshot (VERDICT round-3 next #7): item
        arrays over the FULL capacity ring (the ring may have wrapped, so
        the live region is position-dependent), per-slot p^alpha mass,
        and the cursor/counters. Pairs with ``load_state_dict`` for the
        apex runtime's opt-in replay checkpointing; a 60k-slot pixel
        shard snapshots at ~1.7 GB (documented trade-off in
        utils/checkpoint.py — the default remains stateless refill)."""
        if self._data is None:
            raise ValueError("state_dict() on an unallocated shard "
                             "(nothing added yet)")
        if self.device_sampler is not None:
            self.device_sampler._flush_writes()
            mass = np.asarray(self.device_sampler._plane,
                              np.float32).reshape(-1)[:self.capacity].copy()
        else:
            mass = np.asarray(
                self.tree.get(np.arange(self.capacity, dtype=np.int64)),
                np.float64)
        out = {f"data.{k}": v for k, v in self._data.items()}
        out.update(mass=mass, slot_gen=self._slot_gen.copy(),
                   meta=np.array([self._pos, self._size, self.added,
                                  self.sampled], np.int64),
                   max_priority=np.float64(self._max_priority),
                   alpha=np.float64(self.alpha),
                   capacity=np.int64(self.capacity))
        return out

    def load_state_dict(self, state: Dict[str, np.ndarray]) -> None:
        """Restore a ``state_dict`` snapshot into this (same-capacity,
        same-alpha) shard; storage is allocated from the snapshot."""
        if int(state["capacity"]) != self.capacity:
            raise ValueError(
                f"replay snapshot capacity {int(state['capacity'])} != "
                f"configured {self.capacity} — restore with the same "
                "replay.capacity used at save time")
        if float(state["alpha"]) != self.alpha:
            raise ValueError(
                f"replay snapshot alpha {float(state['alpha'])} != "
                f"configured {self.alpha}")
        self._data = {k[len("data."):]: np.array(v)
                      for k, v in state.items() if k.startswith("data.")}
        self._pos, self._size, self.added, self.sampled = (
            int(x) for x in state["meta"])
        self._max_priority = float(state["max_priority"])
        self._slot_gen = np.array(state["slot_gen"], np.int64)
        idx = np.arange(self.capacity, dtype=np.int64)
        mass = np.asarray(state["mass"], np.float64)
        if self.device_sampler is not None:
            self.device_sampler.set(idx, mass.astype(np.float32))
        else:
            self.tree.set(idx, mass)

    def generation(self, idx: np.ndarray) -> np.ndarray:
        """Write-generation stamps of the given slots (see update guard)."""
        return self._slot_gen[np.asarray(idx, np.int64)].copy()

    def update_priorities(self, idx: np.ndarray, priorities: np.ndarray,
                          expected_gen: Optional[np.ndarray] = None) -> None:
        """Write back learner |TD| priorities. With ``expected_gen`` (the
        ``generation`` captured at sample time), slots overwritten since
        are skipped — required when the write-back is deferred past
        subsequent inserts (pipelined learners)."""
        idx = np.asarray(idx, np.int64)
        p = np.abs(np.asarray(priorities, np.float64)) + self.priority_eps
        if expected_gen is not None:
            live = self._slot_gen[idx] == expected_gen
            if not live.all():
                idx, p = idx[live], p[live]
            if idx.size == 0:
                return
        self._max_priority = max(self._max_priority, float(p.max()))
        self._g_max_prio.set(self._max_priority)
        mass = p ** self.alpha
        if self.device_sampler is not None:
            self.device_sampler.set(idx, mass)
        else:
            self.tree.set(idx, mass)


class UniformHostReplay:
    """Uniform ring-buffer shard with the same item interface."""

    def __init__(self, capacity: int, seed: int = 0):
        self.capacity = capacity
        self._data: Optional[Dict[str, np.ndarray]] = None
        self._pos = 0
        self._size = 0
        self._rng = np.random.default_rng(seed)
        # Distinct store label: a process holding both a PER shard and a
        # uniform buffer must not have them clobber one gauge series.
        self._g_size, self._g_cap, self._g_occ = \
            tm.replay_gauges("host_uniform")
        self._g_cap.set(capacity)

    def __len__(self) -> int:
        return self._size

    def add(self, items: Dict[str, np.ndarray]) -> None:
        batch = next(iter(items.values())).shape[0]
        if self._data is None:
            self._data = {
                k: np.zeros((self.capacity,) + v.shape[1:], v.dtype)
                for k, v in items.items()
            }
        idx = (self._pos + np.arange(batch)) % self.capacity
        for k, v in items.items():
            self._data[k][idx] = v
        self._pos = int((self._pos + batch) % self.capacity)
        self._size = int(min(self._size + batch, self.capacity))
        self._g_size.set(self._size)
        self._g_occ.set(self._size / self.capacity)

    def sample(self, batch_size: int) -> Dict[str, np.ndarray]:
        idx = self._rng.integers(0, self._size, size=batch_size)
        return {k: v[idx] for k, v in self._data.items()}

    def state_dict(self) -> Dict[str, np.ndarray]:
        """Uniform-shard counterpart of PrioritizedHostReplay.state_dict
        (no mass/priority state to carry)."""
        if self._data is None:
            raise ValueError("state_dict() on an unallocated shard "
                             "(nothing added yet)")
        out = {f"data.{k}": v for k, v in self._data.items()}
        out.update(meta=np.array([self._pos, self._size], np.int64),
                   capacity=np.int64(self.capacity))
        return out

    def load_state_dict(self, state: Dict[str, np.ndarray]) -> None:
        if int(state["capacity"]) != self.capacity:
            raise ValueError(
                f"replay snapshot capacity {int(state['capacity'])} != "
                f"configured {self.capacity} — restore with the same "
                "replay.capacity used at save time")
        self._data = {k[len("data."):]: np.array(v)
                      for k, v in state.items() if k.startswith("data.")}
        self._pos, self._size = (int(x) for x in state["meta"])

"""Host<->device batch staging: H2D double buffering (ISSUE 2), the
streamed D2H evacuation pipeline (ISSUE 3), and the prioritized
sample-ahead prefetcher (ISSUE 5).

H2D half — double-buffered host->device batch staging (ISSUE 2 #3).

Both learner paths promise the same overlap: while train step ``g`` runs
on the device, the host samples batch ``g+1`` and starts its H2D upload,
so the device never waits on the link between steps. This module makes
that overlap explicit, bounded and measured instead of an accident of
JAX's async dispatch:

  * a fixed pool of ``depth`` REUSABLE host staging buffer sets,
    allocated once from the first batch's shapes/dtypes. Samples are
    gathered into these persistent arrays (``np.copyto``) rather than
    fresh allocations, so the upload always reads from stable,
    page-warm host memory — the closest a portable JAX program gets to
    pinned staging (there is no public pin API; what matters for DMA is
    that the source pages are resident and reused, and they are);
  * ``stage()`` begins the upload asynchronously (``jax.device_put``
    returns before the copy completes) and queues the device-side
    batch; ``pop()`` hands batches back in FIFO order;
  * buffer reuse is SAFE by construction: before a host set is
    overwritten, the device arrays previously uploaded from it are
    block-until-ready'd — a no-op in steady state, since a full train
    step has run since that upload was issued.

D2H half — ``StreamedEvacuator`` + ``EvacuationWorker`` (ISSUE 3): the
host-replay loop's chunk records leave the device as ``--evac-slices``
time slices instead of one monolithic blocking ``device_get``. The
evacuator compiles ONE splitting program per chunk shape (one dispatch
per chunk, not per slice), starts every slice's host copy asynchronously
(``copy_to_host_async``), and publishes each slice into the ring's
preallocated slot arrays as it arrives — slice k's ring append overlaps
slice k+1's transfer, and the whole stream overlaps the next chunk's
device compute. The worker moves the blocking tail (transfer wait + ring
append) off the main thread entirely, behind a per-chunk completion
handle the training loop fences on before sampling.

Sample-ahead half — ``SamplePrefetcher`` (ISSUE 5): the H2D twin of the
``EvacuationWorker``. A background thread runs the whole
sample -> gather -> stage (reusable pinned-host copy + async H2D
upload) chain AHEAD of the learner, feeding a bounded queue of
device-resident batches through an internal ``DoubleBufferedStager``;
the training loop pops finished batches instead of paying host-side
sampling (uniform gathers or sum-tree descents) on its critical path.
A generation-fence handshake with the ring keeps it honest: every
batch is tagged with the ring generation it sampled against, and a
batch sampled against an OLDER window than the train event fenced on
is counted, dropped and re-sampled — never trained on silently.

Telemetry (ISSUE 2/3/5): queue occupancy gauge, staged-batch and
staged-byte counters, D2H byte/slice counters and evacuation-latency /
slice-lag histograms, sample-latency / prefetch-wait histograms and the
stale-batch counter — all labeled with the owning loop's name so the
service learner and the host-replay loop stay separable on one
dashboard.
"""
from __future__ import annotations

import queue
import threading
import time
from collections import deque
from typing import Any, Callable, List, Optional, Tuple

import numpy as np

from dist_dqn_tpu import chaos
from dist_dqn_tpu.telemetry import collectors as tm, get_registry
from dist_dqn_tpu.telemetry import flight as tm_flight
from dist_dqn_tpu.telemetry import watchdog as tm_watchdog


class DoubleBufferedStager:
    """FIFO of in-flight H2D uploads over ``depth`` reusable buffer sets.

    ``stage(host_batch, aux=...)`` copies a pytree of numpy arrays into
    the next staging set and starts its device upload; ``pop()`` returns
    ``(device_batch, aux)`` oldest-first. ``aux`` carries whatever
    host-side bookkeeping must travel with the batch (replay indices,
    write generations) without touching the device.

    ``depth`` bounds both host memory (depth x batch bytes) and how far
    sampling may run ahead of training. Depth 2 is classic double
    buffering; higher depths only pay off when upload latency exceeds a
    whole train step.
    """

    def __init__(self, depth: int = 2, name: str = "learner",
                 device_put: Optional[Callable] = None):
        if depth < 1:
            raise ValueError(f"stager depth must be >= 1, got {depth}")
        import jax  # deferred: keep the module importable without jax

        self._jax = jax
        self.depth = depth
        self._put = device_put if device_put is not None else jax.device_put
        # Alias guard (found by the ISSUE 5 prefetcher's equivalence
        # pin): CPU PJRT zero-copies suitably-aligned numpy buffers, so
        # the "uploaded" Array can ALIAS the staging pages for its whole
        # lifetime — the reuse barrier below (upload ready) then does
        # not stop a later np.copyto into the slot from rewriting data
        # a still-pending train step has not read yet. One jitted
        # device-side copy breaks the alias, and ITS readiness (the
        # barrier waits on the copy's output) proves the staging pages
        # were fully read. Real accelerators DMA a genuine copy on
        # device_put, so the guard and its extra device memcpy stay off
        # there.
        self._alias_guard = (device_put is None
                             and jax.default_backend() == "cpu")
        if self._alias_guard:
            import jax.numpy as jnp

            self._unalias = jax.jit(
                lambda tree: jax.tree_util.tree_map(jnp.copy, tree))
        # host staging sets, allocated lazily from the first batch:
        # _bufs[i] is a list of numpy leaves matching the batch treedef.
        self._bufs: List[Optional[List[np.ndarray]]] = [None] * depth
        # device arrays last uploaded FROM each set — reuse barrier.
        self._last_upload: List[Any] = [None] * depth
        self._treedef = None
        self._queue: deque = deque()
        self._staged_total = 0
        self.bytes_staged = 0
        labels = {"loop": name}
        reg = get_registry()
        self._g_occ = reg.gauge(
            tm.STAGING_OCCUPANCY,
            "H2D batches staged ahead, not yet consumed", labels)
        self._c_staged = reg.counter(
            tm.STAGING_STAGED, "batches staged through the double buffer",
            labels)
        self._c_bytes = reg.counter(
            tm.STAGING_BYTES, "host bytes copied into staging buffers",
            labels)

    def __len__(self) -> int:
        return len(self._queue)

    @property
    def staged_total(self) -> int:
        return self._staged_total

    def stage(self, host_batch: Any, aux: Any = None) -> None:
        """Copy ``host_batch`` (pytree of numpy arrays) into the next
        staging set and begin its async upload."""
        if len(self._queue) >= self.depth:
            raise RuntimeError(
                f"stager depth {self.depth} exceeded: pop() before "
                "staging further batches")
        jax = self._jax
        leaves, treedef = jax.tree_util.tree_flatten(host_batch)
        if self._treedef is None:
            self._treedef = treedef
            self._leaf_specs = [(np.shape(leaf), np.asarray(leaf).dtype)
                                for leaf in leaves]
        elif treedef != self._treedef:
            raise ValueError("staged batch structure changed mid-run")
        for leaf, (shape, dtype) in zip(leaves, self._leaf_specs):
            arr = np.asarray(leaf)
            if arr.shape != shape or arr.dtype != dtype:
                raise ValueError(
                    f"staged leaf {arr.shape}/{arr.dtype} does not match "
                    f"the staging buffer {shape}/{dtype}")
        slot = self._staged_total % self.depth
        bufs = self._bufs[slot]
        if bufs is None:
            bufs = [np.empty(np.shape(leaf), np.asarray(leaf).dtype)
                    for leaf in leaves]
            self._bufs[slot] = bufs
        else:
            # Reuse barrier: the upload previously issued from this set
            # must have finished reading the host pages before they are
            # overwritten. Steady state: that upload is depth pops old
            # and long done, so this returns immediately.
            prev = self._last_upload[slot]
            if prev is not None:
                jax.block_until_ready(prev)
        nbytes = 0
        for buf, leaf in zip(bufs, leaves):
            arr = np.asarray(leaf)
            np.copyto(buf, arr)
            nbytes += arr.nbytes
        device_batch = self._put(
            jax.tree_util.tree_unflatten(self._treedef, bufs))
        if self._alias_guard:
            device_batch = self._unalias(device_batch)
        self._last_upload[slot] = device_batch
        self._queue.append((device_batch, aux))
        self._staged_total += 1
        self.bytes_staged += nbytes
        self._c_staged.inc()
        self._c_bytes.inc(nbytes)
        self._g_occ.set(len(self._queue))

    def pop(self) -> Tuple[Any, Any]:
        """Oldest staged ``(device_batch, aux)``; raises when empty."""
        if not self._queue:
            raise RuntimeError("pop() on an empty stager — stage() first")
        out = self._queue.popleft()
        self._g_occ.set(len(self._queue))
        return out


def _slice_bounds(length: int, num_slices: int) -> List[Tuple[int, int]]:
    """Contiguous near-equal [lo, hi) time slices covering [0, length)."""
    k = max(1, min(int(num_slices), int(length)))
    base, rem = divmod(int(length), k)
    bounds, lo = [], 0
    for i in range(k):
        hi = lo + base + (1 if i < rem else 0)
        bounds.append((lo, hi))
        lo = hi
    return bounds


class _EvacJob:
    """One chunk's in-flight evacuation: device slices with their host
    copies already started, plus the completion handle state."""

    def __init__(self, slices, bounds, treedef, submitted_at: float):
        self.slices = slices            # [k][leaf] device arrays
        self.bounds = bounds            # [k] (lo, hi)
        self.treedef = treedef
        self.submitted_at = submitted_at
        self.stats: dict = {}
        self._done = threading.Event()
        self._exc: Optional[BaseException] = None

    # -- completion handle surface (what the training loop sees) ------------
    def wait(self, timeout: Optional[float] = None) -> bool:
        """Fence: block until every slice of this chunk is appended (or
        the worker failed). Re-raises the worker's exception."""
        ok = self._done.wait(timeout)
        if self._exc is not None:
            raise self._exc
        return ok

    @property
    def done(self) -> bool:
        return self._done.is_set() and self._exc is None

    def _finish(self, stats: dict) -> None:
        self.stats = stats
        self._done.set()

    def _fail(self, exc: BaseException) -> None:
        self._exc = exc
        self._done.set()


class StreamedEvacuator:
    """Streamed sub-chunk D2H evacuation — the D2H twin of
    ``DoubleBufferedStager`` (ISSUE 3 tentpole #2).

    ``start(records)`` splits a pytree of ``[C, B, ...]`` device arrays
    into ``num_slices`` contiguous time slices with ONE jitted device
    program (the caller drops its records reference after — the split
    outputs replace them) and starts every slice's asynchronous host
    copy; it returns an
    ``_EvacJob`` and never blocks on the link. ``drain(job, on_slice)``
    then walks the slices in time order: each ``np.asarray`` completes
    when that slice's transfer lands (earlier slices finish while later
    ones are still in flight) and ``on_slice(tree, lo, hi)`` publishes
    it. The fetched arrays go to ``on_slice`` as-is: the reusable
    preallocated host buffers of this pipeline are the RING'S OWN slot
    arrays, which ``add_chunk`` memcpys into synchronously before
    ``on_slice`` returns — an intermediate staging pool here would add
    a third full copy of every evacuated byte for a handoff nothing
    reads afterward (unlike the H2D stager, whose pool IS read by an
    in-flight async upload). Slice trees are only valid within their
    ``on_slice`` call.

    Splitting costs one device dispatch per chunk, not per slice.
    """

    def __init__(self, num_slices: int = 4, name: str = "host_replay",
                 shard: Optional[int] = None):
        if num_slices < 1:
            raise ValueError(
                f"evacuator num_slices must be >= 1, got {num_slices}")
        import jax  # deferred: keep the module importable without jax

        self._jax = jax
        self.num_slices = int(num_slices)
        self._split_cache: dict = {}
        self.bytes_total = 0
        self.slices_total = 0
        labels = {"loop": name}
        reg = get_registry()
        self._c_bytes = reg.counter(
            tm.HOST_REPLAY_D2H_BYTES,
            "bytes evacuated device->host by the replay pipeline", labels)
        self._c_slices = reg.counter(
            tm.HOST_REPLAY_EVAC_SLICES,
            "sub-chunk D2H slices streamed", labels)
        # Sharded collect (ISSUE 15): when this evacuator drains one dp
        # shard's lane block, its bytes carry an explicit {shard} label
        # too — the per-shard conservation evidence scaling_bench's
        # collect arm reads (each shard's ring fed by its OWN device).
        self._c_shard_bytes = None
        if shard is not None:
            self._c_shard_bytes = reg.counter(
                tm.HOST_REPLAY_SHARD_D2H_BYTES,
                "bytes evacuated from this shard's own device into its "
                "own ring (zero cross-shard lane scatter)",
                {"loop": "host_replay", "shard": str(shard)})

    def start(self, records: Any) -> _EvacJob:
        """Dispatch the slice split + async host copies for one chunk.
        Cheap and non-blocking; call from the thread that owns the
        dispatch order (the training loop), BEFORE the next device
        program is enqueued, so the transfers overlap its compute."""
        jax = self._jax
        leaves, treedef = jax.tree_util.tree_flatten(records)
        C = int(leaves[0].shape[0])
        key = (treedef, C)
        split = self._split_cache.get(key)
        if split is None:
            bounds = _slice_bounds(C, self.num_slices)

            def _split(tree):
                return tuple(
                    jax.tree_util.tree_map(lambda x: x[lo:hi], tree)
                    for lo, hi in bounds)

            # No donation: the slice outputs cannot alias the [C, ...]
            # input buffer (XLA would warn every run); the records
            # buffer frees when the caller drops its reference anyway.
            split = (jax.jit(_split), bounds)
            self._split_cache[key] = split
        split_fn, bounds = split
        slices = split_fn(records)
        flat_slices = []
        for s in slices:
            s_leaves = jax.tree_util.tree_leaves(s)
            for x in s_leaves:
                copy_async = getattr(x, "copy_to_host_async", None)
                if copy_async is not None:
                    copy_async()
            flat_slices.append(s_leaves)
        return _EvacJob(flat_slices, bounds, treedef,
                        submitted_at=time.perf_counter())

    def drain(self, job: _EvacJob, on_slice: Callable[[Any, int, int], None],
              on_slice_done: Optional[Callable[[int], None]] = None) -> dict:
        """Fetch + publish every slice of ``job`` in time order; returns
        per-chunk stats. Runs on the evacuation worker thread (or inline
        for a synchronous caller)."""
        jax = self._jax
        nbytes = 0
        for i, (leaves, (lo, hi)) in enumerate(zip(job.slices, job.bounds)):
            host = [np.asarray(x) for x in leaves]
            nbytes += sum(h.nbytes for h in host)
            job.slices[i] = None  # release the device slice promptly
            on_slice(jax.tree_util.tree_unflatten(job.treedef, host),
                     lo, hi)
            self.slices_total += 1
            self._c_slices.inc()
            if on_slice_done is not None:
                on_slice_done(i)
        self.bytes_total += nbytes
        self._c_bytes.inc(nbytes)
        if self._c_shard_bytes is not None:
            self._c_shard_bytes.inc(nbytes)
        return {"bytes": nbytes, "slices": len(job.bounds),
                "evac_s": time.perf_counter() - job.submitted_at}


class EvacuationWorker:
    """Background D2H evacuation (ISSUE 3 tentpole #3): drains
    ``StreamedEvacuator`` jobs on a daemon thread so transfer waits and
    ring appends never block ``sample_host``/``train_jit`` dispatches.

    ``submit(records)`` runs ``evacuator.start`` on the CALLER's thread
    (dispatch-order ownership, see ``start``) and queues the drain;
    the returned job doubles as the completion handle the loop fences
    on (``job.wait()``). A worker exception fails the in-flight job AND
    every queued one, re-raises from ``wait()``/the next ``submit()``,
    and exits the thread — no silent half-appended chunks, no hang.
    """

    def __init__(self, evacuator: StreamedEvacuator,
                 on_slice: Callable[[Any, int, int], None],
                 name: str = "host_replay",
                 shard: Optional[int] = None):
        self._evac = evacuator
        self._on_slice = on_slice
        self._q: "queue.Queue" = queue.Queue()
        self._exc: Optional[BaseException] = None
        # Stall-watchdog heartbeat (ISSUE 4): beaten per queue wake and
        # per published slice, so a worker wedged inside a transfer wait
        # or a ring append goes stale and the forensics stacks name the
        # "evac-<name>" thread. Idle is healthy: the drain loop wakes on
        # a queue timeout and beats even with nothing to do.
        self._hb = tm_watchdog.heartbeat(f"evac.{name}")
        self._flight = tm_flight.get_flight()
        self._name = name
        labels = {"loop": name}
        reg = get_registry()
        self._h_evac = reg.histogram(
            tm.HOST_REPLAY_EVAC_SECONDS,
            "per-chunk evacuation wall (submit -> last slice published)",
            labels)
        self._h_lag = reg.histogram(
            tm.HOST_REPLAY_SLICE_LAG_SECONDS,
            "slice publication lag behind its chunk's submission", labels)
        # Sharded collect (ISSUE 15): the per-shard evac gauge — the
        # last drained chunk's evacuation wall for THIS shard's lane
        # block, so a straggler shard shows up by label, not buried in
        # the fan-in max the loop's fence reports.
        self._g_shard_evac = None
        if shard is not None:
            self._g_shard_evac = reg.gauge(
                tm.HOST_REPLAY_SHARD_EVAC_SECONDS,
                "last chunk's evacuation wall for this shard's lane "
                "block", {"loop": "host_replay", "shard": str(shard)})
        self._thread = threading.Thread(
            target=self._run, name=f"evac-{name}", daemon=True)
        self._thread.start()

    def submit(self, records: Any) -> _EvacJob:
        if self._exc is not None:
            raise RuntimeError(
                "evacuation worker died; no further chunks can be "
                "evacuated") from self._exc
        if not self._thread.is_alive():
            raise RuntimeError("evacuation worker is closed")
        job = self._evac.start(records)
        self._flight.record("queue", f"evac.{self._name}.submit",
                            slices=len(job.bounds))
        self._q.put(job)
        return job

    def _get_beating(self):
        """Queue pop that beats the heartbeat while idle (an empty queue
        is healthy; a worker stuck mid-drain is the stall). The wake
        period stays well under the stage's deadline, or idling BETWEEN
        beats would itself read as a stall."""
        timeout = min(1.0, self._hb.deadline_s / 4.0)
        while True:
            self._hb.beat()
            try:
                return self._q.get(timeout=timeout)
            except queue.Empty:
                continue

    def _run(self) -> None:
        while True:
            job = self._get_beating()
            if job is None:
                self._hb.close()
                return
            try:
                # Chaos seam (ISSUE 8): exception exercises the
                # tombstone + fence-poisoning contract below with a
                # provenance-typed error; stall exercises the watchdog
                # (a sleep past the deadline = one bundle + 503, beats
                # resume = recovery) — both against the REAL drain path.
                ev = chaos.fire("evac.drain")
                if ev is not None:
                    if ev.fault == "exception":
                        raise chaos.ChaosInjectedError("evac.drain",
                                                       ev.fault)
                    chaos.sleep_for(ev)
                    chaos.mark_recovered("evac.drain")
                t0 = job.submitted_at

                def _lag(_i):
                    self._h_lag.observe(time.perf_counter() - t0)
                    self._hb.beat()

                stats = self._evac.drain(job, self._on_slice,
                                         on_slice_done=_lag)
                self._h_evac.observe(stats["evac_s"])
                if self._g_shard_evac is not None:
                    self._g_shard_evac.set(stats["evac_s"])
                self._flight.record("queue", f"evac.{self._name}.drained",
                                    slices=stats["slices"],
                                    bytes=stats["bytes"],
                                    evac_s=round(stats["evac_s"], 4))
                job._finish(stats)
            except BaseException as e:  # propagate, never hang the fence
                self._exc = e
                self._flight.record("queue", f"evac.{self._name}.failed",
                                    error=f"{type(e).__name__}: {e}")
                job._fail(e)
                # Stay alive as a tombstone: every job already queued or
                # racing a submit() past the _exc check fails immediately
                # instead of stranding its fence. close() still exits.
                # Tombstone passes still beat — a DEAD worker re-raises
                # loudly from submit()/wait(); the watchdog hunts the
                # silent kind.
                while True:
                    pending = self._get_beating()
                    if pending is None:
                        self._hb.close()
                        return
                    pending._fail(e)

    def close(self) -> None:
        """Stop the worker and join. Queued jobs finish first; after a
        worker death this returns immediately (the thread is gone). The
        stage heartbeat deregisters with the thread — a closed worker is
        not a stall."""
        self._q.put(None)
        self._thread.join()
        self._hb.close()

    @property
    def failed(self) -> Optional[BaseException]:
        return self._exc


class SamplePrefetcher:
    """Background sample-ahead pipeline (ISSUE 5 tentpole): the H2D twin
    of ``EvacuationWorker``. A daemon thread executes
    ``sample_fn(k) -> (host_batch, aux)`` work items and stages each
    result through an internal ``DoubleBufferedStager`` (reusable
    page-warm host buffers, async ``device_put``); the training loop
    pops device-resident batches in strict ``k`` order.

    Determinism contract: batch ``k``'s content must be a pure function
    of ``(k, ring window)`` — callers derive batch ``k``'s RNG from a
    per-index stream split from the run seed
    (``np.random.SeedSequence(seed, spawn_key=(k,))``), never from a
    shared stateful generator. That is what makes the prefetched path
    BIT-IDENTICAL to the serial sample-in-loop reference: thread timing
    can change WHEN a batch is drawn, never WHAT it contains.

    Generation-fence handshake: ``request(n, min_generation)`` tags the
    work with the ring generation the upcoming train event fenced on.
    The worker blocks on ``wait_generation(min_generation)`` before
    sampling (so a request issued ahead of the publication simply
    waits), and ``pop(min_generation)`` re-checks the tag the sample
    actually carried: a batch sampled against an OLDER window is
    counted (``dqn_host_replay_stale_batches_total``), dropped, and
    re-sampled at the fenced window on the calling thread — stale data
    is never trained on silently, and the counter makes any occurrence
    visible. ``depth`` bounds host memory and how far sampling runs
    ahead of training, exactly like the stager it wraps.

    Failure contract mirrors ``EvacuationWorker``: a worker exception
    re-raises from ``pop()``/``request()`` and the thread drains to a
    tombstone so ``close()`` never hangs.
    """

    def __init__(self, sample_fn: Callable[[int], Tuple[Any, Any]],
                 depth: int = 2, name: str = "host_replay",
                 wait_generation: Optional[Callable] = None,
                 device_put: Optional[Callable] = None):
        if depth < 1:
            raise ValueError(f"prefetch depth must be >= 1, got {depth}")
        import jax  # deferred: keep the module importable without jax

        self._jax = jax
        self._sample_fn = sample_fn
        self._wait_gen = wait_generation
        self._put = device_put if device_put is not None \
            else jax.device_put
        self.depth = int(depth)
        self._stager = DoubleBufferedStager(depth=depth, name=name,
                                            device_put=device_put)
        self._work: "queue.Queue" = queue.Queue()
        self._ready = threading.Semaphore(0)
        self._free = threading.Semaphore(depth)
        self._exc: Optional[BaseException] = None
        self._closing = False
        self._next_k = 0
        self.sample_s_total = 0.0
        self.wait_s_total = 0.0
        self.stale_total = 0
        self.sampled_total = 0
        labels = {"loop": name}
        reg = get_registry()
        self._h_sample = reg.histogram(
            tm.HOST_REPLAY_SAMPLE_SECONDS,
            "host-side sample+gather wall per batch (prefetcher thread "
            "when prefetching — off the critical path)", labels)
        self._h_wait = reg.histogram(
            tm.HOST_REPLAY_PREFETCH_WAIT_SECONDS,
            "main-thread wait for a prefetched batch (the sample-side "
            "share left on the critical path)", labels)
        self._c_stale = reg.counter(
            tm.HOST_REPLAY_STALE_BATCHES,
            "prefetched batches dropped for carrying a ring generation "
            "older than the train event's fence", labels)
        self._g_depth = reg.gauge(
            tm.HOST_REPLAY_PREFETCH_DEPTH,
            "device-resident batches staged ahead of the learner",
            labels)
        self._hb = tm_watchdog.heartbeat(f"prefetch.{name}")
        self._flight = tm_flight.get_flight()
        self._name = name
        self._thread = threading.Thread(target=self._run,
                                        name=f"prefetch-{name}",
                                        daemon=True)
        self._thread.start()

    def __len__(self) -> int:
        """Batches staged and not yet popped (observed prefetch depth)."""
        return len(self._stager)

    @property
    def next_k(self) -> int:
        """The next batch index request() will hand out — the caller's
        RNG-stream cursor."""
        return self._next_k

    def seek(self, k: int) -> None:
        """Fast-forward the batch-index cursor (checkpoint resume,
        ISSUE 8): batch RNG streams are per-index, so a resumed run
        must continue the killed run's index sequence, not restart at
        0. Only valid while idle — requested-but-unpopped work would
        make the cursor jump ambiguous."""
        if self._work.qsize() or len(self._stager):
            raise RuntimeError("seek() on a prefetcher with work in "
                               "flight")
        self._next_k = int(k)

    @property
    def bytes_staged(self) -> int:
        """Host bytes copied through the internal staging buffers."""
        return self._stager.bytes_staged

    def request(self, n: int, min_generation: int) -> None:
        """Enqueue the next ``n`` batch indices, to be sampled against a
        ring window of at least ``min_generation``. Call once per train
        event, after fencing the chunk whose data the event must see."""
        if self._exc is not None:
            raise RuntimeError(
                "sample prefetcher died; no further batches can be "
                "prefetched") from self._exc
        if self._closing or not self._thread.is_alive():
            raise RuntimeError("sample prefetcher is closed")
        for _ in range(int(n)):
            self._work.put((self._next_k, int(min_generation)))
            self._next_k += 1

    def _beat_timeout(self) -> float:
        return min(0.5, self._hb.deadline_s / 4.0)

    def _resample(self, k: int, min_generation: int) -> Tuple[Any, Any]:
        """Stale-batch backstop: re-draw batch ``k`` on the CALLING
        thread once the ring reaches ``min_generation``. Rare by
        construction (the loop gates appends on sampling), so the
        direct ``device_put`` here skips the staging pool."""
        deadline = time.monotonic() + 30.0
        while True:
            if self._wait_gen is not None:
                reached = self._wait_gen(
                    min_generation,
                    timeout=max(deadline - time.monotonic(), 0.0))
            else:
                # No fence waiter provided: poll with a backoff instead
                # of hot-looping full re-draws.
                reached = True
            if reached:
                host_batch, aux = self._sample_fn(k)
                if getattr(aux, "generation", min_generation) \
                        >= min_generation:
                    return self._put(host_batch), aux
            if time.monotonic() >= deadline:
                raise RuntimeError(
                    f"prefetch batch {k} waited 30s for ring "
                    f"generation {min_generation} which never "
                    "published — appends stopped while a train event "
                    "still expected them")
            if self._wait_gen is None:
                time.sleep(0.01)

    def pop(self, min_generation: int) -> Tuple[Any, Any]:
        """Next batch in ``k`` order -> (device_batch, aux). Blocks for
        the worker; drops + re-samples batches tagged with a generation
        older than ``min_generation``."""
        t0 = time.perf_counter()
        while not self._ready.acquire(timeout=0.1):
            if self._exc is not None:
                # Re-raise the worker's own exception (the
                # _EvacJob.wait discipline): the loop surfaces the real
                # cause, not a wrapper.
                raise self._exc
            if self._closing or not self._thread.is_alive():
                raise RuntimeError("sample prefetcher is closed")
        device_batch, (k, aux) = self._stager.pop()
        self._free.release()
        if getattr(aux, "generation", min_generation) < min_generation:
            self.stale_total += 1
            self._c_stale.inc()
            self._flight.record(
                "queue", f"prefetch.{self._name}.stale", k=k,
                sampled_gen=int(aux.generation),
                required_gen=int(min_generation))
            device_batch, aux = self._resample(k, min_generation)
        self._g_depth.set(len(self._stager))
        dt = time.perf_counter() - t0
        self.wait_s_total += dt
        self._h_wait.observe(dt)
        return device_batch, aux

    def _run(self) -> None:
        timeout = self._beat_timeout()
        while True:
            self._hb.beat()
            try:
                item = self._work.get(timeout=timeout)
            except queue.Empty:
                if self._closing:
                    self._hb.close()
                    return
                continue
            if item is None:
                self._hb.close()
                return
            k, min_gen = item
            try:
                # Fence handshake: never sample a window older than the
                # one the train event will fence on.
                if self._wait_gen is not None:
                    while not self._wait_gen(min_gen, timeout=timeout):
                        self._hb.beat()
                        if self._closing:
                            self._hb.close()
                            return
                while not self._free.acquire(timeout=timeout):
                    self._hb.beat()
                    if self._closing:
                        self._hb.close()
                        return
                # Chaos seam (ISSUE 8): the prefetcher's failure
                # contract (exception re-raises from pop()/request(),
                # tombstone drains, close() never hangs) and its stall
                # behavior, driven on the real worker thread.
                cev = chaos.fire("prefetch.sample")
                if cev is not None:
                    if cev.fault == "exception":
                        raise chaos.ChaosInjectedError("prefetch.sample",
                                                       cev.fault)
                    chaos.sleep_for(cev)
                    chaos.mark_recovered("prefetch.sample")
                t0 = time.perf_counter()
                host_batch, aux = self._sample_fn(k)
                dt = time.perf_counter() - t0
                self.sample_s_total += dt
                self.sampled_total += 1
                self._h_sample.observe(dt)
                self._stager.stage(host_batch, aux=(k, aux))
                self._g_depth.set(len(self._stager))
                self._ready.release()
            except BaseException as e:  # propagate, never hang a pop
                self._exc = e
                self._flight.record("queue",
                                    f"prefetch.{self._name}.failed",
                                    error=f"{type(e).__name__}: {e}")
                # Tombstone: drain remaining work so close() returns;
                # pop()/request() re-raise loudly.
                while True:
                    self._hb.beat()
                    try:
                        pending = self._work.get(timeout=timeout)
                    except queue.Empty:
                        if self._closing:
                            self._hb.close()
                            return
                        continue
                    if pending is None:
                        self._hb.close()
                        return

    def close(self) -> None:
        """Stop the worker and join; staged-but-unpopped batches are
        discarded. Safe after a worker death (the thread is already in
        its tombstone loop or gone)."""
        self._closing = True
        self._work.put(None)
        self._thread.join()
        self._hb.close()

    @property
    def failed(self) -> Optional[BaseException]:
        return self._exc

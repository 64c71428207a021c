"""Multi-host Ape-X: one learner service per host, gradients over DCN.

The pod-scale reading of BASELINE.json:9 ("distributed prioritized replay +
sharded/multi-learner"): every host runs its own ApexLearnerService — its
own actor fleet, trajectory assembly, and replay SHARD in host DRAM — and
the train step is ONE collective XLA program over the global device mesh:
each host feeds its shard's batch slice, gradients pmean across hosts
(ICI within a host slice, DCN between hosts), and params stay replicated
bit-identically everywhere. Ingestion stays fully asynchronous per host;
only training is in lockstep.

Cadence without a scheduler: hosts agree on global counters (transitions
inserted, readiness, env steps) through a tiny psum "agreement" collective.
Each host fires an agreement when its local clock says one is due and then
BLOCKS until every peer joins — calls therefore pair 1:1 across hosts by
construction (a host cannot complete agreement k+1 before its peers
completed k), and every host derives the SAME train-step target from the
SAME agreed numbers, so the collective train steps pair too. This replaces
the reference family's parameter-server/NCCL-group coordination with pure
SPMD + one scalar collective.

Requires a ``jax.distributed`` runtime (parallel/distributed.py). Used by
ApexLearnerService when ``jax.process_count() > 1``; single-process runs
never import this module.
"""
from __future__ import annotations

import os
import threading
from typing import Tuple

import numpy as np



class MultihostLearner:
    """Collective-learner machinery for one service process in the group."""

    def __init__(self, state_example_fn=None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        from dist_dqn_tpu.parallel import make_mesh

        self.jax = jax
        self.P = P
        self.NamedSharding = NamedSharding
        self.nprocs = jax.process_count()
        self.local_devices = jax.local_device_count()
        self.total_devices = jax.device_count()
        self.mesh = make_mesh(devices=jax.devices())  # dp over the pod
        self._repl = NamedSharding(self.mesh, P())
        self._agree = None
        # Set when an agreement collective times out: the daemon worker
        # thread is then permanently parked inside the psum, so issuing a
        # SECOND collective from this process could interleave with the
        # first and corrupt the group's collective ordering. Poisoning
        # makes that structurally impossible instead of relying on the
        # caller exiting promptly after the raise.
        self._agree_poisoned = False

    # -- init ---------------------------------------------------------------
    def wrap_init(self, init):
        """Learner init -> global REPLICATED state (identical inputs on
        every process; the jit is the group's first collective program)."""
        jax = self.jax
        jitted = jax.jit(init, out_shardings=self._repl)

        def replicated_init(rng, obs_example):
            return jitted(np.asarray(rng), np.asarray(obs_example))

        return replicated_init

    # -- train --------------------------------------------------------------
    def wrap_train_step(self, train_step, data_specs, metric_specs):
        """Per-device train step -> collective step over the global mesh.

        The returned fn takes THIS process's numpy batch shard (leading
        data axis = the local slice of the global batch), assembles global
        arrays with ``make_array_from_process_local_data``, runs the
        shard_map'd step (state replicated, data sharded over ``dp``,
        pmean inside — agents/), and returns (state, metrics) where
        ``metrics["priorities"]`` is this process's LOCAL slice as numpy.
        """
        jax = self.jax
        P = self.P
        mesh = self.mesh
        repl = P()

        def sharded(state, *data):
            state_spec = jax.tree.map(lambda _: repl, state,
                                      is_leaf=lambda x: x is None)
            # mesh-axis: data_specs/metric_specs name the dp axis
            # (parallel/learner.py train_step_specs).
            body = jax.shard_map(
                train_step, mesh=mesh,
                in_specs=(state_spec,) + data_specs,
                out_specs=(state_spec, metric_specs), check_vma=False)
            return body(state, *data)

        jitted = jax.jit(sharded, donate_argnums=0)

        def to_global(spec, x):
            x = np.asarray(x)
            return jax.make_array_from_process_local_data(
                self.NamedSharding(mesh, spec), x)

        def step(state, *host_data):
            gdata = tuple(
                jax.tree.map(to_global, spec, d)
                for spec, d in zip(data_specs, host_data))
            state, metrics = jitted(state, *gdata)
            prios = metrics.pop("priorities")
            # The local slice of the sharded priorities vector, in global
            # batch order (shards sorted by their global offset).
            shards = sorted(prios.addressable_shards,
                            key=lambda s: s.index[0].start or 0)
            metrics["priorities"] = np.concatenate(
                [np.asarray(s.data) for s in shards])
            return state, metrics

        return step

    # -- agreement ----------------------------------------------------------
    # Counter psums run in float32 on device (the repo never enables x64),
    # where integers are exact only below 2**24 — far too small for pod
    # counters. Each value is therefore split into base-2**14 limbs before
    # the collective: the low-limb SUM stays < 2**24 for up to 1024 hosts
    # (each low limb < 2**14), and the high-limb SUM stays < 2**24 because
    # each host's value is bounded by 2**38 // num_processes (so the summed
    # high limbs total < 2**38 / 2**14 = 2**24) — recombination is EXACT
    # for any GLOBAL total up to 2**38 ≈ 2.7e11.
    _LIMB = 1 << 14

    def agree(self, values: np.ndarray) -> np.ndarray:
        """Exact psum of small non-negative integer counters across
        processes. BLOCKS until every process joins — see module docstring
        for why this makes agreement calls pair 1:1 — but only up to
        ``DQN_AGREE_TIMEOUT_S`` (default 600s): a peer that died with an
        uncaught error would otherwise wedge every surviving host inside
        the collective forever. On timeout the process raises (and exits),
        which in turn times out the peers' agreements — the whole fleet
        fails loudly instead of hanging silently."""
        jax = self.jax
        P = self.P
        if self._agree_poisoned:
            raise RuntimeError(
                "agree() called after a previous agreement collective timed "
                "out; the worker thread may still be blocked inside that "
                "psum, so this learner is poisoned — restart the process")
        if self._agree is None:
            # donation: few-element counter psum, nothing worth donating
            # (caller reuses its input).
            self._agree = jax.jit(jax.shard_map(
                lambda x: jax.lax.psum(x, "dp"), mesh=self.mesh,
                in_specs=P("dp"), out_specs=P(), check_vma=False))
        ints = np.asarray(values, np.int64)
        # Low-limb exactness needs nprocs * 2**14 < 2**24 — enforce the
        # documented 1024-host ceiling rather than silently rounding.
        if self.nprocs > 1024:
            raise ValueError(
                f"agree() limb split is exact only up to 1024 hosts "
                f"(group has {self.nprocs}); widen the limb split first")
        # Per-host bound scaled by host count so the GLOBAL sum keeps the
        # high-limb exactness guarantee (see limb note above).
        limit = (1 << 38) // max(self.nprocs, 1)
        if (ints < 0).any() or (ints >= limit).any():
            raise ValueError(
                f"agree() counters out of per-host range [0, {limit}): "
                f"{ints}")
        limbs = np.stack([ints // self._LIMB, ints % self._LIMB]
                         ).astype(np.float32)  # [2, k]
        # Exactly one contributing row per PROCESS: device 0 carries the
        # values, other local devices zeros.
        local = np.zeros((self.local_devices,) + limbs.shape, np.float32)
        local[0] = limbs
        garr = self.jax.make_array_from_process_local_data(
            self.NamedSharding(self.mesh, P("dp")), local)
        result: dict = {}

        def collective():
            try:
                result["out"] = np.asarray(
                    self.jax.device_get(self._agree(garr)))[0]
            except Exception as e:  # noqa: BLE001 — re-raised on the caller
                result["err"] = e

        timeout_s = float(os.environ.get("DQN_AGREE_TIMEOUT_S", "600"))
        worker = threading.Thread(target=collective, name="mh-agree",
                                  daemon=True)
        worker.start()
        # <= 0 means "no timeout" (block forever, the pre-fix behavior).
        worker.join(timeout_s if timeout_s > 0 else None)
        if worker.is_alive():
            self._agree_poisoned = True
            raise RuntimeError(
                f"agreement collective incomplete after {timeout_s:.0f}s — "
                "a peer host likely died; failing fast instead of wedging "
                "the fleet (DQN_AGREE_TIMEOUT_S to tune)")
        if "err" in result:
            raise result["err"]
        out = result["out"]
        return out[0].astype(np.int64) * self._LIMB \
            + out[1].astype(np.int64)

    # -- host mirrors -------------------------------------------------------
    def host_copy(self, tree):
        """Replicated global pytree -> process-local numpy (for the local
        act/eval/priority-bootstrap programs, which must not touch global
        arrays)."""
        from dist_dqn_tpu.parallel.distributed import host_replica
        return host_replica(tree)

    def shard_batch_size(self, global_batch: int) -> Tuple[int, int]:
        """(this process's slice, per-device slice) of a global batch."""
        if global_batch % self.total_devices:
            raise ValueError(
                f"global batch {global_batch} must divide over "
                f"{self.total_devices} devices")
        per_dev = global_batch // self.total_devices
        return per_dev * self.local_devices, per_dev

"""Checkpoint/resume via orbax (SURVEY.md §5).

Failure model of the actor/learner architecture: actors are stateless
workers (they re-pull params after a restart), replay refills from live
experience, so the *learner state* — params, target params, optimizer
moments, step counters — is the recovery point. By DEFAULT checkpoints
hold the learner pytree plus the host-side training cursor (env
frames), not the replay ring.

The replay trade-off: a 65k-slot 84x84x4 pixel ring is ~1.8 GB vs
~7 MB of Nature-CNN learner state — ~260x the checkpoint bytes. Refill
on resume costs ``min_fill / steady-rate`` of training delay (the
rates are not measured on the current installation beyond PERF.md §5).
What refill does NOT recover is the ring's *contents* — a resumed run
trains on freshly generated experience, so it is statistically
equivalent, not bit-equal. Runs that need bit-exact resume (debugging, preemption-
heavy pods where distribution continuity matters) opt into
``train(..., checkpoint_replay=True)`` / ``--checkpoint-replay``,
which checkpoints the WHOLE fused carry (ring + env states + rng) at
ring-sized save cost; ``tests/test_checkpoint.py`` pins the bit-equal
resume property. The apex runtime's same flag
(``ApexRuntimeConfig.checkpoint_replay``) snapshots the host replay
shard beside the learner checkpoint (``replay/host.py state_dict``) —
warm-buffer, statistically-continuous resume; the async service is not
bit-replayable by design.

Orbax handles the pytree IO (async-capable, atomic renames, works with
sharded jax.Arrays on a mesh — global arrays are saved/restored with their
shardings, so a pod checkpoint restores onto the same mesh layout).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
import numpy as np
import orbax.checkpoint as ocp

from dist_dqn_tpu import chaos
from dist_dqn_tpu.types import PyTree


@dataclasses.dataclass
class TrainCheckpointer:
    """Periodic learner-state checkpoints with retention + resume.

    Usage:
      ckpt = TrainCheckpointer(dir, save_every_frames=100_000)
      start = ckpt.restore_latest(learner)   # (frames, learner) or None
      ...
      ckpt.maybe_save(frames, learner)       # inside the training loop
    """

    directory: str
    save_every_frames: int = 100_000
    max_to_keep: int = 3

    def __post_init__(self):
        self._mgr = ocp.CheckpointManager(
            self.directory,
            options=ocp.CheckpointManagerOptions(
                max_to_keep=self.max_to_keep, create=True),
        )
        self._next_save = 0
        self._meta_mgr = None  # lazy; only eval's restore_params needs it
        self._pytree_mgr = None  # lazy twin for params-only restores

    def maybe_save(self, frames: int, learner: PyTree) -> bool:
        """Save when the frame cursor crosses the next save boundary."""
        if frames < self._next_save:
            return False
        self.save(frames, learner)
        self._next_save = frames + self.save_every_frames
        return True

    def save(self, frames: int, learner: PyTree) -> None:
        """Save + stamp the atomic ``LATEST`` pointer (ISSUE 7).

        The pointer (step + param checksum + manifest hash) is written
        only after the save LANDED, so any reader that trusts it — the
        serving ModelStore watcher, evaluate's restores — addresses a
        complete checkpoint. The stamp rides a small background thread
        that blocks on ``wait_until_finished`` so the training loop
        keeps orbax's async-save overlap (ring-sized --checkpoint-replay
        carries would otherwise stall the loop for the full write);
        ``wait()``/the next ``save`` join it. A crash between commit
        and stamp leaves a stale pointer — ``latest_step`` guards by
        also consulting the orbax listing.

        Orbax surfaces an async save's failure exactly ONCE, from the
        first ``wait_until_finished`` — which is now the stamp thread's.
        The thread therefore captures any failure and the next join
        point (``save``/``wait``/``close``) re-raises it on the caller's
        thread, so a failed commit still fails the run instead of dying
        silently in a daemon thread.
        """
        import errno
        import threading

        self._join_pointer_stamp()
        # Chaos seam (ISSUE 8): "fail" is a disk-full save (the caller
        # must surface it, not train on silently); "crash_before_stamp"
        # commits the orbax step but never stamps LATEST — exactly the
        # crash window latest_step()'s listing fallback exists for.
        ev = chaos.fire("checkpoint.save")
        if ev is not None and ev.fault == "fail":
            raise OSError(errno.ENOSPC,
                          "chaos: injected disk-full on checkpoint save")
        self._mgr.save(frames, args=ocp.args.StandardSave(learner))
        # Checksum on the caller's thread: orbax has already snapshotted
        # the tree, and device-backed arrays stay off the side thread.
        checksum = _pointer_checksum(learner)
        if ev is not None and ev.fault == "crash_before_stamp":
            self._mgr.wait_until_finished()
            return

        def _stamp():
            try:
                self._mgr.wait_until_finished()
                write_latest_pointer(self.directory, frames,
                                     param_checksum=checksum)
                # A completed save + stamp proves recovery from any
                # earlier injected save/stamp fault.
                chaos.mark_recovered("checkpoint.save")
            except BaseException as e:  # re-raised at the next join
                self._ptr_error = e

        self._ptr_thread = threading.Thread(
            target=_stamp, name="checkpoint-latest-pointer", daemon=True)
        self._ptr_thread.start()

    def _join_pointer_stamp(self) -> None:
        t = getattr(self, "_ptr_thread", None)
        if t is not None:
            t.join()
            self._ptr_thread = None
        err = getattr(self, "_ptr_error", None)
        if err is not None:
            self._ptr_error = None  # surfaced once, like orbax's own
            raise err

    def wait(self) -> None:
        """Block until any async save landed (call before process exit)."""
        self._join_pointer_stamp()
        self._mgr.wait_until_finished()

    def all_steps(self) -> Tuple[int, ...]:
        """Retained checkpoint steps (frame cursors), oldest first."""
        return tuple(sorted(self._mgr.all_steps()))

    def delete(self, step: int) -> None:
        """Remove one retained step (ISSUE 12): a committed orbax step
        whose sidecar proved torn/unreadable is NOT a usable checkpoint
        — the resume path deletes it so the run can fall back to the
        previous step AND later re-save at the same frame cursor
        without orbax's StepAlreadyExists refusal."""
        self._join_pointer_stamp()
        self._mgr.delete(int(step))

    def latest_step(self) -> Optional[int]:
        """Newest COMPLETE checkpoint step: the max of the ``LATEST``
        pointer (when present and its step dir still exists) and orbax's
        directory listing. The pointer is what makes an in-progress save
        invisible (a complete-by-construction step id); the listing
        guards against a pointer left stale by a crash between a save's
        commit and its stamp — preferring a stale pointer outright would
        silently resume/serve older params than the newest complete
        checkpoint.
        """
        import os

        steps = []
        ptr = read_latest_pointer(self.directory)
        if ptr is not None:
            step = int(ptr["step"])
            if os.path.isdir(os.path.join(self.directory, str(step))):
                steps.append(step)
        mgr_step = self._mgr.latest_step()
        if mgr_step is not None:
            steps.append(int(mgr_step))
        return max(steps) if steps else None

    def restore_latest(self, example: PyTree, step: Optional[int] = None,
                       older: Optional[Tuple[PyTree, Callable]] = None
                       ) -> Optional[Tuple[int, PyTree]]:
        """Restore the newest checkpoint (or a specific retained ``step``
        from ``all_steps()``) as (frames, learner), or None.

        ``example`` is a live learner pytree of the target structure; its
        shapes/dtypes/shardings template the restore, so restoring onto a
        different mesh layout re-shards on load. ``older`` is
        ``(example, adopt)``: the tree an earlier program saved where it
        was another one, and how that tree restored becomes this one's
        (train_loop.py ``twin_obs_checkpoint``); tried where the
        checkpoint does not fit ``example``.
        """
        # The save schedule advances only on the latest-resume path: an
        # explicitly requested OLD step (the eval surfaces walk
        # all_steps()) must not regress _next_save and re-save over
        # newer retained steps (ADVICE round 3).
        advance_schedule = step is None
        if step is None:
            step = self.latest_step()
        if step is None:
            return None

        def restore(tree):
            abstract = jax.tree.map(
                lambda x: jax.ShapeDtypeStruct(
                    np.shape(x), x.dtype,
                    sharding=getattr(x, "sharding", None)),
                tree)
            return self._mgr.restore(
                step, args=ocp.args.StandardRestore(abstract))

        try:
            restored = restore(example)
        except ValueError as e:
            restored = None
            if older is not None:
                try:
                    restored = older[1](restore(older[0]))
                except ValueError:
                    pass    # the first refusal stands
            if restored is None:
                self._refuse(e)
        if advance_schedule:
            self._next_save = step + self.save_every_frames
        return int(step), restored

    @staticmethod
    def _refuse(e: ValueError) -> None:
        """Re-raise a restore's ValueError, the usual causes said first."""
        # Orbax's structure-mismatch error lists raw pytree paths;
        # the usual cause is a config drift, so say that first — but
        # only for actual structure mismatches; any other restore
        # ValueError (corruption, sharding mapping, ...) passes
        # through untouched.
        msg = str(e)
        if "not compatible with the stored shape" in msg:
            # The same tree, an array in another shape: refused,
            # never reinterpreted.
            raise ValueError(
                "checkpoint holds an array in another shape than this "
                "program stores it in: a whole-carry checkpoint "
                "(--checkpoint-replay) from before the device ring "
                "kept its per-step planes flat ([slots * lanes], once "
                "[slots, lanes]) cannot be resumed; start the run "
                f"again.\n\nOriginal error:\n{e}") from e
        if not ("structures do not match" in msg
                or "User-provided restore item" in msg):
            raise e
        raise ValueError(
            "checkpoint does not match the current config's learner "
            "structure — it was saved with a different network/"
            "optimizer architecture. Rebuild with the same --config "
            "and --set overrides used at save time.\n\nOriginal "
            f"error:\n{e}") from e

    def restore_params(self, example_params: PyTree,
                       step: Optional[int] = None,
                       prefix: Tuple[str, ...] = (),
                       member: Optional[int] = None
                       ) -> Optional[Tuple[int, PyTree]]:
        """Restore ONLY the policy parameters of a checkpoint.

        Deploy surfaces (evaluate) need the params to match the live
        network — the true requirement — but ``restore_latest`` also
        demands the optimizer/counter structure match, coupling eval
        invocations to training-only knobs (an lr schedule adds a count
        leaf to opt_state, so an eval without the exact training
        ``--set`` flags would fail its restore). This surface templates
        just the ``(*prefix, "params")`` subtree from the live example
        and partial-restores it; optimizer contents never constrain
        eval, and carry-kind checkpoints (``prefix=("learner",)``) no
        longer pay a ring-sized template either. Read-only: never
        advances the save schedule.

        Population checkpoints (ISSUE 20) hold an [M]-stacked params
        tree; ``member=k`` templates the stacked shape from the solo
        ``example_params``, restores the stack and returns member k's
        slice — so evaluate.py and the serving ModelStore serve any
        single member of a population run without knowing how to train
        one. Direction mismatches fail with the actual cause: a member
        request against a solo directory, or a member-less restore of a
        stacked directory (its leaves would come back [M]-leading and
        shape-mismatch the live net downstream).
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            return None
        pop_size = read_population_size(self.directory)
        if member is not None:
            if pop_size is None:
                raise ValueError(
                    f"member={member} requested but {self.directory!r} "
                    "is not a population checkpoint (no POPULATION "
                    "width marker) — drop the member selector")
            if not 0 <= member < pop_size:
                raise ValueError(
                    f"member={member} is out of range for a population-"
                    f"{pop_size} checkpoint (members are 0-based)")
        elif pop_size is not None:
            raise ValueError(
                f"{self.directory!r} holds a population-{pop_size} "
                "[M]-stacked tree — pass member=k (evaluate.py "
                "--member k) to extract one policy")
        default_dev = jax.local_devices()[0]
        stack = (pop_size,) if member is not None else ()
        live_abs = jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(
                stack + tuple(np.shape(x)), x.dtype,
                sharding=getattr(x, "sharding", None)
                or jax.sharding.SingleDeviceSharding(default_dev)),
            example_params)
        # Partial restore takes the INTERSECTION silently, so a network
        # drift in either direction (template leaves missing on disk, OR
        # on-disk heads the live net lacks) must be caught up front by
        # comparing the params subtree against the on-disk metadata —
        # otherwise a mismatched eval runs with wrong/unrestored params
        # instead of erroring.
        self._check_params_match(step, live_abs, prefix)
        rargs = ocp.checkpoint_utils.construct_restore_args(live_abs)
        item: Any = live_abs
        for key in reversed(prefix + ("params",)):
            item = {key: item}
            rargs = {key: rargs}
        restored = self._pytree_restore_mgr().restore(
            step, args=self._partial_restore_args(item, rargs))
        out = restored
        for key in prefix + ("params",):
            out = out[key]
        bad = [str(p) for p, leaf
               in jax.tree_util.tree_flatten_with_path(out)[0]
               if not hasattr(leaf, "addressable_data")
               and isinstance(leaf, jax.ShapeDtypeStruct)]
        if bad:  # defense in depth behind _check_params_match
            raise ValueError(
                f"checkpoint restore left {len(bad)} parameter leaves "
                f"unrestored (first: {bad[0]}) — network architecture "
                "drift between save and eval.")
        if member is not None:
            out = jax.tree.map(lambda x: x[member], out)
        return int(step), out

    def _pytree_restore_mgr(self):
        """Manager for params-only (PyTreeRestore) reads. The main
        manager registers its handlers from the save/StandardRestore
        args it has seen; on orbax 0.7.x its composite handler then
        REJECTS a PyTreeRestoreArgs restore outright ("does not match
        any registered handler"), so the partial restore needs its own
        manager with the PyTree handler registered explicitly — cached,
        like the metadata manager."""
        if self._pytree_mgr is None:
            self._pytree_mgr = ocp.CheckpointManager(
                self.directory,
                item_handlers=ocp.PyTreeCheckpointHandler())
        return self._pytree_mgr

    @staticmethod
    def _partial_restore_args(item, rargs):
        """Version-adaptive partial-restore args: orbax >= 0.11 spells
        it ``partial_restore=True``; 0.7.x (this container) only has
        the legacy transforms API, where an EMPTY ``transforms`` dict
        with an item tree that is a subset of the saved tree restores
        exactly that subset (verified against 0.7.0 — the deprecation
        warning it logs is the API's own, not a misuse)."""
        import inspect

        params = inspect.signature(
            ocp.args.PyTreeRestore.__init__).parameters
        if "partial_restore" in params:
            return ocp.args.PyTreeRestore(
                item, restore_args=rargs, partial_restore=True)
        return ocp.args.PyTreeRestore(
            item, restore_args=rargs, transforms={})

    def _check_params_match(self, step: int, live_abs: PyTree,
                            prefix: Tuple[str, ...]) -> None:
        """Raise the config-drift error unless the on-disk params
        subtree matches ``live_abs`` in structure, shape and dtype."""
        if self._meta_mgr is None:
            # The main manager has no handler registry (restore args
            # pick its handlers), so item_metadata on it returns None;
            # cache one metadata-capable manager for the whole walk.
            self._meta_mgr = ocp.CheckpointManager(
                self.directory,
                item_handlers=ocp.StandardCheckpointHandler())
        meta = self._meta_mgr.item_metadata(step)
        try:
            for key in prefix + ("params",):
                meta = meta[key]
        except (KeyError, TypeError) as e:
            raise ValueError(
                f"checkpoint at step {step} has no "
                f"{'/'.join(prefix + ('params',))} subtree — wrong "
                "checkpoint kind or directory") from e
        meta = jax.tree.map(lambda m: m, meta)  # plain containers
        live_paths = {
            tuple(str(k) for k in p): (tuple(leaf.shape), leaf.dtype)
            for p, leaf in jax.tree_util.tree_flatten_with_path(
                live_abs)[0]}
        disk_paths = {
            tuple(str(k) for k in p): (tuple(m.shape),
                                       np.dtype(m.dtype))
            for p, m in jax.tree_util.tree_flatten_with_path(meta)[0]}
        if live_paths != disk_paths:
            only_live = sorted(set(live_paths) - set(disk_paths))[:3]
            only_disk = sorted(set(disk_paths) - set(live_paths))[:3]
            shape_drift = sorted(
                k for k in set(live_paths) & set(disk_paths)
                if live_paths[k] != disk_paths[k])[:3]
            raise ValueError(
                "checkpoint parameters do not match the current config's "
                "network structure — it was saved with a different "
                "network architecture. Rebuild with the same --config "
                "and --set overrides used at save time.\n"
                f"param leaves only in the live net: {only_live}\n"
                f"only in the checkpoint: {only_disk}\n"
                f"shape/dtype drift: {shape_drift}")

    def close(self) -> None:
        try:
            # Re-raises a captured stamp/async-save failure — keep it
            # loud, but never at the cost of leaking the managers.
            self._join_pointer_stamp()
            self._mgr.wait_until_finished()
        finally:
            self._mgr.close()
            if self._meta_mgr is not None:
                self._meta_mgr.close()
                self._meta_mgr = None
            if self._pytree_mgr is not None:
                self._pytree_mgr.close()
                self._pytree_mgr = None


def checkpoint_tree(carry, whole: bool) -> PyTree:
    """What a fused run saves and restores: the learner, or (``whole``,
    ``--checkpoint-replay``) the carry as its fields by name WITHOUT those
    that hold no leaf — a feed-forward agent's actor state is ``()`` — so
    the tree on disk is the one written before that field existed and
    those checkpoints restore (``carry._replace(**restored)``)."""
    if not whole:
        return carry.learner
    return {k: v for k, v in carry._asdict().items() if jax.tree.leaves(v)}


class CheckpointMissingError(FileNotFoundError):
    """The requested checkpoint (dir or step) is absent. A distinct type
    so bounded-retry launchers (evaluate/serving --wait-for-checkpoint)
    and --all-steps walks can catch EXACTLY this condition without
    swallowing unrelated FileNotFoundErrors (missing ROM/asset) from
    the work itself (ADVICE round 3)."""


def wait_for_checkpoint(fn, wait_s: float, stop=None):
    """Run ``fn()``, bounded-retrying :class:`CheckpointMissingError`
    for up to ``wait_s`` seconds — the launched-alongside-training
    startup window shared by evaluate.py and the serving CLI. A 0
    budget keeps fail-fast single-attempt behavior; any other error
    stays loud on the first attempt. ``stop`` (a ``threading.Event``)
    aborts the wait early by re-raising the pending
    CheckpointMissingError — how the serving CLI's SIGTERM handler
    stays honored during a long startup wait instead of being ignored
    until the budget runs out."""
    import time

    deadline = time.monotonic() + max(wait_s, 0.0)
    while True:
        try:
            return fn()
        except CheckpointMissingError as e:
            remaining = deadline - time.monotonic()
            if remaining <= 0 or (stop is not None and stop.is_set()):
                raise
            print(f"# waiting for checkpoint ({e}); "
                  f"{remaining:.0f}s left", flush=True)
            nap = min(2.0, remaining)
            if stop is not None:
                if stop.wait(nap):
                    raise
            else:
                time.sleep(nap)


_LATEST_FILE = "LATEST"


def _pointer_checksum(tree: PyTree):
    """Cheap params digest for the ``LATEST`` pointer: the float64 fold
    of the policy-params subtree (the SAME rule as the loops'
    ``param_checksum`` pin anchors), or None when the saved tree has no
    recognizable params (custom pytrees). Carry-kind trees digest their
    nested learner's params — never the ring."""
    obj = tree
    if isinstance(obj, dict) and "learner" in obj:
        obj = obj["learner"]
    obj = getattr(obj, "learner", obj)
    params = getattr(obj, "params", None)
    if params is None and isinstance(obj, dict):
        params = obj.get("params")
    if params is None:
        return None
    try:
        return float(sum(
            np.float64(np.sum(np.asarray(jax.device_get(leaf),
                                         np.float64)))
            for leaf in jax.tree.leaves(params)))
    except Exception:
        # Provenance only — a params tree the host cannot materialize
        # (e.g. non-fully-addressable global arrays on a pod) must not
        # break the save; the pointer just carries no digest.
        return None


def write_latest_pointer(directory: str, step: int,
                         param_checksum=None) -> None:
    """Atomically (tmp + rename) stamp ``<directory>/LATEST`` with the
    newest COMPLETE checkpoint step, its param checksum and the run's
    manifest config hash — so readers (serving ModelStore watcher,
    evaluate) address the newest checkpoint without globbing step dirs
    and racing an in-progress save (ISSUE 7 satellite)."""
    import json
    import os
    import time

    from dist_dqn_tpu.telemetry.manifest import get_run_manifest

    man = get_run_manifest()
    payload = {
        "step": int(step),
        "param_checksum": param_checksum,
        "manifest_hash": man.get("config_hash") if man else None,
        "saved_unix": time.time(),
    }
    path = os.path.join(directory, _LATEST_FILE)
    ev = chaos.fire("latest.write")
    if ev is not None and ev.fault == "torn":
        # A torn stamp: half a JSON object lands as the final file
        # (crash mid-write on a filesystem without atomic rename
        # semantics). read_latest_pointer must reject it and every
        # reader must fall back to the orbax listing.
        with open(path, "w") as fh:
            fh.write(json.dumps(payload)[: max(4, len(str(step)))])
        return
    # Per-process tmp name: on multihost runs every process stamps the
    # shared dir after its save; a fixed tmp would let writers truncate
    # each other mid-write and rename a torn JSON into place. Distinct
    # tmps keep each os.replace atomic (last writer wins whole-file).
    tmp = f"{path}.tmp.{os.getpid()}"
    with open(tmp, "w") as fh:
        json.dump(payload, fh, sort_keys=True)
    os.replace(tmp, path)
    # A committed, well-formed stamp proves recovery from an earlier
    # injected torn write.
    chaos.mark_recovered("latest.write")


def checkpoint_present(directory: str) -> bool:
    """Cheap committed-checkpoint presence probe: the ``LATEST`` pointer
    or any committed digit-named step dir. No orbax manager (which would
    mkdir a typo'd path), no restore — the gate --wait-for-checkpoint
    loops poll so a retry never pays an env/network build just to find
    the directory still empty. In-progress orbax saves live under
    ``*.orbax-checkpoint-tmp-*`` names, so a digit-named dir is a
    committed step."""
    import os

    if not os.path.isdir(directory):
        return False
    if read_latest_pointer(directory) is not None:
        return True
    try:
        entries = os.listdir(directory)
    except OSError:
        return False
    return any(e.isdigit() and os.path.isdir(os.path.join(directory, e))
               for e in entries)


def read_latest_pointer(directory: str):
    """The parsed ``LATEST`` pointer dict, or None (absent — pre-pointer
    directory — or torn/corrupt, in which case readers fall back to the
    orbax directory listing)."""
    import json
    import os

    try:
        with open(os.path.join(directory, _LATEST_FILE)) as fh:
            payload = json.load(fh)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) or "step" not in payload:
        return None
    return payload


_KIND_FILE = "CHECKPOINT_KIND"


def record_checkpoint_kind(directory: str, kind: str) -> None:
    """Stamp what a checkpoint directory's items contain — ``learner``
    (the default recovery point), ``carry`` (--checkpoint-replay's
    whole fused carry) or ``host_loop`` (the host-replay runtime's
    whole-state {learner, carry} + npz sidecar, ISSUE 8). Restore
    paths read this to template correctly and to say THE ACTUAL CAUSE
    when the flavors mismatch, instead of orbax's structure error
    being rewrapped as a config drift."""
    import os

    path = os.path.join(directory, _KIND_FILE)
    existing = read_checkpoint_kind(directory)
    if existing is not None and existing != kind:
        raise ValueError(
            f"checkpoint directory {directory!r} holds {existing!r} "
            f"checkpoints but this run would write {kind!r} — the "
            "--checkpoint-replay flag differs from the run that created "
            "the directory. Resume with the same flag, or use a fresh "
            "--checkpoint-dir.")
    if existing is None:
        with open(path, "w") as fh:
            fh.write(kind)


def read_checkpoint_kind(directory: str):
    """The recorded kind, or None (pre-marker directories: learner-only
    by construction, since the marker landed with --checkpoint-replay)."""
    import os

    try:
        with open(os.path.join(directory, _KIND_FILE)) as fh:
            return fh.read().strip() or None
    except OSError:
        return None


_POPULATION_FILE = "POPULATION"


def record_population_size(directory: str, size: int) -> None:
    """Stamp a population run's member-axis width M (ISSUE 20). The
    stacked tree's leading [M] axis is checkpoint STRUCTURE: resuming a
    population-M' directory at a different --population would fail as
    an opaque orbax shape mismatch, so — like the kind marker above —
    the width is pinned up front and a mismatch says the actual cause
    (callers count it under dqn_checkpoint_refused_resumes_total with
    reason="population")."""
    import os

    existing = read_population_size(directory)
    if existing is not None and existing != size:
        raise ValueError(
            f"checkpoint directory {directory!r} holds a population-"
            f"{existing} stacked tree but this run trains --population "
            f"{size} — the member axis is part of the checkpoint "
            "structure. Resume with the same --population, use a fresh "
            "--checkpoint-dir, or extract single members with "
            "restore_params(member=k) / evaluate.py --member.")
    if existing is None:
        os.makedirs(directory, exist_ok=True)
        with open(os.path.join(directory, _POPULATION_FILE), "w") as fh:
            fh.write(str(int(size)))


def read_population_size(directory: str):
    """The recorded member width M, or None (solo directories — every
    pre-population checkpoint by construction)."""
    import os

    try:
        with open(os.path.join(directory, _POPULATION_FILE)) as fh:
            text = fh.read().strip()
    except OSError:
        return None
    return int(text) if text else None


def list_checkpoint_steps(directory: str) -> Tuple[int, ...]:
    """Retained checkpoint steps under ``directory``, oldest first,
    without keeping a manager open. Read-only surface: a missing
    directory raises instead of being created (the manager itself
    mkdirs, so guard before constructing it)."""
    import os

    if not os.path.isdir(directory):
        raise FileNotFoundError(
            f"no checkpoint found under {directory!r}")
    ckpt = TrainCheckpointer(directory)
    try:
        return ckpt.all_steps()
    finally:
        ckpt.close()


def atomic_savez(path: str, **arrays) -> None:
    """np.savez to ``path`` atomically (tmp + rename): a crash mid-write
    leaves the previous file, never a torn npz. The one shared writer
    for replay/sidecar snapshots — the host-replay SIDECAR save is the
    deliberate exception (it splices the ``sidecar.write`` chaos seam
    between its tmp write and the rename)."""
    import os

    import numpy as np

    tmp = f"{path}.tmp"
    with open(tmp, "wb") as fh:
        np.savez(fh, **arrays)
    os.replace(tmp, path)


def save_pytree(path: str, tree: PyTree) -> None:
    """One-shot pytree save (e.g. the --export-params deploy artifact).

    The checkpointer saves asynchronously; close (which blocks on the
    outstanding save) before returning so a CLI process can exit
    immediately after — a dropped instance races interpreter shutdown
    and loses the write."""
    with ocp.StandardCheckpointer() as ckptr:
        ckptr.save(path, tree, force=True)


def restore_pytree(path: str, example: PyTree) -> Any:
    abstract = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(np.shape(x), x.dtype,
                                       sharding=getattr(x, "sharding", None)),
        example)
    return ocp.StandardCheckpointer().restore(path, abstract)

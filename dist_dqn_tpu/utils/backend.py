"""The accelerator this process runs on, decided in one place.

Every entry point (train, evaluate, serving, atari57, chip_smoke.py,
the benchmark scripts) goes through here for the three things that depend
on the machine rather than on the config:

* where JAX keeps its persistent compilation cache, and which programs'
  cache keys hold their stage names;
* the ``{"platform", "kind", "count"}`` block a run logs first, so no log
  can be read as a chip run when it was not one;
* the refusal to take a device measurement on a CPU backend.

A chip belongs to one process: these helpers run in the process that will
use the device, never in a probe child.
"""
from __future__ import annotations

import contextlib
import json
import os
from pathlib import Path
from typing import Dict

import jax

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = Path(__file__).resolve().parents[2]


def enable_compile_cache() -> str:
    """Place the persistent compilation cache; returns the directory.

    Call before the first backend touch. Where ``JAX_COMPILATION_CACHE_DIR``
    is set JAX reads it itself and nothing is set in code. Otherwise the
    cache lives at ``<checkout>/.jax_cache`` — a fixed path, because the
    path is part of the cache key and a directory that moves never hits.
    """
    env_dir = os.environ.get(CACHE_ENV)
    if env_dir:
        return env_dir
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


@contextlib.contextmanager
def names_in_cache_key():
    """Compile inside this and the program's HLO metadata — scope names,
    source lines — is part of its persistent-cache key.

    JAX leaves metadata out of the key, so an executable compiled before a
    ``jax.named_scope`` existed, or before it was renamed, is served for
    the program that has it, with its OLD op_names: a stage table read from
    it (telemetry/stages.py) would be stale or empty. The chunk program is
    compiled inside this; every other program keeps JAX's key, so nothing
    else compiles again. The price: after an edit that shifts the traced
    code's source lines the first run compiles the chunk program even where
    its HLO did not change; warm runs are untouched."""
    flag = "jax_compilation_cache_include_metadata_in_key"
    before = getattr(jax.config, flag)
    jax.config.update(flag, True)
    try:
        yield
    finally:
        jax.config.update(flag, before)


def device_summary() -> Dict:
    """``{"platform", "kind", "count"}`` as JAX reports the default
    backend. Initialises the backend."""
    devices = jax.devices()
    return {"platform": devices[0].platform,
            "kind": devices[0].device_kind,
            "count": len(devices)}


def log_device() -> Dict:
    """Print an entry point's FIRST log line — ``{"device": {...}}`` as
    JAX reports it, so a run that landed on the CPU says so — and return
    the block."""
    dev = device_summary()
    print(json.dumps({"device": dev}), flush=True)
    return dev


def require_accelerator() -> Dict:
    """:func:`device_summary`, or RuntimeError when the default backend is
    the CPU — a device metric is never taken from a CPU run."""
    dev = device_summary()
    if dev["platform"] == "cpu":
        raise RuntimeError(
            "no accelerator: JAX's default backend is the CPU "
            f"({dev['count']} device(s)); this path reports device "
            "metrics and does not fall back")
    return dev


def select_platform(allow_cpu: bool) -> str:
    """The benchmark scripts' shared platform gate: ``allow_cpu`` forces
    the CPU backend (harness smokes at toy size); otherwise an
    accelerator is required. Returns the platform name for the rows."""
    if allow_cpu:
        jax.config.update("jax_platforms", "cpu")
        return "cpu"
    return require_accelerator()["platform"]

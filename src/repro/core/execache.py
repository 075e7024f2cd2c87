"""AOT executor cache: pre-planned statement serving (paper §3.1).

The daemon used to hand every statement shape a lazy
``jax.jit(fn, donate_argnums=0)`` callable and let the FIRST dispatch of
each (shape x device placement) pair pay a full XLA compile inside the
serving path — the reason every benchmark hand-rolled an unmeasured
warm-up loop. This module makes executors first-class:

* an :class:`ExecEntry` wraps the jitted callable together with a dict
  of **ahead-of-time compiled executables**
  (``jitted.lower(*avals).compile()``), keyed by a *placement token*
  (which device, or which mesh, the state lives on). Serving calls the
  ``Compiled`` object directly — in jax the live jit cache does NOT
  reuse AOT executables, so going through ``jitted(*args)`` would
  recompile;
* :meth:`ExecEntry.warm` lowers from **abstract avals** derived from the
  schema (state leaves become :class:`jax.ShapeDtypeStruct` carrying the
  lane/mesh sharding; scalar params stay concrete placeholders), so
  pre-planning needs no real state and never touches table contents;
* a cache-wide **schema epoch** replaces implicit dict-key drift:
  RESHARD / REINDEX / RESTORE (mesh re-placement) bump the epoch, which
  atomically retires every compiled executable — a stale executable can
  never be looked up again because the epoch is part of the entry key;
* hit / miss / compile counters surface through ``SHOW STATS t`` as the
  ``executors`` block, and a host-side *signature set* records which
  dispatch shapes are already planned — the scheduler's admission hook
  (``SQLCached.group_warm``) and ``EXPLAIN`` read it without any device
  sync.

Safety: a ``Compiled`` executable validates its inputs (aval, sharding,
committed device) BEFORE executing, and a mismatch raises without
consuming donated buffers — so :meth:`ExecEntry.__call__` can fall back
to the lazy jitted callable with the caller's state intact. Fallbacks
count as misses; correctness never depends on the AOT path.

Across processes, :func:`use_persistent_cache` points JAX's persistent
compilation cache at a fixed directory, so a restarted daemon loads the
executables it warmed last time instead of compiling them again.
"""
from __future__ import annotations

import os
import pathlib
import threading
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import telemetry as TEL
from repro.lint import lockorder as LK

# <checkout>/.jax_cache: a fixed path, because the directory is part of
# what a later process must find again
_DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def use_persistent_cache() -> str:
    """Turn on JAX's persistent compilation cache for this process and
    return its directory: ``JAX_COMPILATION_CACHE_DIR`` when it is set
    (JAX reads it itself, and no other directory is set here), else
    ``<checkout>/.jax_cache``. Every compile is cached, however short:
    the daemon's executors are many small programs, and each one
    recompiled on a cold start costs serving latency. Idempotent."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_DEFAULT_CACHE_DIR)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path

__all__ = ["ExecEntry", "ExecutorCache"]

# Input-validation errors a Compiled executable raises BEFORE running
# (wrong sharding/device -> ValueError, wrong arity/pytree structure ->
# TypeError). Anything else (e.g. XlaRuntimeError mid-flight) must
# propagate: the donated state may already be consumed.
_FALLBACK_ERRORS = (ValueError, TypeError)


class ExecEntry:
    """One executor: the lazy jitted callable plus its per-placement AOT
    executables. Instances are handed out by :meth:`ExecutorCache.get`
    and are direct replacements for the jitted callables the daemon used
    to memoize — calling one runs the statement."""

    __slots__ = ("_cache", "jitted", "compiled")

    def __init__(self, cache: "ExecutorCache", jitted: Callable):
        self._cache = cache
        self.jitted = jitted
        # placement token -> jax Compiled executable. Placement tokens
        # are host-side values (("dev", id) or ("mesh", (ids...))) — see
        # SQLCached._placement.
        self.compiled: dict[Any, Any] = {}

    # ------------------------------------------------------------- serving
    def __call__(self, *args, placement: Any = None):
        """Run the executor. Hit: replay the pre-planned executable for
        this placement. Miss: lower from the concrete call args (their
        avals ARE the runtime avals), compile once, store, run."""
        cache = self._cache
        comp = self.compiled.get(placement)
        if comp is None:
            cache.counters.add("misses")
            t0 = time.perf_counter()
            comp = self.jitted.lower(*args).compile()
            ms = (time.perf_counter() - t0) * 1e3
            cache.counters.add("compiles")
            cache.counters.add("compile_ms_total", ms)
            TEL.note_exec("compile", ms)
            self.compiled[placement] = comp
        else:
            cache.counters.add("hits")
            TEL.note_exec("hit")
        try:
            return comp(*args)
        except _FALLBACK_ERRORS:
            # aval/placement drift (e.g. a lane migrated devices between
            # key and call): input validation fired before execution, so
            # donated buffers are intact — serve through the lazy path.
            cache.counters.add("fallbacks")
            TEL.note_exec("fallback")
            return self.jitted(*args)

    # ------------------------------------------------------------- warm-up
    def warm(self, placement: Any, args: tuple) -> bool:
        """Pre-plan this executor for ``placement`` from ``args`` — a
        mix of abstract ``ShapeDtypeStruct`` leaves (state, carrying the
        target sharding) and concrete placeholder scalars/arrays whose
        avals match what dispatch will pass. Returns True when a new
        executable was compiled, False when one was already cached."""
        if placement in self.compiled:
            return False
        cache = self._cache
        t0 = time.perf_counter()
        comp = self.jitted.lower(*args).compile()
        cache.counters.add("compiles")
        cache.counters.add("compile_ms_total", (time.perf_counter() - t0) * 1e3)
        self.compiled[placement] = comp
        self._prime(comp, args)
        return True

    @staticmethod
    def _prime(comp: Any, args: tuple) -> None:
        """Run the fresh executable once on throwaway zero state
        (donation-safe: the zeros are ours, real table state is never
        touched) so the runtime's per-executable first-call work —
        argument-handler setup, the AOT call fastpath — is paid here,
        off the serving path, instead of by the first live statement."""
        def concretize(leaf):
            if isinstance(leaf, jax.ShapeDtypeStruct):
                z = jnp.zeros(leaf.shape, leaf.dtype)
                return z if leaf.sharding is None else jax.device_put(
                    z, leaf.sharding)
            return leaf
        try:
            dummy = jax.tree_util.tree_map(concretize, args)
            jax.block_until_ready(comp(*dummy))
        except Exception:  # noqa: BLE001 — priming is best effort
            pass


class ExecutorCache:
    """Per-table executor registry: epoch-keyed entries + counters.

    ``get(key, builder)`` memoizes like the old ``SQLCached._executor``
    dict, but the effective key is ``(epoch, key)`` — after
    :meth:`bump`, every old executable is unreachable by construction
    (the tentpole's "explicit invalidation instead of dict-key drift").
    """

    def __init__(self):
        self.epoch = 0
        self._entries: dict[Any, ExecEntry] = {}
        # dispatch signatures already pre-planned: (kind, stmt, bucket,
        # mode, placement). Host-only; read by scheduler admission and
        # EXPLAIN. Cleared on bump() with the entries they describe.
        self.sigs: set = set()
        # "<stmt>: <error>" for each CREATE-time background warm-up that
        # failed (e.g. a compile the backend refused), so SHOW STATS
        # shows it before the first live statement hits the same error
        self.warmup_errors: list[str] = []
        self._lock = LK.make_lock("execache.entries")
        # Atomic counters: the concurrent wave path increments these from
        # several worker threads at once (see telemetry.Counters).
        self.counters = TEL.Counters({"hits": 0, "misses": 0, "compiles": 0,
                                      "fallbacks": 0, "compile_ms_total": 0.0})

    @property
    def hits(self) -> int:
        return self.counters["hits"]

    @property
    def misses(self) -> int:
        return self.counters["misses"]

    @property
    def compiles(self) -> int:
        return self.counters["compiles"]

    @property
    def fallbacks(self) -> int:
        return self.counters["fallbacks"]

    @property
    def compile_ms_total(self) -> float:
        return self.counters["compile_ms_total"]

    # ------------------------------------------------------------- entries
    def get(self, key: Any, builder: Callable[[], Callable]) -> ExecEntry:
        """The entry for ``key`` under the current epoch, building its
        jitted callable on first use."""
        ek = (self.epoch, key)
        entry = self._entries.get(ek)
        if entry is None:
            with self._lock:
                entry = self._entries.get(ek)
                if entry is None:
                    entry = ExecEntry(self, builder())
                    self._entries[ek] = entry
        return entry

    def bump(self) -> int:
        """Retire every compiled executable (schema epoch bump). Called
        under the owning table's lock by RESHARD / REINDEX / RESTORE —
        anything that changes state shapes or device placement."""
        with self._lock:
            self.epoch += 1
            self._entries.clear()
            self.sigs.clear()
        return self.epoch

    # ---------------------------------------------------------- signatures
    def note_sig(self, sig: tuple) -> None:
        self.sigs.add(sig)

    def has_sig(self, sig: tuple) -> bool:
        return sig in self.sigs

    # --------------------------------------------------------------- stats
    def stats_dict(self) -> dict:
        """The ``executors`` block of ``SHOW STATS t``."""
        return {
            "cached": sum(len(e.compiled) for e in self._entries.values()),
            "entries": len(self._entries),
            "epoch": self.epoch,
            "hits": self.hits,
            "misses": self.misses,
            "compiles": self.compiles,
            "fallbacks": self.fallbacks,
            "compile_ms_total": round(self.compile_ms_total, 3),
            "warmup_errors": list(self.warmup_errors),
        }

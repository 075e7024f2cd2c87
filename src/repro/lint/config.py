"""Project-specific configuration for the reprolint rules.

Everything a rule needs to know about THIS codebase — which modules
form the serving path, which functions on them are hot, which attribute
names hold device state, which helpers are the blessed lock paths —
lives here, so the rule implementations in ``rules.py`` stay generic
AST analysis.

Module keys are the last two path components of a file
(``core/daemon.py``); the engine computes them in
``engine.ModuleContext``.
"""
from __future__ import annotations

import re

# ---------------------------------------------------------------------------
# REP001 — device-sync-on-serving-path

# The serving modules: every statement a client sends flows through
# exactly these five files (wire -> scheduler -> daemon -> executor
# cache, with telemetry riding along).
SERVING_MODULES = frozenset({
    "core/daemon.py",
    "core/scheduler.py",
    "core/protocol.py",
    "core/telemetry.py",
    "core/execache.py",
})

# The hot functions inside them. REP001 checks these (and any function
# nested in them); everything else in a serving module is management
# plane (CREATE/RESHARD/CHECKPOINT/SHOW ...), where a host sync is the
# documented cost of the operation. ``Result``/``_HostStack``
# materialization is deliberately absent: lazy first-access sync IS the
# engine's one sanctioned device round-trip (render stage).
SERVING_FUNCS: dict[str, frozenset] = {
    "core/daemon.py": frozenset({
        "execute", "execute_async", "executemany", "_dispatch_stmt",
        "_parse", "_table", "_intern_ast", "_prep_params", "_executor",
        "_placement", "_sig", "_note_sig", "_lane_of", "group_lane",
        "item_lanes", "_exec_mode", "_expire_flag", "_run_state",
        "_note_route", "_insert_sids", "_check_partition_update",
        "group_shard_ids", "_shard_ids_of", "_host_pval", "_insert_pvals",
        "group_warm", "_preplanned", "shape_key", "_shape_key_uncached",
        "_do_insert_batch", "_do_batch_dml", "_do_batch_select",
        "_do_batch_agg", "_do_select", "_do_update", "_do_delete",
        "_do_insert", "_jit_with_expiry", "_jit_exec",
    }),
    "core/scheduler.py": frozenset({
        "submit", "_plan", "_call_traced", "_run_single", "_locks_for",
        "_split_group", "_dispatch", "_dispatch_one", "_dispatch_inner",
        "_footprints_disjoint", "_compatible", "_is_cold",
        "_dispatch_wave", "_wait_for_arrivals", "_hold_window", "_loop",
    }),
    "core/protocol.py": frozenset({
        "_line", "_encode_arg", "_decode_arg", "_render_result",
        "_render_burst", "readline", "put_raw", "put_future", "_run",
        "_handle", "_mark_dropped",
    }),
    "core/telemetry.py": frozenset({
        "trace", "finish", "mark", "fold", "_fold_one", "_fold_loop",
        "record", "add", "max", "bulk", "bucket_of", "note_mode",
        "note_exec", "current_traces", "spans", "stage_totals", "child",
        "render_begin", "render_end", "_span",
    }),
    "core/execache.py": frozenset({
        "get", "__call__", "preplanned", "note_sig",
    }),
}

# Attribute names that hold device values (jax arrays / state pytrees):
# an expression reaching one of these is treated as device-tainted.
DEVICE_ATTRS = frozenset({
    "state", "lanes", "count_device", "row_ids_device", "present_device",
    "value_device", "payloads", "_dev",
})

# jax call chains that return HOST values (never device handles) — not
# taint sources.
HOST_JAX_CALLS = frozenset({
    "jax.devices", "jax.local_devices", "jax.device_count",
    "jax.local_device_count", "jax.default_backend",
    "jax.ShapeDtypeStruct", "jax.eval_shape",
})

# Sync sinks: calling one of these on (or with) a device-tainted value
# forces a device->host transfer or a blocking wait.
SYNC_METHOD_ALWAYS = frozenset({"block_until_ready"})
SYNC_METHOD_TAINTED = frozenset({"item", "tolist"})
SYNC_CALL_ALWAYS = frozenset({"jax.block_until_ready", "jax.device_get"})
SYNC_FN_TAINTED = frozenset({"int", "float", "np.asarray", "np.array",
                             "numpy.asarray", "numpy.array"})

# ---------------------------------------------------------------------------
# REP002 — bare shared-counter mutation outside telemetry.Counters

# Modules whose shared counters must go through telemetry.Counters.
COUNTER_MODULES_PREFIX = "core/"
COUNTER_MODULES_EXEMPT = frozenset({"core/telemetry.py"})
# A subscripted target whose base identifier matches this is a counter
# map (``stats["k"] += 1`` / ``counters[k] = counters[k] + 1``).
COUNTER_NAME_RE = re.compile(r"(^|_)(stats|counters|counts)$")

# ---------------------------------------------------------------------------
# REP003 — lock acquisition outside the ordered helper

# The one function allowed to CONSTRUCT scheduler lane/base locks ...
LOCK_BUILDER_FUNCS = frozenset({"_locks_for"})
# ... and the one allowed to acquire several of them (it consumes the
# helper's globally-ordered list: base first, lanes ascending).
MULTI_ACQUIRE_ALLOWED = frozenset({
    ("core/scheduler.py", "_dispatch_one"),
})
LOCK_MODULES_PREFIX = "core/"
# terminal identifier of a lock-ish expression: contains the token
# "lock"/"locks" as its own segment ("lock", "_lock", "fold_lock",
# "lock_a", "lanes_lock") — but NOT "clock"/"blocked"
LOCK_NAME_RE = re.compile(r"(^|_)r?locks?(_|$)", re.IGNORECASE)

# ---------------------------------------------------------------------------
# REP004 — host clock / randomness captured inside jit/pallas bodies

JIT_WRAPPER_SUFFIXES = ("jit", "pallas_call", "shard_map")
HOST_NONDET_CHAINS = (
    "time.", "random.", "np.random.", "numpy.random.", "os.urandom",
    "uuid.", "secrets.", "datetime.now", "datetime.utcnow",
)

# ---------------------------------------------------------------------------
# REP005 — leftover prints on the serving path

PRINT_MODULES = SERVING_MODULES | frozenset({
    "kernels/relscan.py", "kernels/hashidx.py", "kernels/ops.py",
})
PRINT_ALLOWED_FUNCS = frozenset({"main", "repl", "_main"})
PRINT_CHAINS = frozenset({"jax.debug.print", "pl.debug_print",
                          "debug.print"})

# ---------------------------------------------------------------------------
# REP006 — use-after-donation

# (module, function) -> {callee parameter name: donated positional args}.
# Inside these functions, a call through the named parameter donates the
# listed positional arguments (the daemon's executors are all built with
# ``jax.jit(fn, donate_argnums=0)``; ``_run_state`` receives them as
# ``fn``).
DONATING_PARAMS: dict[tuple, dict[str, tuple]] = {
    ("core/daemon.py", "_run_state"): {"fn": (0,)},
}

"""A cell cut to a size a test run holds: CAPACITY 4096 on the CPU, at
the configuration's ratio of records to pages to users."""
from __future__ import annotations

import contextlib
import signal
import time

import pytest

SMALL = {"rows": 3000, "pages": 900, "users": 30, "capacity": 4096,
         "load_batch": 1024}
MIX = {"warm_seconds": 0.5, "gap_seconds": 1.0}
RATE = {"rate_per_s": 120}


@contextlib.contextmanager
def deadline(seconds: int):
    """Fail the test, rather than hang it, past ``seconds``."""
    def expire(*_):
        raise TimeoutError(f"test ran past its {seconds} s limit")
    old = signal.signal(signal.SIGALRM, expire)
    signal.alarm(seconds)
    try:
        yield
    finally:
        signal.alarm(0)
        signal.signal(signal.SIGALRM, old)


@pytest.fixture
def small_env(monkeypatch, tmp_path):
    """No persistent compile cache, no CREATE-time background warm-up,
    and only the smallest grouped batch size warmed."""
    import jax
    from bench import harness
    monkeypatch.setenv("REPRO_WARMUP", "0")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    monkeypatch.setattr(harness, "BUCKETS", (2,))
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)


def drive(workload: str, seed: int, seconds: float = 2.0, rate=RATE,
          **kw):
    from bench import harness
    return harness.drive(workload, seed, seconds, False,
                         process_start=time.monotonic(), require_chip=False,
                         config_override=SMALL, cell_override=rate,
                         mix_override=MIX, **kw)

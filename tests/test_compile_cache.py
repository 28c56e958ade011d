"""Persistent compile cache (repro.obs.telemetry): robustness suite.

The disk layer's contract is *never crash, never trust*: any entry that
is truncated, bit-flipped, version-mismatched, or simply not a cache
entry at all is skipped (and evicted) with a silent fallback to
recompilation.  Writers are atomic (``os.replace``), so concurrent
processes racing on one key both leave valid blobs.  The in-process
layer is a bounded LRU.  ``JAX_COMPILATION_CACHE_DIR=""`` disables the
disk layer entirely.  Counters (:func:`cache_stats`) make all of it
observable.
"""
import os
import pickle
import subprocess
import sys
import textwrap
import threading
from functools import partial
from pathlib import Path

import numpy as np
import pytest

import repro
from repro.obs import telemetry
from repro.obs.telemetry import (CACHE_FORMAT, cache_dir, cache_stats,
                                 clear_caches, disk_cache_entries,
                                 reset_cache_stats, timed_compiled)


@pytest.fixture()
def cache(tmp_path, monkeypatch):
    """A fresh, isolated cache: empty tmp dir, empty memory LRU, zeroed
    counters."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    clear_caches(memory=True)
    reset_cache_stats()
    yield tmp_path
    clear_caches(memory=True)
    reset_cache_stats()


def _program(k=3):
    """A tiny jitted program; distinct static ``k`` = distinct program."""
    import jax
    import jax.numpy as jnp

    @partial(jax.jit, static_argnums=0)
    def poly(k, x):
        return x * k + jnp.cos(x)

    return poly, jnp.arange(8.0)


def test_miss_then_memory_then_disk(cache):
    poly, x = _program()
    out1, t1 = timed_compiled(poly, 3, x)
    assert t1["compile_cached"] is False and t1["compile_s"] > 0
    assert len(disk_cache_entries()) == 1
    out2, t2 = timed_compiled(poly, 3, x)
    assert t2["compile_cached"] == "memory" and t2["compile_s"] == 0.0
    clear_caches(memory=True)
    out3, t3 = timed_compiled(poly, 3, x)
    assert t3["compile_cached"] == "disk" and t3["compile_s"] > 0
    for out in (out2, out3):
        assert np.array_equal(np.asarray(out1), np.asarray(out))
    stats = cache_stats()
    assert stats["misses"] == 1 and stats["memory_hits"] == 1
    assert stats["disk_hits"] == 1 and stats["disk_writes"] == 1
    assert stats["disk_errors"] == 0


def test_entry_filename_is_versioned(cache):
    poly, x = _program()
    timed_compiled(poly, 3, x)
    (entry,) = disk_cache_entries()
    assert entry.name.endswith(f".v{CACHE_FORMAT}.exe")


@pytest.mark.parametrize("vandalize", [
    lambda p: p.write_bytes(p.read_bytes()[: p.stat().st_size // 2]),
    lambda p: p.write_bytes(b"\x00" * 64),
    lambda p: p.write_bytes(pickle.dumps(["not", "a", "dict"])),
    lambda p: p.write_bytes(pickle.dumps(
        {"format": CACHE_FORMAT + 1, "payload": b"stale"})),
], ids=["truncated", "garbage-bytes", "non-dict-pickle",
        "version-mismatch"])
def test_corrupt_entries_recompile_never_crash(cache, vandalize):
    poly, x = _program()
    out1, _ = timed_compiled(poly, 3, x)
    (entry,) = disk_cache_entries()
    vandalize(entry)
    clear_caches(memory=True)
    reset_cache_stats()
    out2, t2 = timed_compiled(poly, 3, x)
    assert t2["compile_cached"] is False          # skipped, recompiled
    assert np.array_equal(np.asarray(out1), np.asarray(out2))
    stats = cache_stats()
    assert stats["disk_errors"] >= 1 and stats["misses"] == 1
    # The bad blob was evicted and the fresh compile re-persisted over it.
    (entry,) = disk_cache_entries()
    assert pickle.loads(entry.read_bytes())["format"] == CACHE_FORMAT


def test_source_edit_invalidates_disk_entries(cache, monkeypatch):
    """The key covers a digest of the ``repro`` source tree: after a
    code change, the old executable must become unreachable (fresh
    compile under a new key), never a stale hit that silently computes
    the old program."""
    poly, x = _program()
    _, t1 = timed_compiled(poly, 3, x)
    assert t1["compile_cached"] is False
    clear_caches(memory=True)
    monkeypatch.setattr(telemetry, "_source_digest", lambda: "deadbeef")
    _, t2 = timed_compiled(poly, 3, x)
    assert t2["compile_cached"] is False
    # Both versions' entries coexist (distinct keys) until LRU pruning.
    assert len(disk_cache_entries()) == 2


def test_empty_cache_dir_disables_disk_layer(cache, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "")
    assert cache_dir() is None
    poly, x = _program()
    _, t1 = timed_compiled(poly, 3, x)
    clear_caches(memory=True)
    _, t2 = timed_compiled(poly, 3, x)
    # No disk layer: both are fresh compiles and nothing was persisted.
    assert t1["compile_cached"] is False and t2["compile_cached"] is False
    assert disk_cache_entries() == []
    assert cache_stats()["disk_writes"] == 0


def test_cache_dir_env_resolution(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "over"))
    assert cache_dir() == tmp_path / "over"
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    # Unset: a fixed directory in the checkout, whatever HOME says.
    monkeypatch.setenv("HOME", str(tmp_path / "home"))
    checkout = Path(list(repro.__path__)[0]).resolve().parents[1]
    assert cache_dir() == checkout / ".jax_cache"


def test_memory_lru_is_bounded(cache, monkeypatch):
    monkeypatch.setattr(telemetry, "_CACHE_LIMIT", 3)
    poly, x = _program()
    for k in range(5):
        timed_compiled(poly, k, x)
    assert len(telemetry._CACHE) == 3
    assert cache_stats()["evictions"] == 2
    # Oldest program (k=0) was evicted from memory — but the disk layer
    # still has it, so re-acquisition is a disk hit, not a recompile.
    _, t = timed_compiled(poly, 0, x)
    assert t["compile_cached"] == "disk"
    # Most-recently-used (k=4) survived in memory.
    _, t = timed_compiled(poly, 4, x)
    assert t["compile_cached"] == "memory"


def test_disk_prune_bounds_entry_count(cache, monkeypatch):
    monkeypatch.setattr(telemetry, "_DISK_LIMIT", 3)
    poly, x = _program()
    for k in range(5):
        timed_compiled(poly, k, x)
        # mtime granularity: make the prune order deterministic.
        for i, p in enumerate(sorted(cache.glob("*.exe"))):
            os.utime(p, (k + i * 1e-3, k + i * 1e-3))
    assert len(disk_cache_entries()) <= 3


def test_concurrent_writers_and_readers_are_safe(cache):
    """Hammer one entry path from racing writer and reader threads:
    ``os.replace`` atomicity means a reader only ever observes a
    complete blob (or none), so every successful load must execute."""
    import jax

    poly, x = _program()
    timed_compiled(poly, 3, x)
    (path,) = disk_cache_entries()
    lowered = poly.lower(3, x)
    compiled = lowered.compile()
    expect = np.asarray(jax.block_until_ready(compiled(x)))
    failures = []

    def writer():
        for _ in range(20):
            telemetry._disk_store(path, compiled)

    def reader():
        for _ in range(20):
            loaded = telemetry._disk_load(path)
            if loaded is None:
                continue                      # racing unlink/replace: fine
            got = np.asarray(jax.block_until_ready(loaded(x)))
            if not np.array_equal(got, expect):
                failures.append(got)

    threads = [threading.Thread(target=writer) for _ in range(2)]
    threads += [threading.Thread(target=reader) for _ in range(2)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not failures
    assert telemetry._disk_load(path) is not None


def test_cli_cache_subcommand(cache, capsys):
    from repro.studies.__main__ import main
    poly, x = _program()
    timed_compiled(poly, 3, x)
    assert main(["cache"]) == 0
    out = capsys.readouterr().out
    assert str(cache) in out and "entries: 1" in out and "misses=1" in out
    assert main(["cache", "--clear"]) == 0
    assert "cleared 1 entries" in capsys.readouterr().out
    assert disk_cache_entries() == []


def test_second_process_restores_from_disk(cache):
    """The acceptance scenario end to end: a second interpreter, sharing
    only the cache directory, acquires the program from disk."""
    script = textwrap.dedent("""
        from functools import partial
        import jax
        import jax.numpy as jnp
        from repro.obs.telemetry import timed_compiled

        @partial(jax.jit, static_argnums=0)
        def poly(k, x):
            return x * k + jnp.cos(x)

        out, t = timed_compiled(poly, 11, jnp.arange(16.0))
        print("CACHED:", t["compile_cached"])
    """)
    src = os.path.dirname(os.path.abspath(list(repro.__path__)[0]))
    env = dict(os.environ, JAX_COMPILATION_CACHE_DIR=str(cache),
               PYTHONPATH=os.pathsep.join(
                   [src, os.environ.get("PYTHONPATH", "")]))
    runs = [subprocess.run([sys.executable, "-c", script], env=env,
                           capture_output=True, text=True, timeout=300)
            for _ in range(2)]
    for proc in runs:
        assert proc.returncode == 0, proc.stderr[-2000:]
    assert "CACHED: False" in runs[0].stdout
    assert "CACHED: disk" in runs[1].stdout

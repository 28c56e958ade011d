"""Runtime telemetry: compile-vs-execute timing, the compile cache, and
environment provenance.

The compile tax is ROADMAP item 1's whole problem: the compiled engine's
steady-state speedup is real, but a cold program build eats it.  This
module makes the split *measurable everywhere* and — via a persistent
on-disk executable cache — makes the tax a once-per-machine cost instead
of once-per-process:

* :func:`timed_compiled` wraps a jit-compiled function's invocation in
  JAX's ahead-of-time path (``lower() -> compile() -> call``), timing
  the compile and the execute separately.  Program acquisition goes
  through two cache layers:

  1. an in-process **memory** cache (LRU-bounded — a long sweep of
     distinct shapes must not pin unbounded device executables), and
  2. an on-disk **AOT** layer: compiled executables serialized with
     ``jax.experimental.serialize_executable`` under
     :func:`cache_dir` (``JAX_COMPILATION_CACHE_DIR`` when set, an empty
     value disabling it; otherwise ``<checkout>/.jax_cache``), keyed by
     a content digest of the program identity (see :func:`_disk_key`).
     Entries are versioned, written atomically (concurrent writers are
     safe — last writer wins and both blobs are valid), and loads are
     corruption-tolerant: a truncated, bit-flipped, or
     version-mismatched entry is skipped and the program recompiled,
     never crashed on and never trusted.

  The timing dict records which layer served the program:
  ``compile_cached`` is ``"memory"``, ``"disk"``, or ``False`` (fresh
  compile).  :func:`repro.sim.xengine.sweep` routes every program build
  through this path, so the field lands on ``RunStats.timing`` and
  persists into ``Result.provenance``.

* :func:`provenance` is the environment block each
  :class:`repro.studies.store.Result` persists: host, interpreter and
  library versions, cpu count, plus the run's timing dict — enough to
  interpret a stored wall-clock number months later on different
  hardware.

Timing dicts are plain JSON-scalars so they serialize into JSONL stores
and BENCH artifacts unchanged::

    {"backend": "jax", "compile_s": 0.11, "execute_s": 0.74,
     "total_s": 0.85, "compile_cached": "disk", "grid_points": 24}
"""
from __future__ import annotations

import hashlib
import os
import pickle
import platform
import tempfile
import time
from collections import OrderedDict
from functools import lru_cache
from pathlib import Path

import numpy as np

__all__ = ["timed_compiled", "provenance", "timing_dict", "cache_dir",
           "cache_stats", "reset_cache_stats", "clear_caches",
           "disk_cache_entries", "CACHE_FORMAT"]

#: Bump when the on-disk entry layout changes: old entries become
#: unreadable garbage to the new code, so the version participates in
#: both the key digest and the in-entry header (belt and braces — a
#: digest collision must still fail closed).
CACHE_FORMAT = 1

#: Compiled executables keyed by (function, static arg, arg avals), in
#: LRU order (oldest first).  Bounded: a process that really builds this
#: many distinct programs is sweeping shapes, and caching them all would
#: pin device memory — see :data:`_CACHE_LIMIT`.
_CACHE: OrderedDict = OrderedDict()
_CACHE_LIMIT = 64

#: On-disk entries kept before the oldest (by mtime) are pruned on the
#: next write.  Generous: xengine programs serialize to ~100 KB-1 MB.
_DISK_LIMIT = 256

#: Cache-layer counters, exposed for tests and the studies CLI.  Keys:
#: ``memory_hits``/``disk_hits``/``misses`` partition program
#: acquisitions; ``evictions`` counts memory-LRU drops; ``disk_writes``
#: successful entry writes; ``disk_errors`` unreadable/unwritable
#: entries (each one is a silent fallback to recompilation, never a
#: crash).
_STATS = {"memory_hits": 0, "disk_hits": 0, "misses": 0, "evictions": 0,
          "disk_writes": 0, "disk_errors": 0}


def cache_stats() -> dict:
    """A snapshot copy of the cache counters (see :data:`_STATS`)."""
    return dict(_STATS)


def reset_cache_stats() -> None:
    for k in _STATS:
        _STATS[k] = 0


#: The checkout holding the ``repro`` package (``<checkout>/src/repro``).
_CHECKOUT = Path(__file__).resolve().parents[3]


def cache_dir() -> Path | None:
    """The persistent compile-cache directory, or ``None`` when disabled.

    ``JAX_COMPILATION_CACHE_DIR`` places it, so the executables share the
    directory JAX's own persistent cache uses; the empty string disables
    the disk layer entirely (the memory cache still applies).  Unset, it
    is the fixed ``<checkout>/.jax_cache``.  The directory is created
    lazily on first write.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env is not None:
        return Path(env) if env else None
    return _CHECKOUT / ".jax_cache"


def disk_cache_entries() -> list[Path]:
    """The current cache directory's entry files (any format version)."""
    cdir = cache_dir()
    if cdir is None or not cdir.is_dir():
        return []
    return sorted(cdir.glob("*.exe"))


def clear_caches(*, memory: bool = True, disk: bool = False) -> None:
    """Drop cached executables.  ``disk=True`` also unlinks every entry
    in the current :func:`cache_dir` (tests use this to force cold
    compiles)."""
    if memory:
        _CACHE.clear()
    if disk:
        for p in disk_cache_entries():
            try:
                p.unlink()
            except OSError:
                pass


def timing_dict(backend: str, *, compile_s: float = 0.0,
                execute_s: float = 0.0, compile_cached=False,
                grid_points: int = 1) -> dict:
    """The canonical timing record (see the module docstring).  A batched
    program's dict is shared by every grid point it produced —
    ``grid_points`` says how many, so consumers can amortize.
    ``compile_cached`` is ``False`` for a fresh compile, else the cache
    layer that served the program (``"memory"`` or ``"disk"``)."""
    return {
        "backend": backend,
        "compile_s": round(float(compile_s), 6),
        "execute_s": round(float(execute_s), 6),
        "total_s": round(float(compile_s) + float(execute_s), 6),
        "compile_cached": (compile_cached if compile_cached else False),
        "grid_points": int(grid_points),
    }


def _aval_key(args) -> tuple:
    import jax
    leaves, treedef = jax.tree_util.tree_flatten(args)
    return (treedef,
            tuple((tuple(np.shape(leaf)),
                   str(getattr(leaf, "dtype", type(leaf).__name__)))
                  for leaf in leaves))


def _fn_ident(fn) -> str:
    inner = getattr(fn, "__wrapped__", fn)
    mod = getattr(inner, "__module__", "?")
    name = getattr(inner, "__qualname__",
                   getattr(inner, "__name__", repr(inner)))
    return f"{mod}.{name}"


@lru_cache(maxsize=1)
def _source_digest() -> str:
    """sha256 over every ``repro`` source file, computed once per
    process.  The function identity in :func:`_disk_key` names *which*
    program, not *which version of the code* built it — without this, an
    executable compiled from yesterday's engine silently satisfies
    today's edited one.  Hashing the whole package is deliberately
    conservative: an unrelated edit costs one recompile, while a stale
    executable computes the old program's results with no error."""
    import repro
    h = hashlib.sha256()
    for root in sorted(repro.__path__):
        root = Path(root)
        for p in sorted(root.rglob("*.py")):
            try:
                h.update(str(p.relative_to(root)).encode())
                h.update(p.read_bytes())
            except OSError:  # pragma: no cover - racing editor/cleanup
                continue
    return h.hexdigest()[:16]


def _env_header() -> dict:
    import jax
    import jaxlib
    return {"format": CACHE_FORMAT, "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "backend": jax.default_backend(),
            "device_kind": jax.devices()[0].device_kind,
            "src": _source_digest()}


def _disk_key(fn, static_arg, aval_key, key_extra) -> str:
    """Content digest naming a disk entry.  Anatomy (all parts must
    match for a hit): cache format version, jax + jaxlib versions, XLA
    backend and device kind, a digest of the ``repro`` source tree (so
    editing the engine invalidates executables it compiled — see
    :func:`_source_digest`), the wrapped function's qualified name, the
    static argument's ``repr`` (for xengine this is the :class:`XSpec` —
    every field of the compiled program's shape), the argument avals
    (treedef + shapes + dtypes), and the caller's ``key_extra`` (xengine
    passes a content digest of its topology tables, so two fabrics that
    merely share shapes do not share executables)."""
    payload = repr((sorted(_env_header().items()), _fn_ident(fn),
                    repr(static_arg), aval_key, key_extra))
    return hashlib.sha256(payload.encode()).hexdigest()[:40]


def _entry_path(digest: str) -> Path | None:
    cdir = cache_dir()
    if cdir is None:
        return None
    return cdir / f"{digest}.v{CACHE_FORMAT}.exe"


def _disk_load(path: Path):
    """Deserialize one entry; any failure — missing, truncated, corrupt,
    or version/backend-mismatched — returns ``None`` (recompile)."""
    try:
        with open(path, "rb") as f:
            entry = pickle.load(f)
        if not isinstance(entry, dict):
            raise ValueError("cache entry is not a dict")
        header = _env_header()
        if any(entry.get(k) != v for k, v in header.items()):
            # A well-formed entry at this path should match (the digest
            # covers the header); a mismatch means the file was tampered
            # with or collided — treat exactly like corruption.
            raise ValueError("cache entry header mismatch")
        from jax.experimental import serialize_executable as se
        payload = entry["payload"]
        return se.deserialize_and_load(*payload)
    except FileNotFoundError:
        return None
    except Exception:
        _STATS["disk_errors"] += 1
        try:
            path.unlink()
        except OSError:
            pass
        return None


def _disk_store(path: Path, compiled) -> None:
    """Serialize atomically: pickle to a unique temp file in the cache
    directory, then ``os.replace`` — readers never observe a partial
    entry, and two processes racing on one key both leave valid blobs
    (last writer wins).  Failures are counted, never raised."""
    tmp = None
    try:
        from jax.experimental import serialize_executable as se
        entry = dict(_env_header())
        entry["payload"] = se.serialize(compiled)
        entry["created"] = time.time()
        path.parent.mkdir(parents=True, exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                   prefix=".tmp-" + path.stem)
        with os.fdopen(fd, "wb") as f:
            pickle.dump(entry, f)
        os.replace(tmp, path)
        tmp = None
        _STATS["disk_writes"] += 1
        _disk_prune(path.parent)
    except Exception:
        _STATS["disk_errors"] += 1
        if tmp is not None:
            try:
                os.unlink(tmp)
            except OSError:
                pass


def _disk_prune(cdir: Path) -> None:
    """Keep the directory bounded: drop oldest-by-mtime entries past
    :data:`_DISK_LIMIT` (best-effort; racing unlinks are fine)."""
    try:
        entries = sorted(cdir.glob("*.exe"), key=lambda p: p.stat().st_mtime)
        for p in entries[:-_DISK_LIMIT]:
            try:
                p.unlink()
            except OSError:
                pass
    except OSError:  # pragma: no cover - directory vanished mid-prune
        pass


def _memory_insert(key, compiled) -> None:
    while len(_CACHE) >= _CACHE_LIMIT:
        _CACHE.popitem(last=False)
        _STATS["evictions"] += 1
    _CACHE[key] = compiled


def timed_compiled(fn, static_arg, *args, grid_points: int = 1,
                   key_extra=None) -> tuple:
    """Call ``fn(static_arg, *args)`` — a ``jax.jit(...,
    static_argnums=0)`` function — through the AOT path, returning
    ``(output, timing)`` where ``timing`` separates program acquisition
    from execution (:func:`timing_dict`).

    Acquisition checks the in-process LRU first
    (``compile_cached="memory"``, ``compile_s`` 0.0), then the on-disk
    AOT layer (``compile_cached="disk"``, ``compile_s`` = deserialize
    time — milliseconds, not seconds), and only then lowers + compiles
    (``compile_cached`` ``False``), writing the fresh executable back to
    disk for the next process.  A disk-restored executable is the same
    machine code the fresh compile produced, so its results are
    byte-identical (``tests/test_conformance.py`` pins this).
    Execution is timed to completion (``block_until_ready``), so
    ``execute_s`` is device time, not dispatch time.

    ``static_arg=None`` calls ``fn(*args)`` / ``fn.lower(*args)`` — for
    pre-specialized jitted callables (e.g. xengine's sharded runners,
    whose static spec is baked into the function); pass the spec through
    ``key_extra`` so the disk key still covers it.  ``key_extra`` is any
    repr-able value mixed into the disk digest (see :func:`_disk_key`).
    """
    import jax
    key = (fn, static_arg, _aval_key(args), repr(key_extra))
    compile_s = 0.0
    cached: str | bool = False
    if key in _CACHE:
        _CACHE.move_to_end(key)
        compiled = _CACHE[key]
        cached = "memory"
        _STATS["memory_hits"] += 1
    else:
        digest = _disk_key(fn, static_arg, key[2], key_extra)
        path = _entry_path(digest)
        compiled = None
        if path is not None:
            t0 = time.perf_counter()
            compiled = _disk_load(path)
            if compiled is not None:
                compile_s = time.perf_counter() - t0
                cached = "disk"
                _STATS["disk_hits"] += 1
        if compiled is None:
            t0 = time.perf_counter()
            lowered = (fn.lower(*args) if static_arg is None
                       else fn.lower(static_arg, *args))
            compiled = lowered.compile()
            compile_s = time.perf_counter() - t0
            _STATS["misses"] += 1
            if path is not None:
                _disk_store(path, compiled)
        _memory_insert(key, compiled)
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    execute_s = time.perf_counter() - t1
    return out, timing_dict("jax", compile_s=compile_s,
                            execute_s=execute_s, compile_cached=cached,
                            grid_points=grid_points)


def provenance(timing: dict | None = None, *, backend: str | None = None,
               spec_digest: str | None = None) -> dict:
    """The environment/provenance block persisted with results and
    benchmark artifacts: where and with what a number was produced,
    down to the device (``device`` is JAX's platform, ``device_kind``
    and device count)."""
    import jax
    dev = jax.devices()[0]
    out = {
        "host": platform.node(),
        "platform": platform.platform(),
        "python": platform.python_version(),
        "numpy": np.__version__,
        "cpu_count": os.cpu_count(),
        "jax": jax.__version__,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices())},
    }
    if backend is not None:
        out["backend"] = backend
    if spec_digest:
        out["spec_digest"] = spec_digest
    if timing is not None:
        out["timings"] = dict(timing)
    return out

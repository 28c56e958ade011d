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

* :func:`span` names one stretch of host work: a
  ``jax.profiler.TraceAnnotation`` on the profiler's host plane, on the
  device trace's clock, whose ``perf_counter`` seconds also add up under
  ``<name>_s`` in the open :func:`span_record`.  ``timed_compiled``
  opens ``sweep.acquire`` (the memory/disk/compile lookup) and
  ``sweep.execute`` (the call to ``block_until_ready``);
  :func:`repro.sim.xengine.sweep` and ``Study._run_jax`` open the rest
  (``sweep.traffic``, ``sweep.pack``, ``sweep.tables``,
  ``sweep.transfer``, ``sweep.fetch``, ``sweep.stats``,
  ``study.resolve``, ``study.records``) and merge the record into the
  grid's timing dict; ``repro.sim.workloads.moe_dispatch_workload``
  opens ``sweep.gate`` inside ``sweep.traffic``.  :func:`count` adds a
  counter to the same record.

* :func:`scope_maps` maps, for every program the memory cache holds,
  each optimized-HLO instruction to the ``jax.named_scope`` path it was
  traced under, keyed by the module name the device trace shows and the
  executable's fingerprint.  It is computed from the executable's HLO
  text on request only.

Timing dicts are plain JSON-scalars so they serialize into JSONL stores
and BENCH artifacts unchanged::

    {"backend": "jax", "compile_s": 0.11, "execute_s": 0.74,
     "total_s": 0.85, "compile_cached": "disk", "grid_points": 24,
     "sweep.acquire_s": 0.000012, "sweep.execute_s": 0.74, ...}
"""
from __future__ import annotations

import hashlib
import os
import pickle
import platform
import re
import tempfile
import time
from collections import OrderedDict
from contextlib import contextmanager
from functools import lru_cache
from pathlib import Path

import numpy as np

__all__ = ["timed_compiled", "provenance", "timing_dict", "cache_dir",
           "cache_stats", "reset_cache_stats", "clear_caches",
           "disk_cache_entries", "CACHE_FORMAT", "span", "span_record",
           "recorded_spans", "count", "scope_map", "scope_maps"]

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


#: The open span records, innermost last (see :func:`span_record`).
_RECORDS: list[dict] = []


@contextmanager
def span_record():
    """The record :func:`span` adds its seconds to: the one already open
    (so a sweep inside a study shares the study's record), else a new
    one, open until the block ends.  Yields a flat dict of ``<span>_s``
    floats, rounded like :func:`timing_dict`'s."""
    if _RECORDS:
        yield _RECORDS[-1]
        return
    rec: dict = {}
    _RECORDS.append(rec)
    try:
        yield rec
    finally:
        _RECORDS.pop()


def recorded_spans() -> dict:
    """A copy of the open :func:`span_record` (empty when none is
    open): what a timing dict merges in once its spans have closed."""
    return dict(_RECORDS[-1]) if _RECORDS else {}


@contextmanager
def span(name: str):
    """Host work named ``name``: a ``jax.profiler.TraceAnnotation`` in
    the profiler's trace (nothing is recorded when no profiler runs),
    and its ``perf_counter`` seconds added to ``<name>_s`` of the open
    :func:`span_record`, if any.  Costs microseconds: open it around
    per-grid work, never per cycle."""
    import jax
    t0 = time.perf_counter()
    try:
        with jax.profiler.TraceAnnotation(name):
            yield
    finally:
        if _RECORDS:
            rec = _RECORDS[-1]
            key = f"{name}_s"
            rec[key] = round(rec.get(key, 0.0)
                             + time.perf_counter() - t0, 6)


_HLO_INSTR = re.compile(r"^\s+(ROOT\s+)?(%[\w.\-]+) = (.*)$")
_HLO_COMP = re.compile(r"^(?:ENTRY\s+)?(%[\w.\-]+)\s.*\{$")
_HLO_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_HLO_CALLS = re.compile(r"\bcalls=(%[\w.\-]+)")
_HLO_TUPLE = re.compile(r"\btuple\([^%)]*(%[\w.\-]+)")


def _module_key(compiled) -> str:
    """``<module name>(<executable fingerprint, hex>)``.  The device trace
    names a launch ``<module name>(<runtime program id>)``, an id the
    executable does not expose; a trace's program is found by its module
    name, and among programs of one name by its instruction names."""
    exe = compiled.runtime_executable()
    fp = exe.fingerprint
    return f"{exe.hlo_modules()[0].name}({fp.hex() if fp else ''})"


def scope_map(compiled) -> tuple[str, dict]:
    """``(module key, {instruction: scope path})`` of one compiled
    executable, from its optimized HLO text.

    The scope path is the instruction's ``metadata={op_name=...}``: the
    ``jax.named_scope`` names it was traced under, between the
    transformation names (``jit(_run_loop)/while/body/.../route/gather``).
    An instruction that calls a computation (a fusion) takes the path of
    that computation's root, or of the root tuple's first operand, and
    its own when the root has none.  Instruction names are unique within
    a module, so fused and unfused instructions share one map."""
    own: dict[str, str] = {}
    calls: dict[str, str] = {}
    roots: dict[str, str] = {}
    tuple_first: dict[str, str] = {}
    comp = None
    for line in compiled.as_text().splitlines():
        m = _HLO_INSTR.match(line)
        if m is None:
            c = _HLO_COMP.match(line)
            if c is not None:
                comp = c.group(1)
            continue
        name, rest = m.group(2), m.group(3)
        op = _HLO_OP_NAME.search(rest)
        own[name] = op.group(1) if op else ""
        callee = _HLO_CALLS.search(rest)
        if callee is not None:
            calls[name] = callee.group(1)
        if m.group(1) and comp is not None:
            roots[comp] = name
            t = _HLO_TUPLE.search(rest)
            if t is not None:
                tuple_first[name] = t.group(1)

    def path(name: str) -> str:
        root = roots.get(calls.get(name))
        root = tuple_first.get(root, root)
        return (root and path(root)) or own[name]

    return _module_key(compiled), {name: path(name) for name in own}


def scope_maps() -> dict[str, dict]:
    """:func:`scope_map` of every program :func:`timed_compiled` holds in
    its memory cache (every program acquired in this process, up to the
    cache's bound), fresh compiles and disk restores alike.  Computed on
    each call."""
    return dict(scope_map(compiled) for compiled in _CACHE.values())


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


def count(name: str, value, *, largest: bool = False) -> None:
    """A counter of the open :func:`span_record`, if any: ``value``
    added to ``name``, or with ``largest`` the larger of the two kept.
    Counters ride in the grid's timing dict beside the span seconds
    (``moe_copies``, ``moe_pair_max_over_mean``)."""
    if _RECORDS:
        rec = _RECORDS[-1]
        if name in rec:
            value = max(rec[name], value) if largest else rec[name] + value
        rec[name] = value


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


def _acquire(fn, static_arg, args, key_extra) -> tuple:
    """``(executable, compile_s, compile_cached)`` from the memory cache,
    the disk layer, or a fresh compile (see :func:`timed_compiled`)."""
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
    return compiled, compile_s, cached


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

    Acquisition runs under the span ``sweep.acquire`` and the call under
    ``sweep.execute`` (:func:`span`); the open :func:`span_record` gets
    their seconds, and this timing dict only the fields above.
    """
    import jax
    with span("sweep.acquire"):
        compiled, compile_s, cached = _acquire(fn, static_arg, args,
                                               key_extra)
    with span("sweep.execute"):
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

"""What every cell shares: finding a cell's files by name, the device
gate, the clock, the profiler, the per-layer metric readers and the
result line.

A cell (an entry of ``BENCHMARK.json``'s ``workloads``) names a
configuration and a traffic mix.  Everything else is found by name:

* ``configs[].file`` -- the deployment (fabric, terminals, engine);
* ``bench/traffic/<traffic>.json`` -- the mix; its ``driver`` key names
  ``bench/drivers/<driver>.py``, which runs the cell;
* ``bench/limits/<cell>.json`` -- the limits of the numbers that decide
  ``correct``, and the control they were set against;
* ``bench/reference/mixes/<reference>.py`` -- the reference's packets of
  a simulator mix, named by the traffic file's ``reference`` key;
* ``bench/metrics/<metric>.py`` -- one reader per per-layer metric;
* ``bench/peaks.json`` -- the chip's peaks, keyed by ``device_kind``.
"""
from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from contextlib import contextmanager
from dataclasses import dataclass, field

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


class Refused(RuntimeError):
    """The run cannot produce a result (no chip, unknown cell, ...)."""


@dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list = field(default_factory=list)
    per_layer: list = field(default_factory=list)


def _load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load_json(os.path.join(root, "BENCHMARK.json"))


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: str = ROOT) -> Cell:
    bm = benchmark(root)
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise Refused(f"no workload {name!r} in BENCHMARK.json "
                      f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bm["configs"]}[w["config"]]
    cell = make_cell(name, os.path.join(root, cfg["file"]), w["traffic"],
                     int(w["chips"]), root)
    cell.end_to_end = [m for m in bm["end_to_end"] if _applies(m, name)]
    cell.per_layer = [m for m in bm["per_layer"] if _applies(m, name)]
    return cell


def make_cell(name: str, config_file: str, traffic: str, chips: int,
              root: str = ROOT) -> Cell:
    """A cell from its files alone, with no metrics: what
    ``load_cell`` finds for an entry of ``BENCHMARK.json``."""
    bench = os.path.join(root, os.path.basename(BENCH))
    return Cell(
        name=name, chips=chips, config=_load_json(config_file),
        traffic=_load_json(os.path.join(bench, "traffic",
                                        traffic + ".json")),
        limits=_load_json(os.path.join(bench, "limits", name + ".json")))


def load_module(kind: str, name: str):
    """``bench/<kind>/<name>.py`` as a module (``kind`` may be a nested
    directory, names may hold dots)."""
    path = os.path.join(BENCH, kind, name + ".py")
    if not os.path.exists(path):
        raise Refused(f"no {kind} module {name!r}: {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_{name}".replace("/", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    table = _load_json(os.path.join(BENCH, "peaks.json"))
    if device_kind not in table["devices"]:
        raise Refused(f"no peaks for device kind {device_kind!r} in "
                      f"bench/peaks.json")
    return table["devices"][device_kind]


def process_start() -> float:
    """Wall-clock time (``time.time()``) at which this process started,
    from ``/proc``; the interpreter's start-up counts as set-up."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - (uptime - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError):
        return time.time()


def device_gate(chips: int):
    """The first device must be a TPU and ``chips`` devices visible."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devs)}")
    if dev.platform != "tpu":
        raise Refused("no TPU visible; the benchmark never runs elsewhere")
    if len(devs) < chips:
        raise Refused(f"{chips} chips asked for, {len(devs)} visible")
    return devs[:chips]


def device_block(devices) -> dict:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "memory_peak_bytes": peak}


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@contextmanager
def span(name: str):
    """A host span in the profiler's trace (a no-op when not tracing)."""
    import jax
    with jax.profiler.TraceAnnotation(name):
        yield


class Profiler:
    """The ``--trace 1`` profiler session over the measured window;
    ``keep_dir`` also keeps a copy of the trace file."""

    def __init__(self, enabled: bool, out_dir: str,
                 keep_dir: str | None = None):
        self.enabled = enabled
        self.out_dir = out_dir
        self.keep_dir = keep_dir

    def __enter__(self):
        if self.enabled:
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(self.out_dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        if self.enabled:
            import jax
            import shutil
            jax.profiler.stop_trace()
            if self.keep_dir and self.path():
                os.makedirs(self.keep_dir, exist_ok=True)
                shutil.copy(self.path(), self.keep_dir)
        return False

    def path(self) -> str | None:
        import glob
        found = sorted(glob.glob(os.path.join(self.out_dir, "**",
                                              "*.xplane.pb"), recursive=True))
        return found[-1] if found else None


def read_metrics(cell: Cell, ctx: dict) -> dict:
    """Every per-layer metric of the cell whose reader finds something."""
    out = {}
    for m in cell.per_layer:
        value = load_module("metrics", m["name"]).read(ctx)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def checked(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """Each compared number beside its limit; ``correct`` iff every
    number is at or under its limit (a missing number fails)."""
    check = {}
    ok = True
    for name, limit in limits.items():
        value = numbers.get(name)
        passed = value is not None and value <= limit
        ok &= passed
        check[name] = {"value": value, "limit": limit}
    return ok, check


def print_check(check: dict) -> None:
    for name, c in check.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")

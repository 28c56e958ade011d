"""Reduce a profiler trace (``.xplane.pb``) to busy time, idle gaps and
device time per operation and per program.

Read with ``jax.profiler.ProfileData`` alone.  Device planes are named
``/device:TPU:<i>``; the operations that ran on a device are the events
of its ``XLA Ops`` line, and the programs (one event per launch) those of
its ``XLA Modules`` line.  Host spans are the harness's own
``TraceAnnotation`` events on the host plane.  All times share one clock
in nanoseconds.

* busy time: the union of a device's operation intervals inside the
  window, averaged over the devices used;
* idle gaps: the stretches of the first device's window that no
  operation covers, each labelled with the innermost harness span open
  at its middle;
* device time per operation name and per program, averaged over the
  devices used.
"""
from __future__ import annotations

from dataclasses import dataclass, field

#: The harness's own host spans, as they appear in the trace.
SPANS = ("setup", "window", "grid", "allreduce", "check")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float
    devices: int
    op_s: dict = field(default_factory=dict)        # name -> seconds
    module_s: dict = field(default_factory=dict)    # name -> seconds
    module_calls: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)        # (label, seconds)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def breakdown(self, top: int = 10) -> dict:
        """The operations with the most device time, each named by its
        HLO instruction (``%while.1``, not its whole text), and the
        longest idle gaps."""
        ops = sorted(self.op_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[k.split(" = ", 1)[0], v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def union_length(intervals, lo: float, hi: float) -> tuple[float, list]:
    """Covered length of ``intervals`` clipped to [lo, hi], and the
    uncovered stretches as (start, end) pairs."""
    covered = 0.0
    holes = []
    cur = lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            holes.append((cur, s))
        if e > cur:
            covered += e - max(s, cur)
            cur = e
    if hi > cur:
        holes.append((cur, hi))
    return covered, holes


def _label(spans, t: float) -> str:
    inner = [(e - s, n) for n, s, e in spans
             if s <= t <= e and n != "window"]
    return min(inner)[1] if inner else "window"


def reduce_trace(path: str, *, num_devices: int | None = None
                 ) -> TraceSummary | None:
    """The summary of the trace at ``path`` over the harness's
    ``window`` span (over the whole trace when there is none).  ``None``
    when the trace holds no device operation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans = []
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(ev.name, ev.start_ns, ev.end_ns)
                          for ev in line.events if ev.name in SPANS]
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = [(ev.name, ev.start_ns, ev.end_ns)
                   for ev in lines[OPS_LINE].events]
            mods = ([(ev.name, ev.start_ns, ev.end_ns)
                     for ev in lines[MODULES_LINE].events]
                    if MODULES_LINE in lines else [])
            if ops:
                devices.append((int(plane.name.rsplit(":", 1)[1]), ops,
                                mods))
    if not devices:
        return None
    devices.sort(key=lambda d: d[0])
    if num_devices is not None:
        devices = devices[:num_devices]
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for _, ops, _ in devices for _, s, _ in ops)
        hi = max(e for _, ops, _ in devices for _, _, e in ops)
    nd = len(devices)
    busy = 0.0
    op_s: dict = {}
    module_s: dict = {}
    module_calls: dict = {}
    gaps = []
    for i, (_, ops, mods) in enumerate(devices):
        covered, holes = union_length([(s, e) for _, s, e in ops], lo, hi)
        busy += covered
        for name, s, e in ops:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                op_s[name] = op_s.get(name, 0.0) + d
        for name, s, e in mods:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                module_s[name] = module_s.get(name, 0.0) + d
                module_calls[name] = module_calls.get(name, 0) + 1
        if i == 0:
            gaps = [(_label(spans, (s + e) / 2), (e - s) * 1e-9)
                    for s, e in holes]
    return TraceSummary(
        window_s=(hi - lo) * 1e-9, busy_s=busy / nd * 1e-9, devices=nd,
        op_s={k: v / nd * 1e-9 for k, v in op_s.items()},
        module_s={k: v / nd * 1e-9 for k, v in module_s.items()},
        module_calls={k: v // nd for k, v in module_calls.items()},
        gaps=gaps)

"""Reduce a profiler trace (``.xplane.pb``) to device time per named
scope, seconds per program host span, and labelled idle gaps, with the
device events moved onto the host's clock.

Read with ``jax.profiler.ProfileData`` alone, beside ``tracereduce.py``
(whose numbers this module leaves as they are).

* clock offset: the device's timestamps and the host's differ by a
  constant.  A launch is matched across the two by its ``run_id``: the
  host's ``DoEnqueueProgram`` starts before the device's ``XLA Modules``
  event starts, and the host's ``CompleteCallbacks`` starts after it
  ends.  So the offset (host time minus device time) is at least
  max(enqueue start - module start) and at most min(callback start -
  module end) over the matched launches.  Device events are shifted by
  the midpoint of the two bounds, for gap labels and scope attribution
  only; with no matched launch, nothing is shifted.
* leaf ops: an op of a device's ``XLA Ops`` line whose interval holds no
  other op of that line.  A ``%while`` or ``%conditional`` holds the
  ops of its body, so only leaf ops count device time once.
* scope: each leaf op belongs to the program launch (``XLA Modules``
  event) that holds its midpoint.  The program's scope map
  (``repro.obs.telemetry.scope_map``: instruction -> ``op_name`` path,
  a fusion taking its root's) names the scope: the first component of
  the path that is one of the asked-for scope names.  A leaf op whose
  path names none of them is unscoped.  The trace names a launch by
  its module name and a runtime id the executable does not expose, so
  a launch takes the map of its module name that names the most of its
  ops' instructions (see :func:`module_map`).
* host spans: the program's own spans (``sweep.*``, ``study.*``;
  ``repro.obs.telemetry.span``) summed over the window, and idle gaps
  labelled by the innermost harness or program span open at their
  middle.

The traced window is the harness's ``window`` span.  Metric readers
reach the trace through :func:`traced_run`: the driver that runs a
cell holds its profiler (``harness.Profiler``) in a local variable.
"""
from __future__ import annotations

import sys
from dataclasses import dataclass, field

from tracereduce import MODULES_LINE, OPS_LINE, SPANS, _label, union_length

#: The stages of the compiled cycle step (``xengine._step``).
STAGES = ("rng", "eject", "route", "arbitrate", "move", "sample")
#: The phases of the LACIN all-reduce (``core/collectives.py``).
PHASES = ("reduce_scatter", "all_gather")
#: The program's own host spans, by prefix (``repro.obs.telemetry.span``).
PROGRAM_PREFIXES = ("sweep.", "study.")


def is_span(name: str) -> bool:
    """A host span a gap may be labelled with: the harness's or the
    program's."""
    return name in SPANS or name.startswith(PROGRAM_PREFIXES)


@dataclass
class ScopeSummary:
    #: (lower, upper) bound of host minus device time in ns; ``None``
    #: when no launch could be matched.
    offset_ns: tuple | None
    devices: int
    #: module -> scope -> leaf-op seconds (averaged over the devices);
    #: the key ``None`` holds the unscoped time.
    scope_s: dict = field(default_factory=dict)
    #: module -> leaf-op seconds (averaged over the devices).
    leaf_s: dict = field(default_factory=dict)
    #: ``<scope>:<instruction>`` -> leaf-op seconds, over every module.
    ops_s: dict = field(default_factory=dict)
    #: program span name -> seconds inside the window.
    span_s: dict = field(default_factory=dict)
    gaps: list = field(default_factory=list)        # (label, seconds)

    @property
    def clock_offset_ms(self) -> list | None:
        if self.offset_ns is None:
            return None
        return [self.offset_ns[0] * 1e-6, self.offset_ns[1] * 1e-6]

    def breakdown(self, top: int = 10) -> dict:
        """The leaf ops with the most device time, each named
        ``<scope>:<instruction>``, and the longest idle gaps."""
        ops = sorted(self.ops_s.items(), key=lambda kv: -kv[1])[:top]
        gaps = sorted(self.gaps, key=lambda g: -g[1])[:top]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in gaps]}


def leaf_ops(ops):
    """The ops (name, start, end) of one line that hold no other op of
    it.  An op holds another when it starts no later and ends no
    earlier; of two equal intervals the first listed holds the second.
    Ops of no duration hold nothing and are held by nothing."""
    ops = [op for op in ops if op[2] > op[1]]
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    holder = [False] * len(ops)
    stack = []
    for i in order:
        _, s, e = ops[i]
        while stack and ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and ops[stack[-1]][2] >= e:
            holder[stack[-1]] = True
        stack.append(i)
    return [op for op, h in zip(ops, holder) if not h]


def clock_offset(enqueues: dict, callbacks: dict, modules: dict
                 ) -> tuple | None:
    """Bounds of host minus device time from launches matched by
    ``run_id``: ``enqueues`` maps a run id to the first host enqueue's
    start, ``callbacks`` to the last callback's start, ``modules`` to
    the device event's (start, end)."""
    lo = [enqueues[r] - modules[r][0] for r in modules if r in enqueues]
    hi = [callbacks[r] - modules[r][1] for r in modules if r in callbacks]
    if not lo or not hi:
        return None
    return max(lo), min(hi)


def scope_of(path: str, scopes) -> str | None:
    for part in path.split("/"):
        if part in scopes:
            return part
    return None


def module_map(maps: dict, module: str, instrs: set) -> dict:
    """The scope map of the traced program ``module``, whose ops carry
    the instruction names ``instrs``: the map under the same key, else,
    among the maps of its module name (the key less its ``(<id>)``), the
    one that names the most of ``instrs``."""
    if module in maps:
        return maps[module]
    base = module.split("(", 1)[0]
    same = [m for k, m in maps.items() if k.split("(", 1)[0] == base]
    return max(same, key=lambda m: len(instrs & m.keys()), default={})


def _run_id(ev):
    for k, v in ev.stats:
        if k == "run_id":
            return int(v)
    return None


def reduce_scopes(path: str, maps: dict, scopes=STAGES, *,
                  num_devices: int | None = None) -> ScopeSummary | None:
    """The summary of the trace at ``path`` over the harness's
    ``window`` span, scopes named by ``maps`` (module -> instruction ->
    path).  ``None`` when the trace holds no device operation."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    spans, enqueues, callbacks = [], {}, {}
    devices = []
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if is_span(ev.name):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
                    elif ev.name == "DoEnqueueProgram":
                        r = _run_id(ev)
                        if r is not None:
                            enqueues[r] = min(enqueues.get(r, ev.start_ns),
                                              ev.start_ns)
                    elif ev.name == "CompleteCallbacks":
                        r = _run_id(ev)
                        if r is not None:
                            callbacks[r] = max(callbacks.get(r, ev.start_ns),
                                               ev.start_ns)
        elif plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            if OPS_LINE not in lines:
                continue
            ops = [(ev.name, ev.start_ns, ev.end_ns)
                   for ev in lines[OPS_LINE].events]
            mods = ([(ev.name, ev.start_ns, ev.end_ns, _run_id(ev))
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
    # Launches are matched on the first device.  With several devices a
    # run id may have an enqueue and a callback per device: the earliest
    # enqueue and the latest callback still bound the first device's.
    offset = clock_offset(
        enqueues, callbacks,
        {r: (s, e) for _, s, e, r in devices[0][2] if r is not None})
    shift = 0.0 if offset is None else (offset[0] + offset[1]) / 2
    windows = [(s, e) for n, s, e in spans if n == "window"]
    if windows:
        lo, hi = windows[0]
    else:
        lo = min(s for _, ops, _ in devices for _, s, _ in ops) + shift
        hi = max(e for _, ops, _ in devices for _, _, e in ops) + shift
    nd = len(devices)
    scope_s: dict = {}
    leaf_s: dict = {}
    ops_s: dict = {}
    gaps = []
    for i, (_, ops, mods) in enumerate(devices):
        mods = sorted((s + shift, e + shift, name) for name, s, e, _ in mods)
        leaves = sorted((s + shift, e + shift, name)
                        for name, s, e in leaf_ops(ops))
        in_module: dict = {}
        k = 0
        for s, e, name in leaves:
            d = min(e, hi) - max(s, lo)
            if d <= 0:
                continue
            mid = (s + e) / 2
            while k < len(mods) and mods[k][1] < mid:
                k += 1
            module = (mods[k][2] if k < len(mods) and mods[k][0] <= mid
                      else None)
            in_module.setdefault(module, []).append(
                (name.split(" = ", 1)[0], d))
        for module, found in in_module.items():
            m = (module_map(maps, module, {instr for instr, _ in found})
                 if module else {})
            per = scope_s.setdefault(module, {})
            for instr, d in found:
                scope = scope_of(m.get(instr, ""), scopes)
                per[scope] = per.get(scope, 0.0) + d
                leaf_s[module] = leaf_s.get(module, 0.0) + d
                key = f"{scope or 'unscoped'}:{instr}"
                ops_s[key] = ops_s.get(key, 0.0) + d
        if i == 0:
            _, holes = union_length(
                [(s + shift, e + shift) for _, s, e in ops], lo, hi)
            gaps = [(_label(spans, (s + e) / 2), (e - s) * 1e-9)
                    for s, e in holes]
    span_s: dict = {}
    for name, s, e in spans:
        if name.startswith(PROGRAM_PREFIXES):
            d = min(e, hi) - max(s, lo)
            if d > 0:
                span_s[name] = span_s.get(name, 0.0) + d * 1e-9
    return ScopeSummary(
        offset_ns=offset, devices=nd,
        scope_s={m: {k: v / nd * 1e-9 for k, v in per.items()}
                 for m, per in scope_s.items()},
        leaf_s={m: v / nd * 1e-9 for m, v in leaf_s.items()},
        ops_s={k: v / nd * 1e-9 for k, v in ops_s.items()},
        span_s=span_s, gaps=gaps)


# -- what the metric readers share -----------------------------------------

def traced_run() -> tuple | None:
    """``(trace path, driver locals)`` of the traced run whose driver
    called the reader: the frame of its ``run`` holds the harness's
    profiler.  ``None`` when no trace was written."""
    import harness
    frame = sys._getframe(1)
    while frame is not None:
        for v in frame.f_locals.values():
            if isinstance(v, harness.Profiler):
                path = v.path() if v.enabled else None
                return None if path is None else (path, frame.f_locals)
        frame = frame.f_back
    return None


def _telemetry():
    from repro.obs import telemetry
    return telemetry


def sim_summary(ctx) -> ScopeSummary | None:
    """The simulator cell's reduction, once per traced run, with the
    scope maps of the programs ``timed_compiled`` holds (none where the
    program has no ``scope_maps``)."""
    if "_scope_summary" not in ctx:
        run = traced_run()
        maps = getattr(_telemetry(), "scope_maps", dict)
        ctx["_scope_summary"] = (None if run is None
                                 else reduce_scopes(run[0], maps(), STAGES))
    return ctx["_scope_summary"]


def coll_summary(ctx) -> ScopeSummary | None:
    """The collective cell's reduction, once per traced run, with the
    scope map of a compile of the driver's own all-reduce call (the
    driver's ``f`` on its first bucket)."""
    if "_scope_summary" not in ctx:
        summary = None
        one = getattr(_telemetry(), "scope_map", None)
        run = traced_run()
        if one is not None and run is not None:
            path, local = run
            key, m = one(local["f"].lower(local["bufs"][0]).compile())
            summary = reduce_scopes(path, {key: m}, PHASES,
                                    num_devices=ctx.get("chips"))
        ctx["_scope_summary"] = summary
    return ctx["_scope_summary"]


def grid_program(ctx) -> str | None:
    """The grid program: the module with the most device time in the
    traced window, as ``step_ms.sim`` picks it."""
    s = ctx.get("summary")
    if s is None or not s.module_s:
        return None
    return max(s.module_s, key=s.module_s.get)


def stage_ms(ctx, stage: str) -> float | None:
    """Leaf-op device ms of ``stage`` in the grid program per simulated
    cycle of the window."""
    summary = sim_summary(ctx)
    module = grid_program(ctx)
    if summary is None or module is None or not ctx.get("cycles"):
        return None
    per = summary.scope_s.get(module, {})
    if not any(k is not None for k in per):
        return None
    return per.get(stage, 0.0) / ctx["cycles"] * 1e3


def unscoped_share(ctx) -> float | None:
    """Share of the grid program's leaf-op time in no stage, in %."""
    summary = sim_summary(ctx)
    module = grid_program(ctx)
    if summary is None or module is None:
        return None
    per = summary.scope_s.get(module, {})
    total = summary.leaf_s.get(module, 0.0)
    if total <= 0 or not any(k is not None for k in per):
        return None
    return 100.0 * per.get(None, 0.0) / total


def host_s_per_grid(ctx, names) -> float | None:
    """Seconds of the program spans ``names`` per window grid."""
    summary = sim_summary(ctx)
    grids = ctx.get("grids")
    if summary is None or not grids:
        return None
    found = [summary.span_s[n] for n in names if n in summary.span_s]
    if not found:
        return None
    return sum(found) / len(grids)


def phase_ms(ctx, phase: str) -> float | None:
    """Leaf-op device ms of ``phase`` per all-reduce call, averaged over
    the chips."""
    summary = coll_summary(ctx)
    if summary is None or not ctx.get("calls"):
        return None
    total = {}
    for per in summary.scope_s.values():
        for k, v in per.items():
            total[k] = total.get(k, 0.0) + v
    if phase not in total:
        return None
    return total[phase] / ctx["calls"] * 1e3

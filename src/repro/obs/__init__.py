"""``repro.obs`` — observability for the simulation stack.

Everything the engines report elsewhere is an end-of-run aggregate
(:class:`~repro.sim.metrics.RunStats`).  This package adds the
*instruments*: time-series traces of the fabric's dynamics, per-packet /
per-phase spans exported as Chrome trace-event JSON (loadable in
``ui.perfetto.dev``), and wall-clock + compile-vs-execute telemetry
around every compiled-engine program build.

==================  =======================================================
:mod:`.trace`       :class:`TraceConfig` / :class:`Trace` — the sampled
                    time-series channels both engines record (link loads,
                    queue occupancy, injections, deliveries) and the
                    derived series (utilization, backlog, in-flight)
:mod:`.spans`       Chrome trace-event builders: phase spans, per-packet
                    hop spans, counter tracks, schema validation
:mod:`.telemetry`   compile-vs-execute timing of jit programs
                    (:func:`timed_compiled`), the per-grid host spans
                    (:func:`span`), the programs' scope maps
                    (:func:`scope_maps`) and the environment
                    :func:`provenance` block study records persist
:mod:`.export`      one-call composition: a traced replay ->
                    Perfetto-loadable JSON with one lane per switch and
                    one span per phase
==================  =======================================================

Capture is engine-native: the numpy :class:`~repro.sim.engine.Engine`
samples at the end of each cycle, and :mod:`repro.sim.xengine` compiles
statically-shaped ring buffers into its loop (contiguous
``dynamic_update_slice`` rows, like its delivery log — zero scatters in
the hot path).  On drained deterministic workloads (collective replays
whose phases are matchings, one-shot permutations) the two engines'
traces agree *exactly*; ``tests/test_obs.py`` pins that.

Program tracing is separate from the simulated-time traces above: it
names the host work and the device stages of the program itself, on the
clock of a ``jax.profiler`` trace.  Every grid opens the host spans
``study.resolve``, ``sweep.traffic``, ``sweep.pack``, ``sweep.tables``,
``sweep.transfer``, ``sweep.acquire``, ``sweep.execute``, ``sweep.fetch``,
``sweep.stats`` and ``study.records`` (``jax.profiler.TraceAnnotation``;
their seconds also land in the grid's timing dict as ``<span>_s``).  The
compiled cycle step names its stages with ``jax.named_scope``: ``rng``,
``eject``, ``route``, ``arbitrate``, ``move``, and ``sample`` when time
series are traced; the LACIN all-reduce names ``reduce_scatter`` and
``all_gather``.  Capture them with::

    import jax
    with jax.profiler.trace("/tmp/prof"):
        Study(spec).run()
    # -> open the .xplane.pb (or pass create_perfetto_trace=True and open
    #    the perfetto_trace.json.gz) in ui.perfetto.dev

A device op carries only its HLO instruction name; :func:`scope_maps`
gives each instruction's scope for every program acquired in the
process.

Quickstart::

    from repro import sim
    from repro.obs import TraceConfig, export_perfetto, replay_trace_events

    fab = fabric.make_fabric("xor", 16)
    stats = fab.replay("all_to_all", trace=TraceConfig(packets=8))
    export_perfetto("replay.json", replay_trace_events(stats))
    # -> open replay.json in ui.perfetto.dev
"""
from .trace import Trace, TraceConfig, derive_backlog
from .spans import (counter_events, export_perfetto, packet_events,
                    phase_events, request_events, validate_trace_events)
from .telemetry import (cache_dir, cache_stats, clear_caches, provenance,
                        reset_cache_stats, scope_map, scope_maps, span,
                        span_record, timed_compiled)
from .export import link_classes, replay_trace_events

__all__ = [
    "Trace", "TraceConfig", "derive_backlog",
    "counter_events", "export_perfetto", "packet_events", "phase_events",
    "request_events", "validate_trace_events",
    "provenance", "timed_compiled", "span", "span_record", "scope_map",
    "scope_maps",
    "cache_dir", "cache_stats", "clear_caches", "reset_cache_stats",
    "link_classes", "replay_trace_events",
]

"""Collective replay: drive the packet simulator with LACIN schedules.

The paper's central claim is algebraic: isoport wiring makes every
1-factor step of a LACIN schedule contention-free
(:meth:`~repro.core.schedule.LacinSchedule.is_contention_free`), so an
all-to-all completes in exactly ``num_steps`` link-serialization cycles.
This module *measures* that claim: it converts the repo's own schedules
— a flat :class:`~repro.core.schedule.LacinSchedule`, the dimension-order
``all_to_all_grid`` step sequence of a HyperX, or the two-level
``all_reduce_two_level`` sequence of a Dragonfly
(:mod:`repro.fabric.collectives`) — into a :class:`Workload` and replays
it through the cycle-driven engines with queueing, credits, and VCs in
the loop.

A :class:`Workload` is an ordered list of *phases*.  Phase ``k``'s
packets become injection-eligible only once every packet of phases
``< k`` has been **delivered** (ejected at its destination) — the
bulk-synchronous discipline of a stepwise collective, where step ``k+1``
exchanges data that step ``k`` produced.  Both engines implement the
barrier natively (:class:`repro.sim.engine.Engine` gates injection
candidates on the released phase; :mod:`repro.sim.xengine` compiles the
whole replay, barrier included, into one program), and both report the
cycle at which each phase completed.

The headline comparison is measured completion against the schedule
algebra's contention-free lower bound (:attr:`Workload.ideal_cycles` =
the sum over phases of the busiest pair's messages = ``num_steps *
message_size`` for uniform messages): a phase that is a matching on its
fabric meets the bound exactly; the Dragonfly global steps —
``group_size`` flows sharing one global link — exceed it by precisely
the serialization the hierarchy trades for 1/a-sized payloads.

A phase's ``messages`` is one count for every pair, or one count per
pair: the uneven, redrawn-per-step exchange of a mixture-of-experts
layer's expert-parallel dispatch and combine
(:func:`moe_dispatch_workload`, DeepSeek-V3's node-limited gate on the
rows of a HyperX) is a replay like any other.

Entry points, lowest to highest level::

    w = Workload.from_schedule(make_schedule("xor", 16))
    w = collective_workload(fabric, "all_to_all", message_size=2)
    stats = replay(topo, "minimal", w)            # RunStats + replay fields
    stats = fabric.replay("all_to_all")           # one-call Fabric surface
    w = moe_dispatch_workload(hyperx_fab, MoESpec(), seed=7)

and declaratively, ``TrafficSpec("workload", {"collective": ...})`` runs
replays through :mod:`repro.studies` (the bundled ``collective_replay``
spec compares CIN-16 / HyperX-256 / Dragonfly-72), and
``TrafficSpec("workload", {"moe": {...}})`` draws the MoE exchange anew
from every grid point's seed (the bundled ``moe_dispatch`` spec).
"""
from __future__ import annotations

import math
from dataclasses import dataclass, fields
from typing import Mapping, Sequence

import numpy as np

from ..obs.telemetry import count, span
from .metrics import RunStats
from .traffic import Traffic

__all__ = ["Phase", "Workload", "collective_workload", "replay",
           "MoESpec", "moe_dispatch_workload",
           "noaux_tc"]


@dataclass(frozen=True)
class Phase:
    """One barrier-delimited step: ``messages`` packets per (src, dst) pair.

    ``src[i] -> dst[i]`` are the step's flows (idle devices simply do not
    appear).  A schedule step that is a matching has each switch at most
    once on each side; the replay machinery does not require that — the
    anisoport ``cyclic`` baseline and hierarchical global steps are plain
    permutations/flows — but every pair must be a real move
    (``src != dst``).

    ``messages`` is one count for every pair (an int), or a tuple with
    pair ``i``'s count at ``messages[i]`` (uneven steps, such as a
    dropless expert-parallel dispatch); every count is at least 1, so a
    builder drops the pairs that send nothing.
    """
    src: tuple[int, ...]
    dst: tuple[int, ...]
    messages: int | tuple[int, ...] = 1

    def __post_init__(self):
        if len(self.src) != len(self.dst):
            raise ValueError(f"phase src/dst length mismatch: "
                             f"{len(self.src)} != {len(self.dst)}")
        if isinstance(self.messages, (int, np.integer)):
            if self.messages < 1:
                raise ValueError(
                    f"messages must be >= 1, got {self.messages}")
        else:
            per_pair = tuple(int(m) for m in self.messages)
            if len(per_pair) != len(self.src):
                raise ValueError(
                    f"per-pair messages need one count per pair: "
                    f"{len(per_pair)} counts for {len(self.src)} pairs")
            if any(m < 1 for m in per_pair):
                raise ValueError("per-pair messages must be >= 1 (drop "
                                 "the pairs that send nothing)")
            object.__setattr__(self, "messages", per_pair)
        if any(a == b for a, b in zip(self.src, self.dst)):
            raise ValueError("a phase pair must move between distinct "
                             "switches (drop idle devices instead)")

    @property
    def uniform(self) -> bool:
        """Whether every pair sends the same count (an int ``messages``)."""
        return not isinstance(self.messages, tuple)

    @property
    def num_packets(self) -> int:
        if self.uniform:
            return len(self.src) * self.messages
        return sum(self.messages)

    @property
    def peak_messages(self) -> int:
        """The busiest pair's count: the cycles the phase needs at least
        on that pair's first link (the int itself for uniform phases)."""
        if self.uniform:
            return self.messages
        return max(self.messages, default=0)

    def pair_messages(self) -> np.ndarray:
        """Each pair's count, as an int64 array."""
        return (np.full(len(self.src), self.messages, dtype=np.int64)
                if self.uniform else np.asarray(self.messages, np.int64))


@dataclass(frozen=True)
class Workload:
    """A phase-structured closed workload over ``num_switches`` switches.

    Replay semantics: all of phase ``k``'s packets inject (at most one
    per terminal per cycle) once phases ``< k`` are fully delivered.
    The packet-level ``gen`` field of the emitted :class:`Traffic`
    stores the phase *ordinal* (the barrier it waits behind), not a
    wall-clock generation cycle.
    """
    name: str
    num_switches: int
    phases: tuple[Phase, ...]

    def __post_init__(self):
        for k, ph in enumerate(self.phases):
            for v in ph.src + ph.dst:
                if not 0 <= v < self.num_switches:
                    raise ValueError(
                        f"{self.name}: phase {k} references switch {v} "
                        f"outside [0, {self.num_switches})")

    @property
    def num_phases(self) -> int:
        return len(self.phases)

    @property
    def num_packets(self) -> int:
        return sum(ph.num_packets for ph in self.phases)

    @property
    def ideal_cycles(self) -> int:
        """Contention-free lower bound on completion, in cycles.

        Each phase needs at least its busiest pair's messages in cycles
        of link time on that pair's first link (one packet per directed
        link per cycle), and phases are barrier-serialized, so
        completion cannot beat the sum over phases — ``num_steps *
        message_size`` for uniform messages.  The bound is *met with
        equality* when every phase is contention-free on the fabric (one
        flow per directed link, e.g. 1-factor steps on the CIN that
        defined them, under minimal routing).
        """
        return sum(ph.peak_messages for ph in self.phases)

    # -- engine-facing form -------------------------------------------------
    def traffic(self) -> Traffic:
        """The closed :class:`Traffic` the engines replay.

        ``gen`` holds each packet's phase ordinal (its barrier), which
        also keeps the per-terminal FIFO order phase-monotone;
        ``offered == 0`` marks the workload closed, so engines default
        to drain mode.
        """
        if self.num_phases:
            src = np.concatenate([
                np.repeat(np.asarray(ph.src, dtype=np.int64), ph.messages)
                for ph in self.phases])
            dst = np.concatenate([
                np.repeat(np.asarray(ph.dst, dtype=np.int64), ph.messages)
                for ph in self.phases])
            gen = np.concatenate([
                np.full(ph.num_packets, k, dtype=np.int64)
                for k, ph in enumerate(self.phases)])
        else:
            src = dst = gen = np.zeros(0, dtype=np.int64)
        return Traffic(f"replay-{self.name}", src, dst, gen,
                       offered=0.0, horizon=max(self.num_phases, 1),
                       workload=self)

    def phase_cum(self, num_phases: int | None = None) -> np.ndarray:
        """Cumulative packet counts per phase (padded to ``num_phases``
        by repeating the total — padding phases complete instantly)."""
        counts = np.array([ph.num_packets for ph in self.phases],
                          dtype=np.int64)
        cum = np.cumsum(counts) if counts.size else np.zeros(0, np.int64)
        if num_phases is not None and num_phases > cum.size:
            total = cum[-1] if cum.size else 0
            cum = np.concatenate(
                [cum, np.full(num_phases - cum.size, total, np.int64)])
        return cum

    # -- constructors -------------------------------------------------------
    @classmethod
    def from_schedule(cls, schedule, *, message_size: int = 1,
                      name: str | None = None) -> "Workload":
        """One phase per step of a :class:`~repro.core.schedule.LacinSchedule`
        (idle devices — odd-N Circle — are dropped from their step)."""
        return cls(name or f"{schedule.instance}-{schedule.n}-a2a",
                   schedule.n,
                   tuple(_schedule_phases(schedule, message_size)))

    def to_dict(self) -> dict:
        return {"name": self.name, "num_switches": self.num_switches,
                "phases": [{"src": list(ph.src), "dst": list(ph.dst),
                            "messages": (ph.messages if ph.uniform
                                         else list(ph.messages))}
                           for ph in self.phases]}

    @classmethod
    def from_dict(cls, d: Mapping) -> "Workload":
        def messages(m):
            return int(m) if isinstance(m, (int, np.integer)) else tuple(m)
        phases = tuple(
            Phase(tuple(int(v) for v in ph["src"]),
                  tuple(int(v) for v in ph["dst"]),
                  messages=messages(ph.get("messages", 1)))
            for ph in d["phases"])
        return cls(str(d["name"]), int(d["num_switches"]), phases)


# ---------------------------------------------------------------------------
# Builders: the repo's own collective step sequences, per fabric family.
# ---------------------------------------------------------------------------

def _grid_phase_lists(dims: Sequence[int], schedules, coord_of, index_of,
                      message_size: int) -> list[list[Phase]]:
    """Per-dimension phase lists, innermost dimension first (the order
    :func:`repro.fabric.collectives.all_to_all_grid` composes): one
    phase per step of that dimension's schedule, exchanging along that
    dimension only."""
    n = math.prod(dims)
    coords = np.array([coord_of(s) for s in range(n)], dtype=np.int64)
    out = []
    for d in reversed(range(len(dims))):
        sched = schedules[d]
        phases = []
        for step in range(sched.num_steps):
            row = sched.partners(step)
            src, dst = [], []
            for s in range(n):
                digit = int(coords[s, d])
                partner = int(row[digit])
                if partner == digit:
                    continue                       # idle in this step
                nc = coords[s].copy()
                nc[d] = partner
                src.append(s)
                dst.append(index_of(tuple(nc.tolist())))
            phases.append(Phase(tuple(src), tuple(dst),
                                messages=message_size))
        out.append(phases)
    return out


def _grid_phases(dims: Sequence[int], schedules, coord_of, index_of,
                 message_size: int) -> list[Phase]:
    """Flattened dimension-order phases (the grid all-to-all sequence)."""
    return [ph for sub in _grid_phase_lists(dims, schedules, coord_of,
                                            index_of, message_size)
            for ph in sub]


def _cin_all_to_all(fab, message_size: int) -> Workload:
    return Workload.from_schedule(fab.schedule(), message_size=message_size,
                                  name=f"{fab.name}-a2a")


def _hyperx_all_to_all(fab, message_size: int) -> Workload:
    cfg = fab.config
    index_of = {tuple(cfg.switch_coord(s)): s
                for s in range(cfg.num_switches)}
    phases = _grid_phases(cfg.dims, fab.schedule(), cfg.switch_coord,
                          lambda c: index_of[c], message_size)
    return Workload(f"{fab.name}-a2a", cfg.num_switches, tuple(phases))


def _dragonfly_all_to_all(fab, message_size: int) -> Workload:
    """Dragonfly a2a as a (local x global) grid: local matching steps
    first (intra-group), then global steps pairing whole groups — each
    global step routes ``group_size`` flows l-g-l over one global link
    per group pair, the serialization the replay is there to measure."""
    c = fab.config
    a, g = c.group_size, c.num_groups
    sched = fab.schedule()
    phases = _grid_phases(
        (g, a), (sched["global"], sched["local"]),
        lambda s: (s // a, s % a),
        lambda coord: coord[0] * a + coord[1], message_size)
    return Workload(f"{fab.name}-a2a", c.switches, tuple(phases))


def _chain(*phase_lists) -> tuple[Phase, ...]:
    out: list[Phase] = []
    for pl in phase_lists:
        out.extend(pl)
    return tuple(out)


def _schedule_phases(sched, message_size: int, *, repeat: int = 1,
                     to_pairs=None) -> list[Phase]:
    """Phases of one schedule pass, optionally lifted to composite switch
    ids via ``to_pairs(step_row) -> (src, dst)`` lists."""
    phases = []
    for _ in range(repeat):
        for step in range(sched.num_steps):
            row = sched.partners(step)
            if to_pairs is None:
                s = np.arange(sched.n)
                live = row != s
                src = tuple(int(v) for v in s[live])
                dst = tuple(int(v) for v in row[live])
            else:
                src, dst = to_pairs(row)
            phases.append(Phase(src, dst, messages=message_size))
    return phases


def _cin_all_reduce(fab, message_size: int) -> Workload:
    """Flat all-reduce = reduce-scatter chain + all-gather chain: two
    passes over the 1-factor schedule."""
    sched = fab.schedule()
    phases = _schedule_phases(sched, message_size, repeat=2)
    return Workload(f"{fab.name}-allreduce", fab.num_switches, tuple(phases))


def _hyperx_all_reduce(fab, message_size: int) -> Workload:
    """Dimension-wise reduce-scatter (innermost dim first), then the
    all-gather passes in reverse dimension order."""
    cfg = fab.config
    index_of = {tuple(cfg.switch_coord(s)): s
                for s in range(cfg.num_switches)}
    # One phase list per dimension, innermost first (the RS order); the
    # AG passes replay them in reverse.
    per_dim = _grid_phase_lists(cfg.dims, fab.schedule(), cfg.switch_coord,
                                lambda c: index_of[c], message_size)
    phases = _chain(*per_dim, *reversed(per_dim))
    return Workload(f"{fab.name}-allreduce", cfg.num_switches, phases)


def _dragonfly_all_reduce(fab, message_size: int) -> Workload:
    """The :func:`repro.fabric.collectives.all_reduce_two_level` step
    sequence: local reduce-scatter -> global all-reduce of the scattered
    shards -> local all-gather.  Global phases carry
    ``ceil(message_size / group_size)`` messages per pair — the 1/a
    payload shrink the two-level hierarchy buys."""
    c = fab.config
    a, g = c.group_size, c.num_groups
    sched = fab.schedule()
    g_msg = max(1, -(-message_size // a))        # ceil(message_size / a)

    def local_pairs(row):
        src, dst = [], []
        for grp in range(g):
            for s in range(a):
                t = int(row[s])
                if t != s:
                    src.append(grp * a + s)
                    dst.append(grp * a + t)
        return tuple(src), tuple(dst)

    def global_pairs(row):
        src, dst = [], []
        for grp in range(g):
            peer = int(row[grp])
            if peer == grp:
                continue
            for s in range(a):
                src.append(grp * a + s)
                dst.append(peer * a + s)
        return tuple(src), tuple(dst)

    local_rs = _schedule_phases(sched["local"], message_size,
                                to_pairs=local_pairs)
    global_ar = _schedule_phases(sched["global"], g_msg, repeat=2,
                                 to_pairs=global_pairs)
    local_ag = _schedule_phases(sched["local"], message_size,
                                to_pairs=local_pairs)
    return Workload(f"{fab.name}-allreduce", c.switches,
                    _chain(local_rs, global_ar, local_ag))


def _cin_half_reduce(fab, message_size: int, tag: str) -> Workload:
    """One pass over the 1-factor schedule — the reduce-scatter (or,
    identically as a step sequence, the all-gather) half of the flat
    all-reduce."""
    phases = _schedule_phases(fab.schedule(), message_size)
    return Workload(f"{fab.name}-{tag}", fab.num_switches, tuple(phases))


def _hyperx_half_reduce(fab, message_size: int, tag: str,
                        gather: bool) -> Workload:
    """One dimension-order sweep: innermost-first for the reduce-scatter
    half, reversed (outermost-first) for the all-gather half — exactly
    the two halves :func:`_hyperx_all_reduce` chains."""
    cfg = fab.config
    index_of = {tuple(cfg.switch_coord(s)): s
                for s in range(cfg.num_switches)}
    per_dim = _grid_phase_lists(cfg.dims, fab.schedule(), cfg.switch_coord,
                                lambda c: index_of[c], message_size)
    phases = _chain(*(reversed(per_dim) if gather else per_dim))
    return Workload(f"{fab.name}-{tag}", cfg.num_switches, phases)


def _dragonfly_half_reduce(fab, message_size: int, tag: str,
                           gather: bool) -> Workload:
    """Half of the two-level sequence: local RS then one global pass
    (scatter), or one global pass then local AG (gather).  Global phases
    carry the 1/a-shrunk payload, as in :func:`_dragonfly_all_reduce`."""
    c = fab.config
    a, g = c.group_size, c.num_groups
    sched = fab.schedule()
    g_msg = max(1, -(-message_size // a))

    def local_pairs(row):
        src, dst = [], []
        for grp in range(g):
            for s in range(a):
                t = int(row[s])
                if t != s:
                    src.append(grp * a + s)
                    dst.append(grp * a + t)
        return tuple(src), tuple(dst)

    def global_pairs(row):
        src, dst = [], []
        for grp in range(g):
            peer = int(row[grp])
            if peer == grp:
                continue
            for s in range(a):
                src.append(grp * a + s)
                dst.append(peer * a + s)
        return tuple(src), tuple(dst)

    local = _schedule_phases(sched["local"], message_size,
                             to_pairs=local_pairs)
    global_half = _schedule_phases(sched["global"], g_msg,
                                   to_pairs=global_pairs)
    phases = (_chain(global_half, local) if gather
              else _chain(local, global_half))
    return Workload(f"{fab.name}-{tag}", c.switches, phases)


def collective_workload(fabric, collective: str = "all_to_all", *,
                        message_size: int = 1) -> Workload:
    """The replayable step sequence of ``collective`` on ``fabric``.

    * ``"all_to_all"`` — flat 1-factor schedule (CIN), dimension-order
      grid schedule (HyperX), or (local x global) grid (Dragonfly);
    * ``"all_reduce"`` — reduce-scatter + all-gather chains (CIN /
      HyperX per dimension), or the two-level Dragonfly sequence;
    * ``"reduce_scatter"`` / ``"all_gather"`` — the corresponding half
      of the all-reduce sequence (what GSPMD's ZeRO-style sharded DP
      and :func:`repro.runtime.manual_dp.lacin_grad_allreduce` emit as
      separate HLO ops — see :mod:`repro.workload`).

    ``message_size`` is the packets per (src, dst) pair per phase; the
    Dragonfly ``all_reduce``/half-sequence global phases carry
    ``ceil(message_size / group_size)`` (the hierarchical payload
    shrink).
    """
    from repro.fabric import (CINFabric, DragonflyFabric, HyperXFabric,
                              make_fabric)
    fabric = make_fabric(fabric)
    builders = {
        ("all_to_all", CINFabric): _cin_all_to_all,
        ("all_to_all", HyperXFabric): _hyperx_all_to_all,
        ("all_to_all", DragonflyFabric): _dragonfly_all_to_all,
        ("all_reduce", CINFabric): _cin_all_reduce,
        ("all_reduce", HyperXFabric): _hyperx_all_reduce,
        ("all_reduce", DragonflyFabric): _dragonfly_all_reduce,
        ("reduce_scatter", CINFabric):
            lambda f, m: _cin_half_reduce(f, m, "rs"),
        ("reduce_scatter", HyperXFabric):
            lambda f, m: _hyperx_half_reduce(f, m, "rs", gather=False),
        ("reduce_scatter", DragonflyFabric):
            lambda f, m: _dragonfly_half_reduce(f, m, "rs", gather=False),
        ("all_gather", CINFabric):
            lambda f, m: _cin_half_reduce(f, m, "ag"),
        ("all_gather", HyperXFabric):
            lambda f, m: _hyperx_half_reduce(f, m, "ag", gather=True),
        ("all_gather", DragonflyFabric):
            lambda f, m: _dragonfly_half_reduce(f, m, "ag", gather=True),
    }
    builder = builders.get((collective, type(fabric)))
    if builder is None:
        known = sorted({k for k, _ in builders})
        raise ValueError(
            f"no {collective!r} workload builder for "
            f"{type(fabric).__name__}; collectives: {known}")
    return builder(fabric, message_size)


# ---------------------------------------------------------------------------
# Expert-parallel dispatch and combine of a mixture-of-experts layer.
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class MoESpec:
    """An MoE layer's routed experts and its expert-parallel exchange.

    Defaults are DeepSeek-V3's published gate (``n_routed_experts`` 256
    in ``n_group`` 8 groups, ``topk_group`` 4, ``num_experts_per_tok``
    8; ``noaux_tc`` over sigmoid scores) and DeepEP's "normal" kernels at
    its pretraining setting: ``batch_tokens`` 4,096 tokens a rank,
    ``ranks_per_node`` 8, an FP8 dispatch copy of ``dispatch_bytes``
    7,392 B (7,168 B of hidden state plus 56 float32 block scales) and a
    BF16 combine copy of ``combine_bytes`` 14,336 B, cut into packets of
    ``packet_bytes``.  ``sigma`` is the spread of a per-expert bias drawn
    anew for every EP group and step: a batch's domain skew, which moves
    expert load under aux-loss-free balancing (0 = none).
    """
    n_routed_experts: int = 256
    n_group: int = 8
    topk_group: int = 4
    num_experts_per_tok: int = 8
    batch_tokens: int = 4096
    ranks_per_node: int = 8
    dispatch_bytes: int = 7392
    combine_bytes: int = 14336
    packet_bytes: int = 4096
    sigma: float = 0.0

    def __post_init__(self):
        for f in fields(self):
            v = getattr(self, f.name)
            if f.name == "sigma":
                if not v >= 0:
                    raise ValueError(f"sigma must be >= 0, got {v}")
                object.__setattr__(self, f.name, float(v))
            elif int(v) != v or v < 1:
                raise ValueError(f"{f.name} must be a positive integer, "
                                 f"got {v!r}")
            else:
                object.__setattr__(self, f.name, int(v))
        per_group, rem = divmod(self.n_routed_experts, self.n_group)
        if rem or per_group < 2:
            raise ValueError(
                f"{self.n_routed_experts} experts do not split into "
                f"{self.n_group} groups of at least 2 (a group scores "
                f"by its top-2 experts)")
        if self.topk_group > self.n_group:
            raise ValueError(f"topk_group {self.topk_group} > n_group "
                             f"{self.n_group}")
        if self.num_experts_per_tok > self.topk_group * per_group:
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} exceeds "
                f"the {self.topk_group * per_group} experts of the kept "
                f"groups")

    @classmethod
    def coerce(cls, v) -> "MoESpec":
        """A spec from itself or its dict form; unknown keys raise."""
        if isinstance(v, cls):
            return v
        if not isinstance(v, Mapping):
            raise ValueError(f"moe params must be a dict, got "
                             f"{type(v).__name__}")
        known = {f.name for f in fields(cls)}
        unknown = sorted(set(v) - known)
        if unknown:
            raise ValueError(f"unknown moe params {unknown}; known: "
                             f"{sorted(known)}")
        return cls(**dict(v))

    @property
    def dispatch_packets(self) -> int:
        """Packets of one token's dispatch copy to one remote node."""
        return -(-self.dispatch_bytes // self.packet_bytes)

    @property
    def combine_packets(self) -> int:
        """Packets of one token's combine copy back from one node."""
        return -(-self.combine_bytes // self.packet_bytes)


def noaux_tc(logits: np.ndarray, moe: MoESpec) -> np.ndarray:
    """DeepSeek-V3's ``noaux_tc`` top-k: the selected expert ids,
    ``(tokens, num_experts_per_tok)``, each row a set in no given order.

    Score = sigmoid(logits); a group (``n_routed_experts / n_group``
    consecutive experts) scores the sum of its two best scores; the
    ``topk_group`` best groups are kept, and the ``num_experts_per_tok``
    best experts among theirs are selected.  The correction bias is 0
    (random weights), so the scores alone select."""
    t, e = logits.shape
    g = moe.n_group
    scores = 1.0 / (1.0 + np.exp(-logits))
    grouped = scores.reshape(t, g, e // g)
    group_scores = np.partition(grouped, -2, axis=-1)[..., -2:].sum(-1)
    kept = np.argpartition(group_scores, -moe.topk_group,
                           axis=-1)[:, -moe.topk_group:]
    in_kept = np.zeros((t, g), dtype=bool)
    np.put_along_axis(in_kept, kept, True, axis=-1)
    masked = np.where(np.repeat(in_kept, e // g, axis=-1), scores, -np.inf)
    return np.argpartition(masked, -moe.num_experts_per_tok,
                           axis=-1)[:, -moe.num_experts_per_tok:]


def _gate_copies(moe: MoESpec, groups: int, nodes: int,
                 seed: int) -> np.ndarray:
    """``copies[r, s, d]``: tokens of node ``s`` of EP group ``r`` that
    send one dispatch copy to node ``d != s`` (see
    :func:`moe_dispatch_workload` for the draw order)."""
    rng = np.random.default_rng(seed)
    tokens = moe.batch_tokens * moe.ranks_per_node
    per_node = moe.n_routed_experts // nodes
    copies = np.zeros((groups, nodes, nodes), dtype=np.int64)
    for r in range(groups):
        bias = rng.normal(0.0, moe.sigma, moe.n_routed_experts)
        for s in range(nodes):
            logits = rng.standard_normal(
                (tokens, moe.n_routed_experts)) + bias
            hit = np.zeros((tokens, nodes), dtype=bool)
            np.put_along_axis(hit, noaux_tc(logits, moe) // per_node,
                              True, axis=-1)
            hit[:, s] = False
            copies[r, s] = hit.sum(axis=0)
    return copies


def moe_dispatch_workload(fabric, moe, seed: int) -> Workload:
    """Expert-parallel dispatch and combine of one MoE layer on every row
    of a HyperX, drawn from ``seed``.

    Each row (the switches that differ only in the last coordinate) is
    one EP group whose ``n_group`` switches are its nodes: node ``j``
    holds experts ``j * E/n .. (j + 1) * E/n - 1`` (E =
    ``n_routed_experts``, n = ``n_group`` = the last dimension), so
    node-limited routing sends a token to at most ``topk_group`` nodes.
    All rows run in lockstep under the fabric-wide phase barrier.

    The draw (a frozen contract: the benchmark holds a copy of it bit
    for bit): ``rng = np.random.default_rng(seed)``; for each row ``r``
    in switch order, ``bias = rng.normal(0, sigma, E)``, then for each
    node ``s`` of the row ``logits = rng.standard_normal((batch_tokens *
    ranks_per_node, E)) + bias`` in float64, and :func:`noaux_tc`
    selects each token's experts.  A token of node ``s`` sends one
    dispatch copy to each distinct node of its experts other than ``s``
    (DeepEP: one RDMA copy per remote node, forwarded inside the node)
    and gets one combine copy back from each.

    Phases: one dispatch phase per step of the last dimension's LACIN
    schedule, pair ``(s, partner)`` carrying ``dispatch_packets x
    copies(s -> partner)``; then the same steps as combine phases, pair
    ``(s, partner)`` carrying ``combine_packets x copies(partner -> s)``.
    Pairs with no copy are dropped.  The ``sweep.gate`` span covers
    the draw and the count build (inside ``sweep.traffic`` when a sweep
    draws it); the counters ``moe_copies`` (remote
    token copies drawn) and ``moe_pair_max_over_mean`` (the busiest
    pair's copies over the mean pair's, largest over the steps; combine
    steps carry the same counts transposed) go to the grid's timing
    dict."""
    from repro.fabric import HyperXFabric, make_fabric
    fab = make_fabric(fabric)
    moe = MoESpec.coerce(moe)
    if not isinstance(fab, HyperXFabric):
        raise ValueError(f"the MoE dispatch replay runs on the rows of a "
                         f"HyperX, not on {type(fab).__name__}")
    nodes = fab.config.dims[-1]
    if nodes != moe.n_group:
        raise ValueError(
            f"an EP group is one row of {nodes} switches, but the gate "
            f"limits tokens by n_group={moe.n_group} node groups; use a "
            f"HyperX whose last dimension is {moe.n_group}")
    n = fab.config.num_switches
    groups = n // nodes
    with span("sweep.gate"):
        copies = _gate_copies(moe, groups, nodes, seed)
        sched = fab.schedule()[-1]
        node = np.arange(nodes)
        steps = [np.asarray(sched.partners(k)) for k in
                 range(sched.num_steps)]

        def phase(partner, pairs):
            r, j = np.nonzero((partner != node) & (pairs > 0))
            return Phase(tuple((r * nodes + j).tolist()),
                         tuple((r * nodes + partner[j]).tolist()),
                         tuple(pairs[r, j].tolist()))

        phases = ([phase(p, moe.dispatch_packets * copies[:, node, p])
                   for p in steps]
                  + [phase(p, moe.combine_packets * copies[:, p, node])
                     for p in steps])
    count("moe_copies", int(copies.sum()))
    ratios = [c.max() / c.mean() for c in
              (copies[:, node, p][:, p != node] for p in steps)
              if c.size and c.mean() > 0]
    count("moe_pair_max_over_mean", float(max(ratios, default=0.0)),
          largest=True)
    return Workload(f"{fab.name}-moe", n, tuple(phases))


# ---------------------------------------------------------------------------
# Replay entry point.
# ---------------------------------------------------------------------------

def replay(topo, policy, workload: Workload, *, backend: str = "numpy",
           terminals: int | None = None, eject_bw: int | None = None,
           num_vcs: int | None = None, queue_capacity: int = 4,
           max_cycles: int | None = None, seed: int = 0,
           trace=None, failures=None, bucket: bool | None = None,
           devices=None) -> RunStats:
    """Replay ``workload`` on ``topo`` under ``policy``; returns the
    engine's :class:`~repro.sim.metrics.RunStats` with the replay fields
    set: ``phase_cycles`` (per-phase durations), ``completion_cycles``
    (the cycle the last packet delivered), and ``ideal_cycles`` (the
    contention-free bound) — ``completion_cycles >= ideal_cycles``
    always, with equality iff no phase ever left its bottleneck link
    idle or contended.

    ``failures`` (a :class:`repro.faults.FailureSpec`) replays on the
    degraded fabric: routing falls back to the surviving graph's tables
    and pairs whose endpoints died or were disconnected are masked out
    of every phase (phase barriers then gate on the surviving packet
    counts, and ``ideal_cycles`` is recomputed for the masked workload).
    """
    from .engine import simulate
    from .policies import make_policy
    if isinstance(policy, str):
        policy = make_policy(policy)
    if workload.num_switches != topo.num_switches:
        raise ValueError(
            f"workload {workload.name!r} spans {workload.num_switches} "
            f"switches but topology {topo.name!r} has {topo.num_switches}")
    return simulate(topo, policy, workload.traffic(), terminals=terminals,
                    eject_bw=eject_bw, num_vcs=num_vcs,
                    queue_capacity=queue_capacity, warmup=0,
                    max_cycles=max_cycles, seed=seed, backend=backend,
                    trace=trace, failures=failures, bucket=bucket,
                    devices=devices)

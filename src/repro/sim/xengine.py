"""Compiled simulation engine: the cycle pipeline as one JAX program.

The numpy :class:`~repro.sim.engine.Engine` is the semantic oracle: one
Python iteration per simulated cycle, with dynamic-shape ``np.nonzero``
gathers selecting the active queues and feasible requests.  That costs
O(points x seeds x cycles) interpreter round-trips per saturation sweep —
the hottest path in the repo.  This module re-expresses the same pipeline
(eject -> route -> inject -> credit-checked link arbitration -> move) as a
*fixed-shape, functionally pure* step over a state pytree, compiled with
``jax.lax`` loops under ``jit``, so an entire sweep — every offered-load
point x every seed — runs as a single compiled program.

Masked-dense design
-------------------
Dynamic selections become dense lanes with validity masks, and — because
XLA's scatter is a serial per-update loop on CPU — every per-cycle update
except the delivery-timestamp record is formulated as a gather, select,
or axis reduction:

* **Queues.** Every (switch, input-port, VC) FIFO is a lane; ``occ > 0``
  masks the active ones.  The packet attributes routing reads in flight
  (destination ``dst``, itinerary ``mid``, routing ``phase``, ``hops``)
  ride *inside* the ring buffers as one packed word per slot, pushed and
  popped with the packet id, so the queue heads route without gathering
  from the packet table; a packet's location is implicit in the queue
  holding it.  The word's field widths follow the fabric
  (:func:`_word_layout`); a fabric too large for ``dst`` to fit leaves it
  out and gathers each head's destination by packet id instead.
* **Routing.** The table-free minimal route is evaluated once per
  topology into a dense ``(N, N)`` next-port table
  (:meth:`SimTopology.minimal_port_table`); in-step routing is a gather.
* **Arbitration.** All contenders for a switch's output links — its
  ``ports x VCs`` queue heads plus its ``terminals`` injection lanes —
  form one dense block, and the oracle's lexsort-based
  :func:`arbitrate` becomes an argmin over a (contender, port) key
  tensor: transit-beats-injection rides in the key's class bit, random
  tie-breaks in its low bits.  Ejection (k winners per switch) is a
  pairwise rank inside the same block.
* **Movement as gathers.** One winner per directed link means the
  downstream queue of link (s, i) receives from exactly one place, so
  pushes invert into a *gather* through the wire's feeder table
  (``nbr[s,i]*P + rev[s,i]``), and ring-buffer writes are one-hot
  selects over the ``capacity`` axis.  Link-load counters increment
  elementwise (loads are link-indexed).  The only scatter left is the
  per-ejection delivery-cycle record.
* **Batching by fabric replication, not vmap.** A sweep's (load, seed)
  grid is laid out as B disjoint copies of the topology inside one flat
  state: queue lane ``b*Q + q``, link slot ``b*L + l``, packet id
  ``b*M + p`` belong to grid point ``b``.  Every op above stays flat
  and vectorized (a vmapped scatter is not), the loop predicate stays
  scalar, and per-op dispatch overhead is amortized over the grid.
* **Traffic.** Packet descriptors concatenate at exact sizes with
  cumulative id offsets — the flat layout needs no per-point padding,
  only that every point shares the compiled horizon.

Equivalence is statistical, not bitwise: both engines simulate the same
queueing system over the same packet sets, but arbitration tie-breaks
draw from different RNG streams.  ``tests/test_xengine.py`` pins the
invariants that *must* agree exactly (delivered packet counts under
drain, minimal-route link loads) and bounds the rest (accepted
throughput, latency) within seed-matched tolerances.
"""
from __future__ import annotations

import hashlib
import inspect
from functools import lru_cache, partial
from typing import Callable, NamedTuple, Sequence

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

from ..obs.telemetry import (recorded_spans, span, span_record,
                             timed_compiled)
from ..obs.trace import Trace, TraceConfig, derive_backlog
from .engine import _DRAIN_SLACK
from .link import LinkLoadCounter, LinkTable
from .metrics import (RunStats, attach_replay, attach_serving, build_stats,
                      replay_timeline)
from .policies import RoutingPolicy, make_policy
from .topology import SimTopology
from .traffic import Traffic, resolve_terminals

_I32 = jnp.int32
_I16 = jnp.int16
_INT32_MAX = np.iinfo(np.int32).max
#: Sentinel generation cycle for padded packet slots: larger than any
#: simulated cycle, so a padded slot never becomes an injection candidate.
_PAD_GEN = _INT32_MAX
#: Hop counts saturate at or below this value inside the packed attribute
#: word (see :func:`_word_layout`); hops only feed the VC-class clamp
#: ``min(hops - 1, num_vcs - 1)``, so saturation is lossless for V <= 128.
_MAX_HOPS = 127
#: Bits of the packed attribute word; the int32 sign bit stays clear.
_WORD_BITS = 31


#: Above this many (horizon x queue-lane) entries the per-cycle ejection
#: log (see _step) falls back to a per-packet scatter to bound memory.
_LOG_ENTRY_BUDGET = 48_000_000


def _bucket_count(x: int) -> int:
    """The shape-bucketing boundary at or above ``x``.

    Grid sizes, packet counts, and cycle horizons are rounded up to one
    of these boundaries so that sweeps over many nearby sizes reuse a
    handful of compiled programs instead of compiling one each (the
    padding is fully masked — see :func:`sweep`).  The ladder bounds the
    padding waste: exact powers of two below 8, multiples of 8 up to 64
    (<= ~30% waste where programs are cheap anyway), then the
    {2^k, 1.5 * 2^k} ladder (<= 33% waste) beyond."""
    x = max(int(x), 1)
    if x <= 8:
        return 1 << (x - 1).bit_length()
    if x <= 64:
        return (x + 7) // 8 * 8
    p = 1 << (x - 1).bit_length()          # next pow2 >= x
    if 3 * p // 4 >= x:
        return 3 * p // 4                  # the 1.5 * 2^(k-1) rung
    return p


class XSpec(NamedTuple):
    """Static (hashable) engine configuration — the jit cache key.

    ``horizon``/``cutoff`` are static so the loop can be a fixed-trip
    ``fori_loop`` and the ejection log can be allocated ``(horizon, Q)``;
    :func:`sweep` buckets them (with the grid width and packet count) to
    shared boundaries and measures to the *runtime* bounds riding in the
    packet dict, so nearby sweep sizes reuse one compiled program.
    """
    n: int
    ports: int
    vcs: int
    cap: int
    terminals: int
    eject_bw: int
    policy: str
    threshold: float
    weight: float
    alpha: float
    drain: bool
    horizon: int
    cutoff: int
    log_deliveries: bool
    #: Collective-replay mode: > 0 enables the phase barrier — packet
    #: ``gen`` is a phase ordinal, injection gates on completed phases,
    #: and ``phase_done`` windows (one static (B, num_phases) record)
    #: capture each phase's completion cycle.  0 = open-loop traffic.
    num_phases: int = 0
    #: Time-series tracing (repro.obs): sample the trace ring buffers
    #: every ``trace_stride`` cycles into ``trace_samples`` statically
    #: allocated rows.  0 = off — the defaults keep the compiled program
    #: (and its jit cache key) identical to an untraced build.
    trace_stride: int = 0
    trace_samples: int = 0


class _Tables(NamedTuple):
    """Constants of one compiled run: topology tables plus precomputed
    index vectors (everything an iota/div/mod chain would otherwise
    recompute inside the loop body every cycle).

    Topology tables use *local* (per-copy) ids; index vectors span the
    flat replicated state (Q = B*N*P*V lanes, L = B*N*P links,
    NT = B*N*T terminal lanes).
    """
    port_table: jax.Array        # (N, N) next-hop output port
    comp_of_switch: jax.Array    # (N,) component label on degraded
    #                              fabrics (-1 = dead switch); all zeros
    #                              pristine, so the Valiant-mid collapse
    #                              below is the identity there
    feeder_local: jax.Array      # (N*P,) local link feeding port (s,i); -1.
    #                              Read both ways: the queue behind input
    #                              port (s,i) receives from link
    #                              feeder_local[s*p+i], and the downstream
    #                              port of link (s,i) IS feeder_local[s*p+i]
    #                              (inverse-wire identity).
    sw_local: jax.Array          # (Q,) local switch of each queue lane
    x_of_lane: jax.Array         # (Q,) contender slot within the block
    vc_of_lane: jax.Array        # (Q,) VC of each queue lane
    linkbase_of_lane: jax.Array  # (Q,) flat link id of the block's port 0
    feeder_flat: jax.Array       # (Q,) flat link feeding the lane's port
    feeder_xbase: jax.Array      # (Q,) feeder's block * x (contender base)
    wired_q: jax.Array           # (Q,) lane's input port is wired
    blk_idx: jax.Array           # (NT,) flat (copy, switch) index
    slot_of_term: jax.Array      # (NT,) terminal slot within the switch
    linkbase_of_term: jax.Array  # (NT,) flat link id of the switch's port 0
    copybase_of_term: jax.Array  # (NT,) copy * N*P (adaptive congestion)
    copybase_of_block: jax.Array  # (B*N,) copy * N*P per switch block
    copy_of_link: jax.Array      # (L,) copy owning each flat link


class _State(NamedTuple):
    """Flat state of all B fabric copies: the loop carry.

    Shapes use Q = B*N*P*V queue lanes, L = B*N*P link slots, and
    M = B*pad packet slots.  Queue ring buffers interleave the packet id
    and its packed attribute word along a trailing axis of 2, so head
    reads and winner gathers move one (pid, attr) pair per row.

    ``deliver`` and ``ej_log`` are the two delivery-record modes: with
    ``spec.log_deliveries`` each cycle writes its ejected pids as one
    contiguous ``(Q,)`` row of ``ej_log`` (a ``dynamic_update_slice`` —
    cheap), and per-packet times are reconstructed on the host after the
    run; otherwise ``deliver`` is scattered per ejection (XLA's CPU
    scatter is a serial per-row loop, but drain-mode runs are small).
    Exactly one of the two is non-trivial per compile.
    """
    buf: jax.Array               # (Q, cap, 2) ring buffers: pid, attr word
    #                              (dst | mid | phase | hops; _word_layout)
    head: jax.Array              # (Q,)
    occ: jax.Array               # (Q,)
    deliver: jax.Array           # (M,) delivery cycle, -1 = in flight
    ej_log: jax.Array            # (horizon, Q) ejected pid per lane, -1
    term_next: jax.Array         # (B*N*T,) injected count per terminal lane
    pressure: jax.Array          # (L,) EWMA requested link demand
    load_total: jax.Array        # (L,) lifetime link traversals
    load_window: jax.Array       # (L,) traversals inside [warmup, horizon)
    delivered_total: jax.Array   # (B,)
    delivered_win: jax.Array     # (B,)
    phase_done: jax.Array        # (B, num_phases) completion cycle, -1
    cycle: jax.Array             # scalar, shared by every copy
    # Trace ring buffers (repro.obs): S = spec.trace_samples rows, one
    # contiguous dynamic_update_slice row write per sampled cycle — the
    # same zero-scatter pattern as ej_log.  (1,)/(1, 1) dummies when off.
    tr_cycle: jax.Array          # (S,) sampled cycle index, -1 = unwritten
    tr_link: jax.Array           # (S, L) cumulative link traversals
    tr_occ: jax.Array            # (S, B*N) per-switch queue occupancy
    tr_inj: jax.Array            # (S, B*N) cumulative injections per switch
    tr_del: jax.Array            # (S, B) cumulative deliveries per copy


def _word_layout(n: int, vcs: int) -> tuple[int, int]:
    """``(hop_bits, id_bits)``: the packed attribute word's field widths
    for a fabric of ``n`` switches and ``vcs`` VCs.

    The word is ``dst | mid | phase | hops``, high bits first: ``dst``
    and ``mid`` take ``id_bits = bits(n - 1)`` each, ``phase`` one bit,
    and hops saturate at ``2**hop_bits - 1``, which is at least
    ``min(vcs, _MAX_HOPS)`` — so the VC class ``min(hops - 1, vcs - 1)``
    reads exactly as unsaturated hops give it.  When the fields overflow
    :data:`_WORD_BITS`, ``id_bits`` is 0: the word is ``mid << 8 | phase
    << 7 | hops`` with no ``dst`` field, and the step gathers each queue
    head's destination from the packet table by packet id."""
    id_bits = max(int(n - 1).bit_length(), 1)
    hop_bits = min(int(vcs).bit_length(), _MAX_HOPS.bit_length())
    if 2 * id_bits + 1 + hop_bits <= _WORD_BITS:
        return hop_bits, id_bits
    return _MAX_HOPS.bit_length(), 0


def _pack_attr(layout: tuple[int, int], dst, mid, phase, hops):
    """Packed attribute words (see :func:`_word_layout`); ``dst`` is
    dropped when the layout has no field for it."""
    hop_bits, id_bits = layout
    word = ((mid << (hop_bits + 1)) | (phase << hop_bits)
            | jnp.minimum(hops, (1 << hop_bits) - 1))
    if id_bits:
        word = word | (dst << (hop_bits + 1 + id_bits))
    return word


def _unpack_attr(layout: tuple[int, int], word):
    """``(dst, mid, phase, hops)`` of packed words; ``dst`` is None when
    the layout has no field for it."""
    hop_bits, id_bits = layout
    hops = word & ((1 << hop_bits) - 1)
    phase = (word >> hop_bits) & 1
    mid = word >> (hop_bits + 1)
    if not id_bits:
        return None, mid, phase, hops
    return (mid >> id_bits, mid & ((1 << id_bits) - 1), phase, hops)


def _resolve_policy(policy) -> RoutingPolicy:
    if isinstance(policy, RoutingPolicy):
        return policy
    if isinstance(policy, str):
        return make_policy(policy)
    if callable(policy):
        return policy()
    raise TypeError(f"cannot resolve a routing policy from {policy!r}")


def _accepts_seed(traffic_factory: Callable) -> bool:
    """True when the factory takes ``(load, seed)`` rather than ``(load)``."""
    try:
        pos = [q for q in
               inspect.signature(traffic_factory).parameters.values()
               if q.kind in (q.POSITIONAL_ONLY, q.POSITIONAL_OR_KEYWORD,
                             q.VAR_POSITIONAL)]
        return len(pos) >= 2
    except (TypeError, ValueError):
        return False


def _pack_traffic(traffic: Traffic, n: int, pid_base: int
                  ) -> dict[str, np.ndarray]:
    """The oracle Engine's packet layout — sorted by (src, gen), with
    per-switch source-FIFO block bounds — offset into the flat packet-id
    space at ``pid_base``.  Grid points keep their exact sizes (no
    padding); the flat layout only needs cumulative offsets."""
    src = traffic.src.astype(np.int64)
    gen = traffic.gen.astype(np.int64)
    # All in-repo generators emit (src, gen)-sorted packets already; the
    # stable lexsort is then the identity, so skip it (it is one of the
    # priciest host-side steps of a batched sweep).
    key = src * (gen.max(initial=0) + 1) + gen
    if np.all(key[1:] >= key[:-1]):
        dst = traffic.dst
    else:
        order = np.lexsort((traffic.gen, traffic.src))
        src = src[order]
        gen = gen[order]
        dst = traffic.dst[order]
    m = src.size
    counts = np.bincount(src, minlength=n) if m else np.zeros(n, np.int64)
    blk_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    blk_end = blk_start + counts
    return {
        "src": src.astype(np.int32),
        "dst": np.asarray(dst, dtype=np.int32),
        "gen": np.clip(gen, 0, _PAD_GEN).astype(np.int32),
        "blk_start": (blk_start + pid_base).astype(np.int32),
        "blk_end": (blk_end + pid_base).astype(np.int32),
        "m_real": np.int32(m),
    }


# ---------------------------------------------------------------------------
# The compiled cycle step (all B fabric copies at once).
# ---------------------------------------------------------------------------

def _step(spec: XSpec, tables: _Tables, pkt: dict, base_key: jax.Array,
          warmup: jax.Array, state: _State) -> _State:
    n, p, v = spec.n, spec.ports, spec.vcs
    cap, t = spec.cap, spec.terminals
    pv = p * v
    blocks = state.head.shape[0] // pv          # B * N switch blocks
    b = blocks // n                             # fabric copies in the batch
    q_flat = blocks * pv
    nt_flat = b * n * t
    n_links = blocks * p
    m_flat = pkt["src"].shape[0]
    x = pv + t                                  # contenders per switch block
    # Packed arbitration key: [cls | rand | contender index], low bits the
    # index so one min-reduction yields both the winning key and who won.
    # Index bits cover x strictly (2^x_bits > x), so the sentinel's index
    # field can never alias a real contender.  Small blocks fit the key
    # in int16 (halving the hot tensor); the random field keeps >= 8 bits
    # either way, so tie-break bias stays negligible.
    x_bits = int(x).bit_length()
    x_mask = (1 << x_bits) - 1
    if x_bits <= 6:
        key_dtype, sent, rand_bits = jnp.int16, 32767, 14 - x_bits
    else:
        key_dtype, sent = _I32, _INT32_MAX
        rand_bits = min(30 - x_bits, 16)
    src, dst, gen = pkt["src"], pkt["dst"], pkt["gen"]
    layout = _word_layout(n, v)
    hop_bits = layout[0]
    hop_mask = (1 << hop_bits) - 1
    c = state.cycle
    # Every op of the step is traced under exactly one named scope (rng,
    # eject, route, arbitrate, move, sample), so a profile can give each
    # stage's device time (repro.obs.telemetry.scope_maps); scopes change
    # only op metadata, never the compiled program.
    with jax.named_scope("rng"):
        if spec.num_phases:
            # Replays measure the whole run (the horizon is only the phase
            # count); the window upper bound applies to open-loop drains.
            in_window = c >= warmup                      # (B,) per-copy mask
        else:
            # The measurement horizon is the *runtime* ``h_eff``, not the
            # (possibly bucket-padded) static ``spec.horizon``: a padded
            # program measures exactly what the exact-shape program would.
            in_window = (c >= warmup) & (c < pkt["h_eff"])
        # One random word per queue lane and per terminal lane; mechanisms
        # consume disjoint bit ranges of a word (threefry bits are
        # independent), halving the per-cycle threefry work.  The stream is
        # drawn *per fabric copy* from a key folded over the copy's global
        # id: copy b's bits depend only on (base key, cycle, copy_id[b]) —
        # never on how many copies share the program — so bucket-padding
        # the batch or sharding it across devices is bit-identical to the
        # exact-shape single-device program.  Copy 0 keeps the unfolded
        # per-cycle key: a single-copy program then draws the stream this
        # engine has always drawn, preserving every seed-era single-run
        # result bit for bit.
        ck = jax.random.fold_in(base_key, c)
        per_copy = n * pv + n * t
        folded = jax.vmap(jax.random.fold_in, in_axes=(None, 0))(
            ck, pkt["copy_id"])
        keys = jnp.where((pkt["copy_id"] == 0)[:, None], ck, folded)
        bits = jax.vmap(lambda k: jax.random.bits(k, (per_copy,)))(keys)
        lane_bits = bits[:, :n * pv].reshape(q_flat)
        #                                  ^ high 16: ejection; low 16: arb
        term_bits = bits[:, n * pv:].reshape(nt_flat)
        #                                  ^ high bits: arb; low: Valiant mid

    with jax.named_scope("eject"):
        # -- queue heads ------------------------------------------------------
        lanes = jnp.arange(q_flat, dtype=_I32)
        valid = state.occ > 0
        head_slot = state.head % cap
        h_pair = state.buf[lanes, head_slot]        # (Q, 2): pid, attr
        pid = jnp.where(valid, h_pair[:, 0], 0)
        h_dst, h_mid, h_phase, h_hops = _unpack_attr(layout, h_pair[:, 1])
        if h_dst is None:                 # no dst field: gather by packet id
            h_dst = dst[pid]
        done = valid & (tables.sw_local == h_dst) & (h_phase == 1)

        # 1. ejection: up to eject_bw random winners per switch ---------------
        # Winners are the eject_bw smallest unique (randbits, lane) keys among
        # the done heads of each switch's (ports * VCs) lane block.  Small
        # blocks use a pairwise rank (fewest dispatches); large blocks a
        # sorted k-th-key threshold (O(pv log pv) beats O(pv^2)).  Both pick
        # the same winners.
        done2 = done.reshape(blocks, pv)
        if spec.eject_bw <= 0:
            # A stalled ejection port: nothing leaves (matches the oracle's
            # arbitrate(..., k=0)); without this guard the sort-threshold
            # branch below would index the k-th key at -1 and eject everything.
            ej_win = jnp.zeros(q_flat, bool)
        elif pv <= 32:
            r2 = (lane_bits >> np.uint32(16)).astype(jnp.uint16
                                                     ).reshape(blocks, pv)
            idx = jnp.arange(pv)
            before = (r2[:, None, :] < r2[:, :, None]) | (
                (r2[:, None, :] == r2[:, :, None])
                & (idx[None, :] < idx[:, None]))
            rank = jnp.sum(before & done2[:, None, :], axis=2)
            ej_win = (done2 & (rank < spec.eject_bw)).reshape(q_flat)
        else:
            e_bits = int(pv).bit_length()
            ekey = (((lane_bits >> np.uint32(16)).astype(_I32)
                     << e_bits) | tables.x_of_lane)
            ekey = jnp.where(done, ekey, _INT32_MAX)
            kth = jnp.sort(ekey.reshape(blocks, pv), axis=1)[
                :, min(spec.eject_bw, pv) - 1]
            ej_win = done & (ekey <= jnp.repeat(kth, pv))

        ej_cnt = ej_win.reshape(b, n * pv).sum(axis=1, dtype=_I32)
        if spec.log_deliveries:
            # One contiguous row write per cycle; per-packet times are
            # reconstructed on the host.  Orders of magnitude cheaper than a
            # per-row scatter on XLA:CPU.
            deliver = state.deliver
            ej_log = lax.dynamic_update_slice(
                state.ej_log, jnp.where(ej_win, pid, -1)[None, :], (c, 0))
        else:
            deliver = state.deliver.at[
                jnp.where(ej_win, pid, m_flat)].set(c, mode="drop")
            ej_log = state.ej_log
        occ = state.occ - ej_win.astype(_I16)
        head = state.head + ej_win.astype(_I16)
        delivered_total = state.delivered_total + ej_cnt
        delivered_win = state.delivered_win + jnp.where(in_window, ej_cnt, 0)

        # -- phase barrier (collective replay) --------------------------------
        # cur_phase[b] = completed phases of copy b, derived from the
        # post-ejection delivered count against the per-copy cumulative phase
        # sizes — the same-cycle release discipline of the oracle engine
        # (a phase's closing delivery unblocks the next phase's injection in
        # this very cycle).  phase_done records each phase's closing cycle.
        if spec.num_phases:
            cum = pkt["phase_cum"]                      # (B, num_phases)
            done_p = delivered_total[:, None] >= cum
            phase_done = jnp.where((state.phase_done < 0) & done_p, c,
                                   state.phase_done)
            cur_phase = jnp.sum(done_p, axis=1).astype(_I32)   # (B,)
        else:
            phase_done = state.phase_done

    with jax.named_scope("route"):
        # 2. transit requests -------------------------------------------------
        transit = valid & ~done
        sw_q = tables.sw_local
        tgt = jnp.where(h_phase == 1, h_dst, h_mid)
        safe_tgt = jnp.where(transit & (tgt != sw_q), tgt, (sw_q + 1) % n)
        t_port = tables.port_table[sw_q, safe_tgt]

        # 3. injection candidates + policy itinerary --------------------------
        cand = (pkt["blk_start"][tables.blk_idx] + tables.slot_of_term
                + state.term_next * t)
        inj_valid = cand < pkt["blk_end"][tables.blk_idx]
        ip = jnp.where(inj_valid, cand, 0)
        if spec.num_phases:
            # Replay: gen is the packet's phase ordinal; it may inject once
            # its copy has completed that many phases.
            inj_valid &= gen[ip] <= cur_phase[
                tables.copybase_of_term // (n * p)]
        else:
            inj_valid &= gen[ip] <= c

        i_dst = dst[ip]
        i_mid, i_phase = i_dst, jnp.ones(nt_flat, _I32)
        if spec.policy != "minimal" and n >= 3:
            # Uniform intermediate avoiding {src, dst} (shift-remap).
            s_i, d_i = src[ip], i_dst
            lo = jnp.minimum(s_i, d_i)
            hi = jnp.maximum(s_i, d_i)
            r = ((term_bits & np.uint32(0x3FFF)) % np.uint32(n - 2)
                 ).astype(_I32)
            r = r + (r >= lo)
            r = r + (r >= hi)
            # Degraded fabrics: a mid that died or fell outside the source's
            # component collapses to the destination (route minimally rather
            # than detour into a black hole).  comp_of_switch is all zeros
            # pristine, so ``ok`` is constant-True there and the collapse is
            # the identity — same sample bits, same results.
            ok = (tables.comp_of_switch[r] == tables.comp_of_switch[s_i])
            if spec.policy == "valiant":
                i_mid = jnp.where(ok, r, d_i)
                i_phase = jnp.where(ok, 0, 1).astype(_I32)
            else:  # adaptive: congestion-threshold detour (UGAL-style)
                per_port_occ = occ.reshape(n_links, v).sum(axis=1)
                base = tables.copybase_of_term

                def congestion(port_local):
                    link_local = s_i * p + port_local
                    backlog = per_port_occ[
                        base + tables.feeder_local[link_local]]
                    return state.pressure[base + link_local] + backlog

                safe_d = jnp.where(d_i != s_i, d_i, (s_i + 1) % n)
                c_min = congestion(tables.port_table[s_i, safe_d])
                c_val = congestion(tables.port_table[s_i, r])
                detour = (c_min > spec.weight * c_val + spec.threshold) & ok
                i_mid = jnp.where(detour, r, d_i)
                i_phase = jnp.where(detour, 0, 1).astype(_I32)

        i_tgt = jnp.where(i_phase == 1, i_dst, i_mid)
        i_src = src[ip]
        i_tgt = jnp.where(i_tgt != i_src, i_tgt, (i_src + 1) % n)
        i_port = tables.port_table[i_src, i_tgt]

    with jax.named_scope("arbitrate"):
        # 4. link arbitration with credit check -------------------------------
        # Contender block per switch: its pv queue heads then its t terminals.
        # The attribute word carries (dst, mid, phase, hops-after-this-hop),
        # so the requested VC class is derived from it: min(hops - 1, V-1).
        act = jnp.concatenate([transit.reshape(blocks, pv),
                               inj_valid.reshape(blocks, t)], axis=1)
        port_x = jnp.concatenate([t_port.reshape(blocks, pv),
                                  i_port.reshape(blocks, t)], axis=1)
        pid_x = jnp.concatenate([pid.reshape(blocks, pv),
                                 ip.reshape(blocks, t)], axis=1)
        attr_x = jnp.concatenate([
            _pack_attr(layout, h_dst, h_mid, h_phase, h_hops + 1
                       ).reshape(blocks, pv),
            _pack_attr(layout, i_dst, i_mid, i_phase,
                       jnp.ones(nt_flat, _I32)).reshape(blocks, t)], axis=1)
        vc_x = jnp.minimum((attr_x & hop_mask) - 1, v - 1)

        # Credit check against the downstream (port, VC) queue of each
        # contender's requested link.  The downstream (switch, input-port) of
        # link (s, i) is ``feeder_local[s*p + i]`` — the same inverse-wire
        # table that routes pushes, read in the other direction.
        link_local_x = jnp.concatenate(
            [(sw_q * p + t_port).reshape(blocks, pv),
             (i_src * p + i_port).reshape(blocks, t)], axis=1)
        dq = ((tables.copybase_of_block[:, None]
               + tables.feeder_local[link_local_x]) * v + vc_x)
        # Unwired slots (feeder_local == -1), including links a FailureSpec
        # killed, are permanently credit-starved: well-formed routing never
        # requests them, and this mask keeps any stray request from reading
        # a garbage queue's occupancy and winning arbitration on it.
        feas = act & (tables.feeder_local[link_local_x] >= 0) & (occ[dq] < cap)

        # Arbitration randomness: transit lanes use the low half of their
        # lane word (the high half fed ejection); terminal lanes use the top
        # of their word (the bottom 14 bits fed the Valiant-mid sample).
        rand = jnp.concatenate(
            [((lane_bits & np.uint32(0xFFFF))
              >> np.uint32(16 - rand_bits)).astype(_I32).reshape(blocks, pv),
             (term_bits >> np.uint32(32 - rand_bits)
              ).astype(_I32).reshape(blocks, t)],
            axis=1)
        cls = (jnp.arange(x, dtype=_I32) >= pv).astype(_I32)[None, :]
        packed = ((((cls << rand_bits) | rand) << x_bits) | jnp.arange(
            x, dtype=_I32)[None, :]).astype(key_dtype)
        # (blocks, x, p) one-hot expansion; one min-reduction per port gives
        # the winning key and the winner's contender index in its low bits.
        key_m = jnp.where(
            feas[:, :, None] & (port_x[:, :, None] == jnp.arange(p)),
            packed[:, :, None], key_dtype(sent))
        minval_flat = jnp.min(key_m, axis=1).reshape(n_links).astype(_I32)

        if spec.policy == "adaptive":
            # EWMA of requested (pre-credit) demand — only adaptive reads it.
            req = act[:, :, None] & (port_x[:, :, None] == jnp.arange(p))
            demand = jnp.sum(req, axis=1).reshape(n_links)
            pressure = (state.pressure
                        + spec.alpha * (demand - state.pressure))
        else:
            pressure = state.pressure

    with jax.named_scope("move"):
        # 5. movement ---------------------------------------------------------
        # Transit pop: queue lane q wins iff the winner of its requested link
        # is contender q itself (sentinel's index field cannot match).
        win_t = transit & ((minval_flat[tables.linkbase_of_lane + t_port]
                            & x_mask) == tables.x_of_lane)
        occ = occ - win_t.astype(_I16)
        head = head + win_t.astype(_I16)

        # Injection advance: terminal lane wins iff the winner of its link is
        # contender pv + (lane's slot within the switch).
        i_win = inj_valid & ((minval_flat[tables.linkbase_of_term + i_port]
                              & x_mask) == pv + tables.slot_of_term)
        term_next = state.term_next + i_win.astype(_I32)

        # Push as a gather: queue (sw', p', vc') receives the winner of its
        # feeder link (the wire into input port p') when the VC matches.
        mv = minval_flat[tables.feeder_flat]
        recv_x = tables.feeder_xbase + (mv & x_mask)
        pair_x = jnp.stack([pid_x, attr_x], axis=-1).reshape(blocks * x, 2)
        pair_w = pair_x[recv_x]                     # (Q, 2): pid, attr
        pid_w, attr_w = pair_w[:, 0], pair_w[:, 1]
        vc_w = jnp.minimum((attr_w & hop_mask) - 1, v - 1)
        recv = tables.wired_q & (mv != sent) & (vc_w == tables.vc_of_lane)
        # Phase flips on arrival at the Valiant intermediate — which, seen
        # from the receiving queue, is simply its own switch.
        _, w_mid, w_phase, _ = _unpack_attr(layout, attr_w)
        attr_w = jnp.where((w_phase == 0) & (w_mid == tables.sw_local),
                           attr_w | (1 << hop_bits), attr_w)

        slot = (head + occ) % cap
        onehot = (jnp.arange(cap, dtype=_I32)[None, :] == slot[:, None]
                  ) & recv[:, None]
        buf = jnp.where(
            onehot[:, :, None],
            jnp.stack([pid_w, attr_w], axis=-1)[:, None, :], state.buf)
        occ = occ + recv.astype(_I16)
        # Ring-buffer heads live in int16 (the dtype diet halves the hot
        # state); stored mod capacity so they never overflow over long runs.
        head = head % cap

        has_w = minval_flat != sent
        load_total = state.load_total + has_w.astype(_I32)
        load_window = state.load_window + (
            has_w & in_window[tables.copy_of_link]).astype(_I32)

    with jax.named_scope("sample"):
        # -- trace sampling (end of cycle c, after movement) ------------------
        # Gated at Python trace time on the static spec, so an untraced
        # program is byte-for-byte the pre-trace program.  Row writes are
        # read-modify-write: an out-of-range dynamic_update_slice start
        # clamps (it would silently overwrite the last row), so the row is
        # first read and only replaced when this cycle really samples.
        if spec.trace_stride:
            row = jnp.minimum(c // spec.trace_stride, spec.trace_samples - 1)
            write = ((c % spec.trace_stride) == 0) & (
                c // spec.trace_stride < spec.trace_samples)

            def _row_write(rbuf, vec):
                cur = lax.dynamic_slice_in_dim(rbuf, row, 1, axis=0)
                new = jnp.where(write, vec[None, :].astype(rbuf.dtype), cur)
                return lax.dynamic_update_slice_in_dim(rbuf, new, row, axis=0)

            cur_c = lax.dynamic_slice_in_dim(state.tr_cycle, row, 1, axis=0)
            tr_cycle = lax.dynamic_update_slice_in_dim(
                state.tr_cycle, jnp.where(write, c.astype(_I32), cur_c),
                row, axis=0)
            tr_link = _row_write(state.tr_link, load_total)
            tr_occ = _row_write(state.tr_occ,
                                occ.reshape(blocks, pv).sum(axis=1))
            tr_inj = _row_write(state.tr_inj,
                                term_next.reshape(blocks, t).sum(axis=1))
            tr_del = _row_write(state.tr_del, delivered_total)
        else:
            tr_cycle, tr_link = state.tr_cycle, state.tr_link
            tr_occ, tr_inj, tr_del = state.tr_occ, state.tr_inj, state.tr_del

    with jax.named_scope("move"):     # the one op left: cycle c + 1
        return _State(buf=buf, head=head, occ=occ, deliver=deliver,
                      ej_log=ej_log, term_next=term_next, pressure=pressure,
                      load_total=load_total, load_window=load_window,
                      delivered_total=delivered_total,
                      delivered_win=delivered_win, phase_done=phase_done,
                      cycle=c + 1, tr_cycle=tr_cycle, tr_link=tr_link,
                      tr_occ=tr_occ, tr_inj=tr_inj, tr_del=tr_del)


def _init_state(spec: XSpec, pkt: dict) -> _State:
    """The loop carry before cycle 0 for the copies ``pkt`` holds."""
    n, p, v = spec.n, spec.ports, spec.vcs
    b = pkt["blk_start"].shape[0] // n
    bq = b * n * p * v
    m_flat = pkt["src"].shape[0]
    return _State(
        buf=jnp.full((bq, spec.cap, 2), -1, _I32),
        head=jnp.zeros(bq, _I16),
        occ=jnp.zeros(bq, _I16),
        deliver=jnp.full(m_flat if not spec.log_deliveries else 1, -1, _I32),
        ej_log=jnp.full((spec.horizon if spec.log_deliveries else 1, bq),
                        -1, _I32),
        term_next=jnp.zeros(b * n * spec.terminals, _I32),
        pressure=jnp.zeros(b * n * p, jnp.float32),
        load_total=jnp.zeros(b * n * p, _I32),
        load_window=jnp.zeros(b * n * p, _I32),
        delivered_total=jnp.zeros(b, _I32),
        delivered_win=jnp.zeros(b, _I32),
        phase_done=jnp.full((b, spec.num_phases), -1, _I32),
        cycle=jnp.zeros((), _I32),
        tr_cycle=jnp.full(spec.trace_samples if spec.trace_stride else 1,
                          -1, _I32),
        tr_link=jnp.zeros((spec.trace_samples, b * n * p)
                          if spec.trace_stride else (1, 1), _I32),
        tr_occ=jnp.zeros((spec.trace_samples, b * n)
                         if spec.trace_stride else (1, 1), _I32),
        tr_inj=jnp.zeros((spec.trace_samples, b * n)
                         if spec.trace_stride else (1, 1), _I32),
        tr_del=jnp.zeros((spec.trace_samples, b)
                         if spec.trace_stride else (1, 1), _I32),
    )


def _run_loop(spec: XSpec, tables: _Tables, pkt: dict, key: jax.Array,
              warmup: jax.Array) -> dict:
    """One device's whole run: state init, the cycle loop, output dict.

    Shapes derive from the *local* packet/block arrays, so the same body
    serves the single-device jit (:data:`_run_flat`, all copies in one
    flat state) and each shard of :func:`_sharded_runner` (a contiguous
    block of copies per device).  The static ``spec.horizon``/``cutoff``
    only size allocations and trip counts; the *measured* bounds are the
    runtime ``pkt["h_eff"]``/``pkt["cutoff_eff"]`` scalars, so a
    bucket-padded program computes exactly what the exact-shape program
    would (see :func:`sweep`).
    """
    n = spec.n
    b = pkt["blk_start"].shape[0] // n
    state = _init_state(spec, pkt)

    def body(st: _State):
        return _step(spec, tables, pkt, key, warmup, st)

    # All copies of one program share a horizon by construction, so the
    # per-copy runtime bounds collapse to scalars.
    h_eff = pkt["h_eff"][0]
    if spec.drain:
        total_m = jnp.sum(pkt["m_real"])
        cutoff_eff = pkt["cutoff_eff"][0]

        def cond(st: _State):
            return (st.cycle < h_eff) | (
                (jnp.sum(st.delivered_total) < total_m)
                & (st.cycle < cutoff_eff))

        final = lax.while_loop(cond, body, state)
    else:
        # Static trip count: unrolling folds several cycles into each XLA
        # loop iteration, amortizing per-op dispatch overhead.  Bucket
        # padding runs the loop to the padded horizon; the cond skips the
        # padded tail cycles, leaving the state untouched past h_eff.
        def step_or_skip(_i, st: _State):
            return lax.cond(st.cycle < h_eff, body, lambda s: s, st)

        final = lax.fori_loop(0, spec.horizon, step_or_skip, state,
                              unroll=8)
    out = {
        "deliver": final.deliver,
        "ej_log": final.ej_log,
        "load_total": final.load_total,
        "load_window": final.load_window,
        "delivered_total": final.delivered_total,
        "delivered_in_window": final.delivered_win,
        "phase_done": final.phase_done,
        "cycle": final.cycle,
        "in_flight": final.occ.reshape(b, -1).sum(axis=1, dtype=_I32),
    }
    if spec.trace_stride:
        out.update(tr_cycle=final.tr_cycle, tr_link=final.tr_link,
                   tr_occ=final.tr_occ, tr_inj=final.tr_inj,
                   tr_del=final.tr_del)
    return out


_run_flat = partial(jax.jit, static_argnums=0)(_run_loop)


@lru_cache(maxsize=None)
def _sharded_runner(spec: XSpec, ndev: int, pkt_keys: tuple):
    """A jitted ``shard_map`` over a ``copies`` mesh axis: each of the
    ``ndev`` devices runs :func:`_run_loop` on its contiguous block of
    fabric copies.  Packet descriptors (``src``/``dst``/``gen``) are
    *replicated* so packet ids stay global — per-shard block bounds,
    delivery records, and ejection logs line up without any remapping —
    while every per-copy array shards along its leading axis.  The copies
    are disjoint fabrics, so the program is SPMD with zero collectives;
    shard outputs gain a leading device axis and reassemble on the host
    (see :func:`sweep`).  Donating the packet/warmup operands lets XLA
    reuse their buffers for the (much larger) state."""
    from jax.sharding import AxisType, PartitionSpec

    mesh = jax.make_mesh((ndev,), ("copies",), axis_types=(AxisType.Auto,))
    rep, shard = PartitionSpec(), PartitionSpec("copies")
    pkt_specs = {k: (rep if k in ("src", "dst", "gen") else shard)
                 for k in pkt_keys}

    def run(tables, pkt, key, warmup):
        out = _run_loop(spec, tables, pkt, key, warmup)
        return jax.tree_util.tree_map(lambda a: a[None], out)

    return jax.jit(jax.shard_map(
        run, mesh=mesh, in_specs=(rep, pkt_specs, rep, shard),
        out_specs=shard, check_vma=False), donate_argnums=(1, 3))


def _resolve_devices(devices) -> int:
    """Number of devices to shard the fabric copies across.

    ``None``/``1`` = the classic single-program path; ``"auto"`` = every
    visible JAX device; an int is validated against availability (on CPU,
    ``XLA_FLAGS=--xla_force_host_platform_device_count=<n>`` exposes n
    host devices)."""
    if devices is None:
        return 1
    avail = jax.local_device_count()
    if devices == "auto":
        return max(avail, 1)
    ndev = int(devices)
    if ndev < 1:
        raise ValueError(f"devices={devices!r} must be >= 1")
    if ndev > avail:
        raise ValueError(
            f"devices={ndev} but only {avail} JAX device(s) are visible; "
            f"on CPU set XLA_FLAGS=--xla_force_host_platform_device_count="
            f"{ndev} before importing jax")
    return ndev


# ---------------------------------------------------------------------------
# Host-side API.
# ---------------------------------------------------------------------------

def _default_num_vcs(topo: SimTopology, policy: RoutingPolicy) -> int:
    return topo.diameter * (2 if policy.vc_required > 1 else 1)


def _build_tables(topo: SimTopology, links: LinkTable, b: int,
                  terminals: int, num_vcs: int) -> _Tables:
    """Topology tables + flat index vectors for ``b`` fabric copies."""
    n, p, v, t = topo.num_switches, topo.num_ports, num_vcs, terminals
    pv, x = p * v, p * v + terminals
    nbr = links.neighbor_flat.astype(np.int64)
    rev = links.rev_flat.astype(np.int64)
    feeder_local = np.where(nbr >= 0, nbr * p + rev, -1)

    lanes = np.arange(b * n * pv, dtype=np.int64)
    copy_of_lane = lanes // (n * pv)
    block_of_lane = lanes // pv
    qport_local = (lanes % (n * pv)) // v
    f_local = feeder_local[qport_local]
    feeder_flat = np.clip(copy_of_lane * (n * p) + f_local, 0,
                          b * n * p - 1)
    ti = np.arange(b * n * t, dtype=np.int64)
    term_block = ti // t
    blk_idx = term_block                         # flat (copy, switch)
    link_ids = np.arange(b * n * p, dtype=np.int64)
    faults = (topo.meta or {}).get("faults")
    comp = (faults["comp"] if faults is not None
            else np.zeros(n, dtype=np.int64))
    as_i32 = lambda a: jnp.asarray(a, _I32)  # noqa: E731
    return _Tables(
        port_table=as_i32(topo.minimal_port_table()),
        comp_of_switch=as_i32(comp),
        feeder_local=as_i32(feeder_local),
        sw_local=as_i32((lanes % (n * pv)) // pv),
        x_of_lane=as_i32(lanes % pv),
        vc_of_lane=as_i32(lanes % v),
        linkbase_of_lane=as_i32(block_of_lane * p),
        feeder_flat=as_i32(feeder_flat),
        feeder_xbase=as_i32((feeder_flat // p) * x),
        wired_q=jnp.asarray(f_local >= 0),
        blk_idx=as_i32(blk_idx),
        slot_of_term=as_i32(ti % t),
        linkbase_of_term=as_i32(term_block * p),
        copybase_of_term=as_i32((ti // (n * t)) * (n * p)),
        copybase_of_block=as_i32((np.arange(b * n) // n) * (n * p)),
        copy_of_link=as_i32(link_ids // (n * p)))


@span_record()
def sweep(topo: SimTopology, policy, traffic_factory: Callable,
          loads: Sequence[float], *, seeds: Sequence[int] = (0,),
          terminals: int | None = None, eject_bw: int | None = None,
          num_vcs: int | None = None, queue_capacity: int = 4,
          cycles: int | None = None, warmup: int | None = None,
          drain: bool | None = None, max_cycles: int | None = None,
          trace=None, bucket: bool | None = None,
          devices=None) -> list[list[RunStats]]:
    """An entire saturation sweep as one compiled program.

    Every (offered load, seed) point becomes one replicated fabric copy
    inside a single jit-compiled run (see the module docstring), so the
    whole grid costs one compile + one device program.  Returns a
    ``[load][seed]`` grid of :class:`RunStats` built by the same metrics
    pipeline as the oracle engine.

    ``traffic_factory`` is called as ``factory(load, seed)`` when it
    accepts two positional arguments, else ``factory(load)`` (the oracle
    sweep's convention, reusing one packet set across seeds).  All grid
    points share one simulated horizon (they are one program): ``cycles=``
    pins it, otherwise it is derived from the traffic objects as the max
    generation window over the grid.  ``terminals`` defaults to the
    traffic objects' own record.  Per-point arbitration streams derive
    from a key over the full seed tuple.

    Every point's stats carry a shared ``timing`` record splitting the
    program's compile time from its execution
    (:func:`repro.obs.telemetry.timed_compiled`), and the seconds of the
    sweep's host spans (``sweep.traffic``, ``sweep.pack``,
    ``sweep.tables``, ``sweep.transfer``, ``sweep.acquire``,
    ``sweep.execute``, ``sweep.fetch``, ``sweep.stats``; see
    :func:`repro.obs.telemetry.span`) under ``<span>_s``.  ``trace``
    (anything :meth:`repro.obs.TraceConfig.coerce` accepts) compiles statically
    shaped time-series ring buffers into the loop — per-point
    :class:`~repro.obs.Trace` objects land on ``stats.trace``.  Packet
    spans (``TraceConfig.packets``) are a numpy-engine feature and are
    ignored here.

    ``bucket`` (default on) rounds the program's *static* shapes — grid
    width, packet count, horizon, drain cutoff — up to
    :func:`_bucket_count` boundaries, so nearby sweep sizes share one
    compiled program (and one persistent-cache entry) instead of
    compiling each.  The padding is fully masked: padded copies carry no
    packets, padded packet slots never become eligible, padded cycles
    are skipped by the runtime ``h_eff`` bound, and the per-copy RNG
    streams are keyed on global copy ids — so a bucketed run is
    *bit-identical* to the exact-shape run (``tests/test_conformance.py``
    pins this).  ``bucket=False`` restores exact shapes.

    ``devices`` shards the fabric copies across JAX devices with
    ``shard_map`` (``None`` = single device, ``"auto"`` = all visible,
    or an int).  Copies are independent fabrics, so sharding is SPMD
    with zero collectives and also bit-identical to the single-device
    program.  Tracing forces the single-device path.
    """
    policy = _resolve_policy(policy)
    seeded_factory = _accepts_seed(traffic_factory)
    n = topo.num_switches
    with span("sweep.traffic"):
        grid: list[tuple[float, int, Traffic]] = []
        for load in loads:
            for seed in seeds:
                tr = (traffic_factory(load, seed) if seeded_factory
                      else traffic_factory(load))
                grid.append((load, seed, tr))
    if not grid:
        return []

    resolved_t = {resolve_terminals(tr, terminals) for _, _, tr in grid}
    if len(resolved_t) > 1:
        raise ValueError(
            f"a batched sweep shares one injector count across the grid "
            f"but the traffic objects record terminals="
            f"{sorted(resolved_t)}; use one terminals value per sweep")
    terminals = resolved_t.pop()

    # Collective replays (traffic.workload set) compile the phase barrier
    # into the program: all-or-none across the grid (the barrier changes
    # the injection gate's meaning), one static phase-window count.
    wls = [tr.workload for _, _, tr in grid]
    replaying = any(w is not None for w in wls)
    if replaying and not all(w is not None for w in wls):
        raise ValueError("a batched sweep cannot mix collective-replay "
                         "workloads with open-loop traffic")
    num_phases = max((w.num_phases for w in wls), default=0) if replaying \
        else 0
    replaying = num_phases > 0

    if drain is None:
        drain = all(tr.offered == 0 for _, _, tr in grid)
    if num_vcs is None:
        num_vcs = _default_num_vcs(topo, policy)
    if num_vcs > _MAX_HOPS + 1:
        raise ValueError(f"compiled engine packs hop counts into 7 bits; "
                         f"num_vcs={num_vcs} is out of range")

    sizes = [tr.num_packets for _, _, tr in grid]
    bases = np.concatenate([[0], np.cumsum(sizes)[:-1]]).astype(np.int64)
    with span("sweep.pack"):
        packed = [_pack_traffic(tr, n, int(bases[i]))
                  for i, (_, _, tr) in enumerate(grid)]
    # One program = one horizon.  cycles= pins it; otherwise take the max
    # generation window over the grid so no point's traffic is truncated
    # (points with shorter windows simply stop generating early).
    if cycles is not None:
        horizon = int(cycles)
    else:
        windows = {max(tr.horizon, 1) for _, _, tr in grid}
        horizon = int(max(windows))
        if len(windows) > 1:
            import warnings
            warnings.warn(
                f"batched sweep derived a shared horizon of {horizon} "
                f"cycles from traffic windows {sorted(windows)}; points "
                f"with shorter generation windows are still measured over "
                f"the shared horizon, which dilutes their accepted "
                f"throughput — pass cycles= to pin one window",
                stacklevel=2)
    default_warmup = 0 if replaying else horizon // 4
    warmups = [default_warmup if warmup is None else warmup] * len(grid)
    cutoff = int(max_cycles if max_cycles is not None
                 else horizon + _DRAIN_SLACK)
    bucket = True if bucket is None else bool(bucket)
    trace_cfg = TraceConfig.coerce(trace)
    # Trace ring buffers slice per-copy columns host-side; the (rare,
    # small) traced runs stay on the classic single-device path.
    ndev = 1 if trace_cfg is not None else _resolve_devices(devices)
    b_real = len(grid)
    b_pad = _bucket_count(b_real) if bucket else b_real
    b_pad = -(-b_pad // ndev) * ndev          # whole copy blocks per device
    h_static = _bucket_count(horizon) if bucket else horizon
    c_static = max(_bucket_count(cutoff) if bucket else cutoff, h_static)
    q_flat = b_pad * n * topo.num_ports * num_vcs
    log_deliveries = (not drain
                      and h_static * q_flat <= _LOG_ENTRY_BUDGET)
    if trace_cfg is not None:
        # Static row budget: a drain run can stop anywhere below the
        # cutoff, so allocate for the worst case (capped by max_samples);
        # unwritten rows stay at the -1 sentinel and are dropped below.
        # Budgets derive from the *exact* span — padded cycles never run.
        sampled = cutoff if drain else horizon
        trace_samples = min(trace_cfg.max_samples,
                            (max(sampled, 1) - 1) // trace_cfg.stride + 1)
    spec = XSpec(
        n=n, ports=topo.num_ports, vcs=num_vcs, cap=queue_capacity,
        terminals=terminals,
        eject_bw=terminals if eject_bw is None else eject_bw,
        policy=policy.name,
        threshold=float(getattr(policy, "threshold", 0.0)),
        weight=float(getattr(policy, "weight", 0.0)),
        alpha=0.05, drain=bool(drain), horizon=h_static, cutoff=c_static,
        log_deliveries=log_deliveries, num_phases=num_phases,
        trace_stride=0 if trace_cfg is None else trace_cfg.stride,
        trace_samples=0 if trace_cfg is None else trace_samples)

    with span("sweep.tables"):
        links = LinkTable.for_topology(topo, num_vcs)
        tables = _build_tables(topo, links, b_pad // ndev, terminals, num_vcs)

    with span("sweep.pack"):
        flat_np = {k: (np.concatenate([pk[k] for pk in packed])
                       if packed[0][k].ndim else
                       np.asarray([pk[k] for pk in packed]))
                   for k in packed[0]}
        # Bucket the flat packet axis too, with inert padding slots: their
        # generation time is past any horizon, so a padded slot never becomes
        # an injection candidate (this also covers the all-empty grid, whose
        # gathers need at least one in-range slot).  Padded *copies* carry
        # empty source blocks, zero real packets, and warmup 0.
        m_total = int(flat_np["src"].size)
        m_pad = _bucket_count(max(m_total, 1)) if bucket else max(m_total, 1)
        flat_np["src"] = np.concatenate(
            [flat_np["src"], np.zeros(m_pad - m_total, np.int32)])
        flat_np["dst"] = np.concatenate(
            [flat_np["dst"],
             np.full(m_pad - m_total, min(1, n - 1), np.int32)])
        flat_np["gen"] = np.concatenate(
            [flat_np["gen"], np.full(m_pad - m_total, _PAD_GEN, np.int32)])
        pad_b = b_pad - b_real
        flat_np["blk_start"] = np.concatenate(
            [flat_np["blk_start"], np.zeros(pad_b * n, np.int32)])
        flat_np["blk_end"] = np.concatenate(
            [flat_np["blk_end"], np.zeros(pad_b * n, np.int32)])
        flat_np["m_real"] = np.concatenate(
            [flat_np["m_real"], np.zeros(pad_b, np.int32)])
        if replaying:
            # Per-copy cumulative phase sizes, padded to the shared static
            # phase count (padding phases are empty and complete instantly).
            flat_np["phase_cum"] = np.concatenate(
                [np.stack([w.phase_cum(num_phases) for w in wls]),
                 np.zeros((pad_b, num_phases))]).astype(np.int32)
        # Global copy ids (the per-copy RNG fold keys) plus the runtime
        # measurement bounds — per-copy so they shard with the batch.
        flat_np["copy_id"] = np.arange(b_pad, dtype=np.int32)
        flat_np["h_eff"] = np.full(b_pad, horizon, np.int32)
        flat_np["cutoff_eff"] = np.full(b_pad, cutoff, np.int32)

    with span("sweep.tables"):
        # The persistent compile cache keys on content, not object identity:
        # fold the (replicated) topology tables into the entry digest so two
        # fabrics that merely share shapes never alias an entry.
        dig = hashlib.sha256()
        for a in tables:
            dig.update(np.asarray(a).tobytes())
        tab_digest = dig.hexdigest()

    with span("sweep.transfer"):
        flat = {k: jnp.asarray(a) for k, a in flat_np.items()}
        key = jax.random.PRNGKey(
            hash(tuple(s for _, s, _ in grid)) & 0x7FFFFFFF)
        warm_j = jnp.asarray(np.asarray(warmups + [0] * pad_b, np.int32))
    if ndev > 1:
        runner = _sharded_runner(spec, ndev, tuple(sorted(flat)))
        out, timing = timed_compiled(
            runner, None, tables, flat, key, warm_j,
            grid_points=b_real, key_extra=(spec, ndev, tab_digest))
    else:
        out, timing = timed_compiled(
            _run_flat, spec, tables, flat, key, warm_j,
            grid_points=b_real, key_extra=tab_digest)
    with span("sweep.fetch"):
        out = jax.tree_util.tree_map(np.asarray, out)
        if ndev > 1:
            # Host reassembly: shard outputs carry a leading device axis over
            # contiguous copy blocks, so per-copy/per-link vectors flatten
            # straight back into global copy-major order and ejection-log
            # rows concatenate along the lane axis.  Delivery records hold
            # *global* packet ids and are disjoint across shards (-1
            # elsewhere), so an axis-0 max merges them.
            out["deliver"] = out["deliver"].max(axis=0)
            out["ej_log"] = np.concatenate(list(out["ej_log"]), axis=1)
            for k in ("load_total", "load_window", "delivered_total",
                      "delivered_in_window", "in_flight"):
                out[k] = out[k].reshape(-1)
            out["phase_done"] = out["phase_done"].reshape(b_pad, -1)
            out["cycle"] = out["cycle"].max()

    with span("sweep.stats"):
        total_m = max(1, int(sum(sizes)))
        if log_deliveries:
            # Reconstruct per-packet delivery cycles from the per-cycle
            # ejection log: row c holds the pids ejected at cycle c.
            log = out["ej_log"].ravel()
            q_per_cycle = out["ej_log"].shape[1]
            deliver_all = np.full(total_m, -1, np.int64)
            hit = np.flatnonzero(log >= 0)
            deliver_all[log[hit]] = hit // q_per_cycle
        else:
            deliver_all = out["deliver"].astype(np.int64)

        n_links = n * topo.num_ports
        if trace_cfg is not None:
            tr_valid = np.flatnonzero(out["tr_cycle"] >= 0)
            tr_cycles = out["tr_cycle"][tr_valid].astype(np.int64)
        results: list[RunStats] = []
        for i, (load, seed, tr) in enumerate(grid):
            m = int(packed[i]["m_real"])
            delivered_total = int(out["delivered_total"][i])
            if drain and delivered_total < m:
                raise RuntimeError(
                    f"{topo.name}/{policy.name}: {m - delivered_total} "
                    f"packets undelivered after {int(out['cycle'])} cycles "
                    f"(deadlock or cutoff too small)")
            counter = LinkLoadCounter(links)
            counter.total = out["load_total"][
                i * n_links:(i + 1) * n_links].astype(np.int64)
            counter.window = out["load_window"][
                i * n_links:(i + 1) * n_links].astype(np.int64)
            deliver = deliver_all[int(bases[i]):int(bases[i]) + m]
            gen_arg = packed[i]["gen"][:m].astype(np.int64)
            cycles_arg = max(horizon, 1)
            if replaying:
                # Measure over the replay's own timeline (see
                # metrics.replay_timeline): horizon = completion cycle,
                # generation = the cycle each packet's phase released.
                pd = out["phase_done"][i, :wls[i].num_phases]
                cycles_arg, gen_arg = replay_timeline(pd, gen_arg)
            stats = build_stats(
                topology=topo, policy=policy, traffic=tr,
                cycles=cycles_arg, warmup=int(warmups[i]),
                terminals=terminals, gen=gen_arg,
                deliver=deliver, link_counter=counter,
                delivered_in_window=int(out["delivered_in_window"][i]),
                in_flight=int(out["in_flight"][i]))
            if replaying:
                attach_replay(stats, wls[i],
                              out["phase_done"][i, :wls[i].num_phases])
            if tr.request is not None:
                # Serving metrics need request ids in the engine's packet
                # order.  Recompute _pack_traffic's permutation (a stable
                # lexsort over identical inputs — bit-identical to the one
                # the packing used) host-side; the compiled program never
                # sees the request array.
                req = np.asarray(tr.request, dtype=np.int64)
                src64 = tr.src.astype(np.int64)
                gen64 = tr.gen.astype(np.int64)
                sort_key = src64 * (gen64.max(initial=0) + 1) + gen64
                if not np.all(sort_key[1:] >= sort_key[:-1]):
                    req = req[np.lexsort((tr.gen, tr.src))]
                attach_serving(stats, req,
                               packed[i]["gen"][:m].astype(np.int64),
                               deliver, slo=tr.slo)
            stats.timing = timing
            if trace_cfg is not None:
                # Slice copy i's columns out of the flat ring buffers; block
                # bounds come back to local pid space by removing the copy's
                # packet-id base.
                injected = out["tr_inj"][tr_valid][:, i * n:(i + 1) * n
                                                   ].astype(np.int64)
                backlog = derive_backlog(
                    tr_cycles, injected,
                    packed[i]["gen"][:m].astype(np.int64),
                    packed[i]["blk_start"].astype(np.int64) - int(bases[i]),
                    packed[i]["blk_end"].astype(np.int64) - int(bases[i]),
                    phase_done=(out["phase_done"][i, :wls[i].num_phases]
                                if replaying else None))
                stats.trace = Trace(
                    stride=trace_cfg.stride, cycles=tr_cycles,
                    link_load=out["tr_link"][tr_valid][
                        :, i * n_links:(i + 1) * n_links],
                    queue_occ=out["tr_occ"][tr_valid][:, i * n:(i + 1) * n],
                    injected=injected,
                    delivered=out["tr_del"][tr_valid][:, i],
                    backlog=backlog,
                    meta={"topology": topo.name, "policy": policy.name,
                          "backend": "jax", "num_switches": n,
                          "num_ports": topo.num_ports, "terminals": terminals,
                          "load": load, "seed": seed})
            results.append(stats)
    timing["dst_in_word"] = int(_word_layout(n, num_vcs)[1] > 0)
    timing.update(recorded_spans())
    return [results[li * len(seeds):(li + 1) * len(seeds)]
            for li in range(len(loads))]


def simulate_jax(topo: SimTopology, policy, traffic: Traffic, *,
                 terminals: int | None = None, eject_bw: int | None = None,
                 num_vcs: int | None = None, queue_capacity: int = 4,
                 cycles: int | None = None, warmup: int | None = None,
                 drain: bool | None = None, max_cycles: int | None = None,
                 seed: int = 0, trace=None, bucket: bool | None = None,
                 devices=None) -> RunStats:
    """One compiled run (a single-copy :func:`sweep`)."""
    if drain is None:
        drain = traffic.offered == 0
    return sweep(topo, policy, lambda _load: traffic, [traffic.offered],
                 seeds=(seed,), terminals=terminals, eject_bw=eject_bw,
                 num_vcs=num_vcs, queue_capacity=queue_capacity,
                 cycles=cycles, warmup=0 if warmup is None else warmup,
                 drain=drain, max_cycles=max_cycles, trace=trace,
                 bucket=bucket, devices=devices)[0][0]

"""A plain cycle-by-cycle network simulator: the reference that decides
whether the simulator under test produced the right answers.

It implements the model the simulator documents, in numpy, one fabric
at a time, with its own random stream:

* every (switch, input port, VC) pair owns a FIFO of ``queue_capacity``
  packets; a hop is allowed only into a queue with a free slot (credit
  flow control), checked against occupancies after this cycle's
  ejections and before this cycle's departures;
* each cycle, in order: ejection (up to ``eject_bw`` random queue heads
  per switch that reached their destination), routing of the other
  heads along the minimal port table towards their current target,
  injection candidates (terminal lane j of a switch offers packets j,
  j + T, j + 2T, ... of the switch's (src, gen)-ordered source list once
  generated, or once its phase is released in a replay), one winner per
  directed link (transit before injection, ties at random), movement;
* a packet's VC is its hop count so far, capped at V - 1; V defaults to
  the fabric's diameter, doubled for non-minimal policies;
* ``adaptive`` (UGAL-style, local information) decides at every
  injection attempt: with r a uniform intermediate other than source
  and destination, detour through r iff
  ``congestion(minimal first hop) > weight * congestion(first hop
  towards r) + threshold``, where a link's congestion is an EWMA
  (``alpha``) of the requests it received per cycle plus the occupancy,
  over all VCs, of the input port it feeds; a detoured packet turns to
  its destination on arriving at r;
* replays release phase k + 1 in the cycle the last packet of phase k
  ejects (``barrier=False`` drops the barrier: a guarantee the
  benchmark's control alone breaks).

Statistics follow the simulator's documented definitions: latency is
delivery cycle - generation cycle + 1 over delivered packets generated
at or after warmup (all delivered packets if none); accepted is the
window's deliveries per terminal per window cycle; link utilization is
window traversals per wired directed link per window cycle.  A replay is
measured on its own timeline: a packet's generation is the cycle its
phase was released and the run's horizon is the completion cycle.
"""
from __future__ import annotations

import numpy as np

from .fabric import Fabric
from .traffic import Packets


def _winners(group: np.ndarray, keys: tuple, k: int) -> np.ndarray:
    """Positions of up to ``k`` winners per group value, lowest keys
    first (``keys`` most significant first)."""
    order = np.lexsort(tuple(reversed(keys)) + (group,))
    g = group[order]
    rank = np.arange(g.size) - np.searchsorted(g, g, side="left")
    return order[rank < k]


def simulate(fab: Fabric, pk: Packets, *, terminals: int, cycles: int | None,
             warmup: int, policy: str, seed: int, queue_capacity: int = 4,
             num_vcs: int | None = None, eject_bw: int | None = None,
             threshold: float = 1.0, weight: float = 2.0,
             alpha: float = 0.05, max_cycles: int = 100_000,
             barrier: bool = True) -> dict:
    n, p = fab.num_switches, fab.num_ports
    t = int(terminals)
    v = int(num_vcs or fab.diameter * (1 if policy == "minimal" else 2))
    cap = int(queue_capacity)
    k_eject = t if eject_bw is None else int(eject_bw)
    rng = np.random.default_rng(seed)
    replay = pk.phase_sizes is not None
    nbr = fab.neighbor.reshape(-1)
    wired = nbr >= 0
    down_port = np.where(wired, nbr * p + fab.rev.reshape(-1), 0)
    table = fab.port_table

    order = np.lexsort((pk.gen, pk.src))
    src, dst, gen = pk.src[order], pk.dst[order], pk.gen[order]
    m = src.size
    mid = dst.copy()
    phase = np.ones(m, dtype=np.int64)
    hops = np.zeros(m, dtype=np.int64)
    deliver = np.full(m, -1, dtype=np.int64)
    counts = np.bincount(src, minlength=n)
    blk_start = np.concatenate([[0], np.cumsum(counts)[:-1]])
    blk_end = blk_start + counts
    term_sw = np.repeat(np.arange(n), t)
    term_lane = np.tile(np.arange(t), n)
    term_next = np.zeros(n * t, dtype=np.int64)

    q = n * p * v
    buf = np.full((q, cap), -1, dtype=np.int64)
    head = np.zeros(q, dtype=np.int64)
    occ = np.zeros(q, dtype=np.int64)
    pressure = np.zeros(n * p)
    load_total = np.zeros(n * p, dtype=np.int64)
    load_window = np.zeros(n * p, dtype=np.int64)
    delivered = delivered_win = 0

    if replay:
        phase_cum = np.cumsum(pk.phase_sizes)
        phase_done = np.full(phase_cum.size, -1, dtype=np.int64)
        cur_phase = 0
        horizon, meas_end = 1, np.inf
        while cur_phase < phase_cum.size and phase_cum[cur_phase] <= 0:
            phase_done[cur_phase] = 0
            cur_phase += 1
    else:
        horizon, meas_end = int(cycles), int(cycles)

    c = 0
    while c < horizon or (replay and delivered < m and c < max_cycles):
        in_window = warmup <= c < meas_end
        # 1. ejection
        aq = np.flatnonzero(occ > 0)
        hp = buf[aq, head[aq] % cap]
        sw = aq // (p * v)
        done = (sw == dst[hp]) & (phase[hp] == 1)
        if done.any():
            eq, ep = aq[done], hp[done]
            win = _winners(sw[done], (rng.random(eq.size),), k_eject)
            head[eq[win]] += 1
            occ[eq[win]] -= 1
            deliver[ep[win]] = c
            delivered += win.size
            if in_window:
                delivered_win += win.size
            if replay:
                while (cur_phase < phase_cum.size
                       and delivered >= phase_cum[cur_phase]):
                    phase_done[cur_phase] = c
                    cur_phase += 1
        # 2. transit requests
        tq, tp, tsw = aq[~done], hp[~done], sw[~done]
        t_port = table[tsw, np.where(phase[tp] == 1, dst[tp], mid[tp])]
        t_vc = np.minimum(hops[tp], v - 1)
        # 3. injection candidates
        idx = blk_start[term_sw] + term_lane + term_next * t
        ok = idx < blk_end[term_sw]
        limit = (cur_phase if barrier else phase_cum.size) if replay else c
        ok &= gen[np.where(ok, idx, 0)] <= limit
        cand = np.flatnonzero(ok)
        ip = idx[cand]
        s_i, d_i = src[ip], dst[ip]
        if policy == "adaptive" and n >= 3 and ip.size:
            lo, hi = np.minimum(s_i, d_i), np.maximum(s_i, d_i)
            r = rng.integers(0, n - 2, size=ip.size)
            r = r + (r >= lo)
            r = r + (r >= hi)
            per_port = occ.reshape(-1, v).sum(axis=1)

            def congestion(port):
                link = s_i * p + port
                return pressure[link] + per_port[down_port[link]]

            detour = (congestion(table[s_i, d_i])
                      > weight * congestion(table[s_i, r]) + threshold)
            mid[ip] = np.where(detour, r, d_i)
            phase[ip] = np.where(detour, 0, 1)
        elif policy in ("minimal", "adaptive"):
            mid[ip] = d_i
            phase[ip] = 1
        else:
            raise ValueError(f"no reference for routing policy {policy!r}")
        i_port = table[s_i, np.where(phase[ip] == 1, d_i, mid[ip])]
        # 4. arbitration with credits
        nt = tp.size
        r_pid = np.concatenate([tp, ip])
        r_link = np.concatenate([tsw * p + t_port, s_i * p + i_port])
        pressure += alpha * (np.bincount(r_link, minlength=n * p)
                             - pressure)
        r_vc = np.concatenate([t_vc, np.zeros(ip.size, dtype=np.int64)])
        r_dq = down_port[r_link] * v + r_vc
        feas = np.flatnonzero((occ[r_dq] < cap) & wired[r_link])
        if feas.size:
            cls = (feas >= nt).astype(np.int64)
            win = feas[_winners(r_link[feas],
                                (cls, rng.random(feas.size)), 1)]
            # 5. movement
            w_t = win[win < nt]
            head[tq[w_t]] += 1
            occ[tq[w_t]] -= 1
            term_next[cand[win[win >= nt] - nt]] += 1
            pid, dq, lk = r_pid[win], r_dq[win], r_link[win]
            buf[dq, (head[dq] + occ[dq]) % cap] = pid
            occ[dq] += 1
            hops[pid] += 1
            at_mid = (phase[pid] == 0) & (nbr[lk] == mid[pid])
            phase[pid[at_mid]] = 1
            load_total[lk] += 1
            if in_window:
                load_window[lk] += 1
        c += 1
    if replay and delivered < m:
        raise RuntimeError(f"reference replay: {m - delivered} packets "
                           f"undelivered after {c} cycles")

    out = {}
    if replay:
        completion = int(phase_done[-1]) if phase_done.size else 0
        release = np.concatenate([[0], phase_done[:-1]])
        gen = release[gen]
        horizon = max(completion, 1)
        out.update(completion_cycles=completion,
                   phase_cycles=[int(b - a) for a, b in
                                 zip(release, phase_done)])
    meas = max(horizon - warmup, 1)
    got = deliver >= 0
    measured = got & (gen >= warmup)
    if not measured.any():
        measured = got
    lat = deliver[measured] - gen[measured] + 1
    util = load_window[wired] / meas
    mean_util = float(util.mean()) if util.size else 0.0
    out.update(
        packets_generated=int(m),
        packets_delivered=int(got.sum()),
        delivered_in_window=int(delivered_win),
        accepted=delivered_win / (n * t * meas),
        latency_mean=float(lat.mean()) if lat.size else 0.0,
        latency_p50=float(np.percentile(lat, 50)) if lat.size else 0.0,
        latency_p99=float(np.percentile(lat, 99)) if lat.size else 0.0,
        latency_max=int(lat.max()) if lat.size else 0,
        link_util_max=float(util.max()) if util.size else 0.0,
        link_util_mean=mean_util,
        link_util_cv=(float(util.std() / mean_util) if mean_util > 0
                      else 0.0),
        in_flight_at_end=int(occ.sum()),
        link_loads=load_total)
    return out

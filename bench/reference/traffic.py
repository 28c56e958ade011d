"""Packet sets of the benchmark's traffic mixes, made from a seed.

``uniform`` draws what the simulator's own uniform generator draws from
the same seed: per switch and cycle a Poisson(offered * terminals)
arrival count, then a destination uniform over the other switches (the
draw is the generator's published contract, repeated here so that the
reference never reads a packet the program made).  ``a2a_replay`` lists
the phases of the dimension-order all-to-all on a HyperX of Circle
CINs: innermost dimension first, one phase per 1-factor of that
dimension, ``message_size`` packets per (switch, partner) pair.  The
builders of ``reference/mixes/``, which the traffic files name, make a
cell's packets from these.
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

from .fabric import circle_neighbor, circle_ports


class Packets(NamedTuple):
    src: np.ndarray
    dst: np.ndarray
    gen: np.ndarray                  # cycle, or phase ordinal for replays
    phase_sizes: np.ndarray | None   # packets per phase (replays only)


def poisson_arrivals(rng, n: int, rate: float, cycles: int
                     ) -> tuple[np.ndarray, np.ndarray]:
    """(src, gen) of Poisson(rate) arrivals per switch and cycle, sorted
    by source, then cycle: the first draw of every open-loop mix."""
    counts = rng.poisson(rate, size=(n, cycles))
    src = np.repeat(np.arange(n), counts.sum(axis=1)).astype(np.int64)
    gen = np.repeat(np.tile(np.arange(cycles), n),
                    counts.reshape(-1)).astype(np.int64)
    return src, gen


def uniform(n: int, *, offered: float, cycles: int, terminals: int,
            seed: int) -> Packets:
    rng = np.random.default_rng(seed)
    src, gen = poisson_arrivals(rng, n, offered * terminals, cycles)
    d = rng.integers(0, n - 1, size=src.size)
    dst = np.where(d >= src, d + 1, d)
    return Packets(src, dst, gen, None)


def a2a_replay(dims, message_size: int) -> Packets:
    dims = tuple(int(k) for k in dims)
    n = int(np.prod(dims))
    coords = np.zeros((n, len(dims)), dtype=np.int64)
    rem = np.arange(n)
    for d in reversed(range(len(dims))):
        coords[:, d] = rem % dims[d]
        rem = rem // dims[d]
    strides = np.cumprod((1,) + dims[:0:-1])[::-1]
    src, dst, gen, sizes = [], [], [], []
    for d in reversed(range(len(dims))):
        for step in range(circle_ports(dims[d])):
            partner = circle_neighbor(coords[:, d], step, dims[d])
            move = (partner != -1) & (partner != coords[:, d])
            s = np.flatnonzero(move)
            t = s + (partner[move] - coords[move, d]) * strides[d]
            src.append(np.repeat(s, message_size))
            dst.append(np.repeat(t, message_size))
            gen.append(np.full(s.size * message_size, len(sizes)))
            sizes.append(s.size * message_size)
    return Packets(np.concatenate(src), np.concatenate(dst),
                   np.concatenate(gen), np.asarray(sizes, dtype=np.int64))

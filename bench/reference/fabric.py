"""Switch graphs of the benchmark's deployments, built from the published
LACIN definitions alone.

The Circle instance is the round-robin 1-factorization of the complete
graph K_n (LACIN paper, Algorithm 1): for even n, port i of switch s
reaches ``(2i - s) mod (n - 1)``, with switch n-1 as the fixed point
(``s == i`` reaches n-1, switch n-1 reaches i).  Odd n is the even n+1
construction with switch n deleted, so port ``s`` of switch ``s`` idles.
Both ends of a link use the same port index (isoport cabling), and the
port towards b at a is the inverse of that map.

A fabric here is ``(neighbor, rev, port_table)``: ``neighbor[s, i]`` the
switch behind port i of s (-1 idle), ``rev[s, i]`` the arrival port at
the far end, and ``port_table[cur, tgt]`` the output port of the minimal
route at ``cur`` towards ``tgt`` (diagonal unused, 0).
"""
from __future__ import annotations

from typing import NamedTuple

import numpy as np

IDLE = -1


class Fabric(NamedTuple):
    neighbor: np.ndarray      # (N, P)
    rev: np.ndarray           # (N, P)
    port_table: np.ndarray    # (N, N)
    diameter: int

    @property
    def num_switches(self) -> int:
        return self.neighbor.shape[0]

    @property
    def num_ports(self) -> int:
        return self.neighbor.shape[1]


def circle_ports(n: int) -> int:
    return n - 1 if n % 2 == 0 else n


def circle_neighbor(s, i, n: int):
    s = np.asarray(s, dtype=np.int64)
    i = np.asarray(i, dtype=np.int64)
    if n % 2 == 0:
        out = np.mod(2 * i - s, n - 1)
        return np.where(s == n - 1, i, np.where(s == i, n - 1, out))
    return np.where(s == i, IDLE, np.mod(2 * i - s, n))


def circle_port(a, b, n: int):
    """Port at ``a`` whose Circle link reaches ``b`` (a != b): the i with
    2i = a + b modulo the odd modulus."""
    a = np.asarray(a, dtype=np.int64)
    b = np.asarray(b, dtype=np.int64)
    if n % 2 == 0:
        out = np.mod((a + b) * (n // 2), n - 1)   # n/2 inverts 2 mod n-1
        return np.where(a == n - 1, b, np.where(b == n - 1, a, out))
    return np.mod((a + b) * ((n + 1) // 2), n)


def hyperx(dims, instance: str = "circle") -> Fabric:
    """HyperX: one Circle CIN per dimension, ports of dimension 0 first;
    switch index is mixed-radix with the last dimension least
    significant; minimal routing corrects the first differing dimension
    first."""
    if instance != "circle":
        raise ValueError(f"only the circle instance is modelled, not "
                         f"{instance!r}")
    dims = tuple(int(k) for k in dims)
    n = int(np.prod(dims))
    coords = np.zeros((n, len(dims)), dtype=np.int64)
    rem = np.arange(n)
    for d in reversed(range(len(dims))):
        coords[:, d] = rem % dims[d]
        rem = rem // dims[d]
    strides = np.ones(len(dims), dtype=np.int64)
    for d in reversed(range(len(dims) - 1)):
        strides[d] = strides[d + 1] * dims[d + 1]
    cols = [circle_ports(k) for k in dims]
    bases = np.concatenate([[0], np.cumsum(cols)[:-1]]).astype(np.int64)
    p = int(sum(cols))
    neighbor = np.full((n, p), IDLE, dtype=np.int64)
    rev = np.full((n, p), IDLE, dtype=np.int64)
    for d, k in enumerate(dims):
        for i in range(cols[d]):
            digit = circle_neighbor(coords[:, d], i, k)
            ok = digit != IDLE
            sw = np.arange(n) + (digit - coords[:, d]) * strides[d]
            neighbor[ok, bases[d] + i] = sw[ok]
            rev[ok, bases[d] + i] = bases[d] + i
    cur = np.repeat(np.arange(n), n)
    tgt = np.tile(np.arange(n), n)
    cc, tc = coords[cur], coords[tgt]
    first = np.argmax(cc != tc, axis=1)
    table = np.zeros(n * n, dtype=np.int64)
    for d, k in enumerate(dims):
        m = (first == d) & (cur != tgt)
        table[m] = bases[d] + circle_port(cc[m, d], tc[m, d], k)
    return Fabric(neighbor, rev, table.reshape(n, n), len(dims))


def dragonfly(group_size: int, global_ports: int, num_groups: int) -> Fabric:
    """Dragonfly with a Circle CIN inside each group and a Circle CIN
    over the groups.  Switch index is ``group * a + local``; local ports
    come first, then the ``h`` global ports.  Global colour c of group
    grp (the global CIN's port towards group ``(2c - grp) mod g``) lives
    on switch ``c' // h``, slot ``c' % h``, where c' is c with the
    group's idle colour squeezed out (odd g); colours past the group's
    ports stay unwired.  Minimal routing is local-global-local through
    the one global link of the group pair."""
    a, h, g = int(group_size), int(global_ports), int(num_groups)
    n = a * g
    la = circle_ports(a)
    p = la + h
    odd = g % 2 == 1

    def squeeze(grp, colour):
        return colour - (colour > grp) if odd else colour

    neighbor = np.full((n, p), IDLE, dtype=np.int64)
    rev = np.full((n, p), IDLE, dtype=np.int64)
    sw = np.arange(n)
    grp, loc = sw // a, sw % a
    for i in range(la):
        t = circle_neighbor(loc, i, a)
        ok = t != IDLE
        neighbor[ok, i] = (grp * a + t)[ok]
        rev[ok, i] = i
    for slot in range(h):
        k = loc * h + slot
        colour = k + (k >= grp) if odd else k
        ok = colour < circle_ports(g)
        peer = circle_neighbor(grp, np.where(ok, colour, 0), g)
        ok &= peer != IDLE
        far = squeeze(peer, colour)          # isoport: same colour back
        neighbor[ok, la + slot] = (peer * a + far // h)[ok]
        rev[ok, la + slot] = (la + far % h)[ok]
    cur = np.repeat(sw, n)
    tgt = np.tile(sw, n)
    gc, sc, gd, sd = cur // a, cur % a, tgt // a, tgt % a
    table = np.zeros(n * n, dtype=np.int64)
    same = (gc == gd) & (cur != tgt)
    table[same] = circle_port(sc[same], sd[same], a)
    diff = gc != gd
    eff = squeeze(gc[diff], circle_port(gc[diff], gd[diff], g))
    exit_sw, slot = eff // h, eff % h
    at_exit = sc[diff] == exit_sw
    table[diff] = np.where(at_exit, la + slot,
                           circle_port(sc[diff], np.where(at_exit, 0,
                                                          exit_sw), a))
    return Fabric(neighbor, rev, table.reshape(n, n), 3)


def num_switches(fabric: dict) -> int:
    """The switch count of a configuration file's ``fabric`` entry."""
    kind, prm = fabric["kind"], fabric["params"]
    if kind == "dragonfly":
        return int(prm["group_size"]) * int(prm["num_groups"])
    if kind == "hyperx":
        return int(np.prod(prm["dims"]))
    raise ValueError(f"no reference fabric for kind {kind!r}")


def build(fabric: dict) -> Fabric:
    """The fabric a configuration file's ``fabric`` entry names."""
    kind, prm = fabric["kind"], fabric["params"]
    if kind == "dragonfly":
        for key, want in (("local_instance", "circle"),
                          ("global_instance", "circle")):
            if prm.get(key, "circle") != want:
                raise ValueError(f"only circle instances are modelled, "
                                 f"not {key}={prm[key]!r}")
        return dragonfly(prm["group_size"], prm["global_ports_per_switch"],
                         prm["num_groups"])
    if kind == "hyperx":
        return hyperx(prm["dims"], prm.get("instance", "xor"))
    raise ValueError(f"no reference fabric for kind {kind!r}")

"""The least memory traffic of one simulated cycle, for the step's
roofline.

It counts the state the cycle model must touch, whatever implements
it, at fixed widths: every queue slot's packet id and attribute word
(8 bytes), each queue lane's head and occupancy (4), two counters per
directed link (8) and each terminal lane's cursor (4), each read once
and written once.  With Q = copies * N * P * V queue lanes, L = copies *
N * P links and NT = copies * N * T terminal lanes:

    bytes per cycle = 2 * (Q * cap * 8 + Q * 4 + L * 8 + NT * 4)
"""
from __future__ import annotations


def step_bytes(*, copies: int, switches: int, ports: int, vcs: int,
               capacity: int, terminals: int) -> int:
    q = copies * switches * ports * vcs
    links = copies * switches * ports
    nt = copies * switches * terminals
    return 2 * (q * capacity * 8 + q * 4 + links * 8 + nt * 4)

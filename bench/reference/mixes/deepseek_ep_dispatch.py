"""DeepSeek-V3's expert-parallel dispatch and combine on the rows of a
HyperX of Circle CINs, replayed phase by phase (arXiv:2412.19437 §3.2;
DeepEP's "normal" kernels).

Each row (the switches that differ only in the last coordinate) is one
EP group, its switch j the node holding experts j*E/n .. (j+1)*E/n - 1
(E experts, n = the last dimension = the gate's ``n_group``).  The draw
is the simulator's own, repeated here as a frozen contract so that the
reference never reads a packet the program made:

* ``rng = np.random.default_rng(seed)``; for each row r in switch order,
  ``bias = rng.normal(0, sigma, E)``, then for each node s of the row
  ``logits = rng.standard_normal((batch_tokens * ranks_per_node, E)) +
  bias``, in float64;
* ``noaux_tc``: score = sigmoid(logits); a group of E/n_group
  consecutive experts scores the sum of its two best scores; the
  ``topk_group`` best groups are kept and the ``num_experts_per_tok``
  best experts of theirs selected (a set);
* a token of node s sends one dispatch copy to each distinct node of its
  experts other than s, and gets one combine copy back from each;
* one dispatch phase per Circle step k of the last dimension, pair
  (s, partner) carrying ceil(dispatch_bytes / packet_bytes) packets a
  copy from s to its partner; then the same steps as combine phases,
  pair (s, partner) carrying ceil(combine_bytes / packet_bytes) packets
  a copy that the partner sent to s.

Load does not change it; the seed redraws it.
"""
from __future__ import annotations

import numpy as np

from reference import traffic as ref_traffic
from reference.fabric import circle_neighbor, circle_ports


def gate(logits: np.ndarray, moe: dict) -> np.ndarray:
    """Selected experts, (tokens, top-k), by full sorts."""
    t, e = logits.shape
    g = int(moe["n_group"])
    scores = 1.0 / (1.0 + np.exp(-logits))
    best = np.sort(scores.reshape(t, g, e // g), axis=-1)
    group_scores = best[..., -2] + best[..., -1]
    kept = np.argsort(group_scores, axis=-1)[:, -int(moe["topk_group"]):]
    allowed = np.zeros((t, g), dtype=bool)
    np.put_along_axis(allowed, kept, True, axis=-1)
    masked = np.where(np.repeat(allowed, e // g, axis=-1), scores, -np.inf)
    return np.argsort(masked, axis=-1)[:, -int(moe["num_experts_per_tok"]):]


def copies(moe: dict, groups: int, nodes: int, seed: int) -> np.ndarray:
    """``out[r, s, d]``: tokens of node s of row r that send a dispatch
    copy to node d."""
    e = int(moe["n_routed_experts"])
    tokens = int(moe["batch_tokens"]) * int(moe["ranks_per_node"])
    rng = np.random.default_rng(seed)
    out = np.zeros((groups, nodes, nodes), dtype=np.int64)
    for r in range(groups):
        bias = rng.normal(0.0, float(moe["sigma"]), e)
        for s in range(nodes):
            logits = rng.standard_normal((tokens, e)) + bias
            node_of = gate(logits, moe) // (e // nodes)
            for d in range(nodes):
                if d != s:
                    out[r, s, d] = int((node_of == d).any(axis=1).sum())
    return out


def packets(fabric: dict, traffic: dict, terminals: int, load: float,
            seed: int) -> ref_traffic.Packets:
    prm = traffic["traffic"]["params"]
    moe = prm.get("moe")
    if fabric["kind"] != "hyperx" or moe is None:
        raise ValueError(f"no reference MoE dispatch of {prm} on a "
                         f"{fabric['kind']} fabric")
    dims = [int(k) for k in fabric["params"]["dims"]]
    nodes = dims[-1]
    if nodes != int(moe["n_group"]):
        raise ValueError(f"an EP group is a row of {nodes} switches, not "
                         f"{moe['n_group']} nodes")
    groups = int(np.prod(dims)) // nodes
    per_copy = {
        "dispatch": -(-int(moe["dispatch_bytes"]) // int(moe["packet_bytes"])),
        "combine": -(-int(moe["combine_bytes"]) // int(moe["packet_bytes"]))}
    c = copies(moe, groups, nodes, seed)
    src, dst, gen, sizes = [], [], [], []
    node = np.arange(nodes)
    for kind in ("dispatch", "combine"):
        for step in range(circle_ports(nodes)):
            partner = circle_neighbor(node, step, nodes)
            count = 0
            for r in range(groups):
                for j in range(nodes):
                    p = int(partner[j])
                    if p < 0 or p == j:
                        continue
                    k = per_copy[kind] * int(c[r, j, p] if kind == "dispatch"
                                             else c[r, p, j])
                    src.append(np.full(k, r * nodes + j))
                    dst.append(np.full(k, r * nodes + p))
                    gen.append(np.full(k, len(sizes)))
                    count += k
            sizes.append(count)
    return ref_traffic.Packets(
        np.concatenate(src).astype(np.int64),
        np.concatenate(dst).astype(np.int64),
        np.concatenate(gen).astype(np.int64),
        np.asarray(sizes, dtype=np.int64))

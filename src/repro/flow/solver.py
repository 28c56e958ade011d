"""Progressive-filling max-min fair solver over a flow/link incidence.

The flow model reduces every traffic pattern to a *rate allocation
problem*: flows (CSR lists of directed-link ids) with demands, links
with capacities, and the engine-calibrated question "what rate does each
flow sustain?".  The canonical answer for a work-conserving fabric with
per-flow queues is the **max-min fair** allocation, computed here by
progressive filling (Bertsekas & Gallager §6.5.2):

1. raise every active flow's rate at a common speed;
2. the first constraint to bind is either a link running out of residual
   capacity (its flows are *bottlenecked* — frozen at the current level)
   or a flow reaching its demand (frozen *satisfied*);
3. repeat with the survivors until no flow is active.

Each iteration freezes at least one flow, and symmetric patterns freeze
whole equivalence classes at once, so the loop runs for the number of
distinct bottleneck levels — single digits on every in-repo pattern —
with O(nnz) vectorized work per iteration.

Two interchangeable cores: the numpy reference (default) and an optional
jitted JAX core (``lax.while_loop`` over the same update) for the
largest fabrics.  Both return identical allocations to float tolerance;
``solver="auto"`` picks JAX only when the incidence is big enough to
amortize the compile.
"""
from __future__ import annotations

import numpy as np

__all__ = ["maxmin_rates", "maxmin_rates_numpy", "maxmin_rates_jax"]

#: Residual-capacity slack below which a link counts as saturated.  The
#: filling step subtracts ``inc * n_active`` from the binding link's
#: residual, which lands on 0 up to one rounding error of the division
#: that produced ``inc``; 1e-9 is orders above that for unit capacities.
TOL = 1e-9

#: ``solver="auto"``: incidence size (nonzeros) above which the jitted
#: core is worth its per-shape compile.
JAX_NNZ_THRESHOLD = 2_000_000


def _entry_flow(flow_ptr: np.ndarray) -> np.ndarray:
    """Flow index of every CSR entry."""
    counts = np.diff(flow_ptr)
    return np.repeat(np.arange(counts.size), counts)


def maxmin_rates_numpy(demand: np.ndarray, link_idx: np.ndarray,
                       flow_ptr: np.ndarray, capacity: np.ndarray, *,
                       max_iters: int = 256) -> np.ndarray:
    """Max-min fair rates (numpy reference core).

    ``demand``: (F,) offered rate per flow; ``link_idx``/(``flow_ptr``):
    CSR of each flow's *compacted* link indices (a flow crossing a link
    twice lists it twice and consumes capacity twice); ``capacity``:
    (L,) per-link capacity.  Returns (F,) rates with ``0 <= rate <=
    demand``.
    """
    demand = np.asarray(demand, dtype=np.float64)
    capacity = np.asarray(capacity, dtype=np.float64)
    F, L = demand.size, capacity.size
    entry_flow = _entry_flow(np.asarray(flow_ptr))
    link_idx = np.asarray(link_idx)
    rates = np.zeros(F)
    active = demand > TOL
    resid = capacity.copy()
    for _ in range(max_iters):
        if not active.any():
            break
        ea = active[entry_flow]
        n_act = np.bincount(link_idx[ea], minlength=L).astype(np.float64)
        used = n_act > 0
        alpha = np.min(resid[used] / n_act[used]) if used.any() else np.inf
        beta = np.min(demand[active] - rates[active])
        inc = min(alpha, beta)
        if np.isfinite(inc) and inc > 0:
            rates[active] += inc
            resid -= inc * n_act
            np.maximum(resid, 0.0, out=resid)
        tight = used & (resid <= TOL)
        flow_tight = np.zeros(F, dtype=bool)
        if tight.any():
            hit = ea & tight[link_idx]
            flow_tight[entry_flow[hit]] = True
        met = rates >= demand - TOL
        newly = active & (flow_tight | met)
        if not newly.any():
            # Numerical stall (should not happen: inc==alpha saturates a
            # link, inc==beta satisfies a flow).  Freeze the survivors at
            # their current — already fair — rates rather than spin.
            break
        active &= ~newly
    return rates


def _jax_core(demand, entry_flow, link_idx, capacity, max_iters: int):
    import jax.numpy as jnp
    from jax import lax

    F = demand.shape[0]
    L = capacity.shape[0]

    def cond(state):
        i, _rates, active, _resid = state
        return (i < max_iters) & active.any()

    def body(state):
        i, rates, active, resid = state
        ea = active[entry_flow]
        n_act = jnp.zeros(L).at[link_idx].add(ea.astype(jnp.float64))
        used = n_act > 0
        share = jnp.where(used, resid / jnp.maximum(n_act, 1.0), jnp.inf)
        alpha = jnp.min(share)
        beta = jnp.min(jnp.where(active, demand - rates, jnp.inf))
        inc = jnp.minimum(alpha, beta)
        inc = jnp.where(jnp.isfinite(inc) & (inc > 0), inc, 0.0)
        rates = jnp.where(active, rates + inc, rates)
        resid = jnp.maximum(resid - inc * n_act, 0.0)
        tight = used & (resid <= TOL)
        flow_tight = (jnp.zeros(F, dtype=bool)
                      .at[entry_flow].max(ea & tight[link_idx]))
        met = rates >= demand - TOL
        newly = active & (flow_tight | met)
        # Same stall safeguard as the numpy core: no progress deactivates
        # everything (rates already hold the fair allocation so far).
        active = jnp.where(newly.any(), active & ~newly,
                           jnp.zeros_like(active))
        return i + 1, rates, active, resid

    state = (jnp.int32(0), jnp.zeros(F), demand > TOL,
             jnp.asarray(capacity, jnp.float64))
    _, rates, _, _ = lax.while_loop(cond, body, state)
    return rates


_JIT_CACHE: dict = {}


def maxmin_rates_jax(demand: np.ndarray, link_idx: np.ndarray,
                     flow_ptr: np.ndarray, capacity: np.ndarray, *,
                     max_iters: int = 256) -> np.ndarray:
    """The jitted core: one ``lax.while_loop`` program per incidence
    shape (cached process-wide), bit-compatible semantics with
    :func:`maxmin_rates_numpy` up to float tolerance.

    float64 is scoped with ``jax.enable_x64(True)`` rather than the
    global ``jax_enable_x64`` flag so that the int32-typed cycle engines
    sharing the process keep their dtypes."""
    import jax
    key = int(max_iters)
    fn = _JIT_CACHE.get(key)
    if fn is None:
        fn = jax.jit(_jax_core, static_argnums=(4,))
        _JIT_CACHE[key] = fn
    entry_flow = _entry_flow(np.asarray(flow_ptr))
    with jax.enable_x64(True):
        out = fn(np.asarray(demand, np.float64), entry_flow,
                 np.asarray(link_idx), np.asarray(capacity, np.float64),
                 max_iters)
    return np.asarray(out)


def maxmin_rates(demand, link_idx, flow_ptr, capacity, *,
                 max_iters: int = 256, solver: str = "auto") -> np.ndarray:
    """Dispatch: ``"numpy"`` | ``"jax"`` | ``"auto"`` (numpy unless the
    incidence is large enough for the jit to pay for itself)."""
    if solver == "numpy":
        return maxmin_rates_numpy(demand, link_idx, flow_ptr, capacity,
                                  max_iters=max_iters)
    if solver == "jax":
        return maxmin_rates_jax(demand, link_idx, flow_ptr, capacity,
                                max_iters=max_iters)
    if solver != "auto":
        raise ValueError(f"unknown flow solver {solver!r}; "
                         f"expected 'numpy', 'jax' or 'auto'")
    if np.asarray(link_idx).size >= JAX_NNZ_THRESHOLD:
        return maxmin_rates_jax(demand, link_idx, flow_ptr, capacity,
                                max_iters=max_iters)
    return maxmin_rates_numpy(demand, link_idx, flow_ptr, capacity,
                              max_iters=max_iters)

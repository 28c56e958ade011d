"""Training-step extraction: compiled HLO -> replayable ``Workload``.

The bridge from the repo's *runtime* half (jitted training/serving steps
on a device mesh) to its *simulator* half: walk a compiled program's
collective sequence in program order
(:func:`repro.launch.hlo_analysis.collective_sequence`) and lower each
op onto a :class:`~repro.fabric.Fabric`'s own step schedules as
barrier-phased :class:`~repro.sim.workloads.Workload` phases, with
byte-accurate message sizes (``bytes_per_packet`` = the simulated link's
per-cycle payload).

Lowering table (per op of group size N = the fabric's switch count,
``raw`` = the op's per-device result bytes, ``ceil`` division
throughout):

================== ======================== ==========================
HLO op             Workload phases          messages per (src, dst)
================== ======================== ==========================
all-to-all         ``all_to_all`` schedule  ``raw / (N * bpp)``
all-reduce         ``all_reduce`` sequence  ``raw / (N * bpp)``
reduce-scatter     ``reduce_scatter`` half  ``raw / bpp``
all-gather         ``all_gather`` half      ``raw / (N * bpp)``
collective-permute one phase from its       ``raw / bpp``
                   ``source_target_pairs``
================== ======================== ==========================

(The reduce-scatter row uses ``raw / bpp`` because XLA's result shape is
the *scattered output* shard, of which each schedule step moves one full
copy; the other rows split an unsharded payload N ways.)

An op whose ``replica_groups`` size differs from the fabric's switch
count cannot be laid onto that fabric's schedules one-to-one:
``strict=True`` (default) raises, ``strict=False`` skips the op and
records it in the returned workload's name no further — the caller
decides whether a partial replay is meaningful.

Ops inside ``known_trip_count`` while loops repeat their phases
``count`` times (a ``grad_accum``-microbatch scan replays its DP
all-reduce per trip, exactly as the wire would see it).
"""
from __future__ import annotations

import math

from repro.launch.hlo_analysis import CollectiveOp, collective_sequence
from repro.sim.workloads import Phase, Workload, collective_workload

__all__ = ["workload_from_hlo", "compiled_hlo", "moe_step_hlo",
           "dp_step_hlo", "pipeline_step_hlo", "COLLECTIVE_TO_SCHEDULE"]

#: HLO op -> (collective_workload name, payload divisor is N).
COLLECTIVE_TO_SCHEDULE = {
    "all-to-all": ("all_to_all", True),
    "all-reduce": ("all_reduce", True),
    "reduce-scatter": ("reduce_scatter", False),
    "all-gather": ("all_gather", True),
}


def _permute_phases(op: CollectiveOp, n: int, messages: int) -> list[Phase]:
    """A collective-permute is already a single explicit matching."""
    src = tuple(a for a, b in op.pairs if a != b)
    dst = tuple(b for a, b in op.pairs if a != b)
    if not src:
        return []
    bad = [v for v in src + dst if not 0 <= v < n]
    if bad:
        raise ValueError(
            f"collective-permute references device {bad[0]} outside the "
            f"fabric's [0, {n}) switch range")
    return [Phase(src, dst, messages=messages)]


def workload_from_hlo(hlo_text: str, fabric, *, bytes_per_packet: int = 8192,
                      strict: bool = True, name: str | None = None
                      ) -> Workload:
    """Lower a compiled module's collective sequence onto ``fabric``.

    ``fabric`` is anything :func:`repro.fabric.make_fabric` accepts;
    ``bytes_per_packet`` sets the simulated link's per-cycle payload
    (message sizes round *up*, so the replayed bound never undercounts
    wire time).  Returns a phased :class:`Workload` replayable on all
    three backends; raises if the module carries no lowerable
    collective.
    """
    from repro.fabric import Fabric, make_fabric
    if isinstance(fabric, Fabric):
        fab = fabric
    elif isinstance(fabric, tuple):
        fab = make_fabric(*fabric)
    else:
        fab = make_fabric(fabric)
    n = int(fab.num_switches)
    if bytes_per_packet < 1:
        raise ValueError(f"bytes_per_packet must be >= 1, "
                         f"got {bytes_per_packet}")
    seq = collective_sequence(hlo_text, default_group=n)
    phases: list[Phase] = []
    skipped = 0
    for op in seq:
        if op.kind != "collective-permute" and op.group_size != n:
            if strict:
                raise ValueError(
                    f"{op.kind} has replica group size {op.group_size} but "
                    f"fabric {fab.name!r} has {n} switches; extract with a "
                    f"matching fabric, or pass strict=False to skip "
                    f"mismatched ops")
            skipped += op.count
            continue
        if op.kind == "collective-permute":
            messages = max(1, math.ceil(op.raw_bytes / bytes_per_packet))
            per_op = _permute_phases(op, n, messages)
        else:
            sched_name, split_n = COLLECTIVE_TO_SCHEDULE[op.kind]
            div = bytes_per_packet * (n if split_n else 1)
            messages = max(1, math.ceil(op.raw_bytes / div))
            per_op = list(collective_workload(
                fab, sched_name, message_size=messages).phases)
        for _ in range(max(op.count, 1)):
            phases.extend(per_op)
    if not phases:
        raise ValueError(
            f"no lowerable collectives found for fabric {fab.name!r} "
            f"({len(seq)} parsed, {skipped} skipped on group-size "
            f"mismatch); was the program compiled for {n} devices?")
    return Workload(name or f"{fab.name}-hlo", n, tuple(phases))


# ---------------------------------------------------------------------------
# Compiled-program helpers.  These touch jax and must run in a process
# whose XLA_FLAGS requested enough host devices *before* the first jax
# import (see repro.launch.dryrun and ``python -m repro.workload
# extract``, which spawns such a process for you).
# ---------------------------------------------------------------------------

def compiled_hlo(fn, *args, static_argnums=(), **jit_kw) -> str:
    """``jit(fn).lower(*args).compile()`` -> optimized HLO text."""
    import jax
    jitted = jax.jit(fn, static_argnums=static_argnums, **jit_kw)
    return jitted.lower(*args).compile().as_text()


def moe_step_hlo(num_devices: int, *, dp: int = 1, d_model: int = 32,
                 d_ff: int = 16, num_experts: int | None = None,
                 batch: int = 4, seq: int = 8) -> str:
    """Compiled HLO of one expert-parallel MoE forward step.

    The EP axis spans ``num_devices // dp`` shards (the ``"model"`` mesh
    axis the LACIN dispatch/combine all-to-alls ride); requires the
    process to expose ``num_devices`` jax devices.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.models.config import ModelConfig
    from repro.models.layers import AxisRules
    from repro.models.moe import apply_moe, init_moe
    ep = num_devices // dp
    if ep * dp != num_devices:
        raise ValueError(f"dp={dp} must divide num_devices={num_devices}")
    cfg = ModelConfig(
        name="extract-moe", family="moe", num_layers=1, d_model=d_model,
        num_heads=4, num_kv_heads=2, d_ff=d_ff, vocab_size=64,
        num_experts=num_experts if num_experts is not None else ep,
        top_k=2, expert_pad_to=1, capacity_factor=2.0)
    mesh = jax.make_mesh((dp, ep), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = AxisRules(dp=("data",), tp="model", mesh=mesh)
    p = init_moe(jax.random.PRNGKey(0), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(1), (batch, seq, d_model))
    with jax.set_mesh(mesh):
        return compiled_hlo(lambda p_, x_: apply_moe(p_, x_, cfg, rules)[0],
                            p, x)


def _tiny_dense_cfg(name: str, *, num_layers: int, d_model: int) -> "object":
    from repro.models.config import ModelConfig
    return ModelConfig(name=name, family="dense", num_layers=num_layers,
                       d_model=d_model, num_heads=4, num_kv_heads=2,
                       d_ff=2 * d_model, vocab_size=64)


def dp_step_hlo(num_devices: int, *, d_model: int = 32, num_layers: int = 1,
                batch: int = 8, seq: int = 8, compress: bool = False) -> str:
    """Compiled HLO of one explicit-DP train step
    (:func:`repro.runtime.manual_dp.make_manual_dp_train_step`) — the
    LACIN reduce-scatter + all-gather gradient reduction appears as
    ``collective-permute`` chains in the sequence."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.optim import OptConfig
    from repro.runtime.manual_dp import make_manual_dp_train_step
    from repro.runtime.trainer import init_train_state
    if batch % num_devices:
        raise ValueError(f"batch={batch} must divide over "
                         f"num_devices={num_devices}")
    cfg = _tiny_dense_cfg("extract-dp", num_layers=num_layers,
                          d_model=d_model)
    mesh = jax.make_mesh((num_devices,), ("data",),
                         axis_types=(AxisType.Auto,))
    step = make_manual_dp_train_step(cfg, mesh, OptConfig(),
                                     compress=compress)
    state = init_train_state(jax.random.PRNGKey(0), cfg)
    batch_d = {"tokens": jnp.zeros((batch, seq), jnp.int32),
               "labels": jnp.zeros((batch, seq), jnp.int32)}
    return step.lower(state, batch_d).compile().as_text()


def pipeline_step_hlo(num_devices: int, *, d_model: int = 32,
                      layers_per_stage: int = 1, n_micro: int = 2,
                      batch: int = 4, seq: int = 8) -> str:
    """Compiled HLO of one GPipe-style pipeline loss
    (:func:`repro.runtime.pipeline.make_pipeline_loss_fn`) — the
    stage-to-stage shifts appear as ``collective-permute`` ops with
    neighbour ``source_target_pairs``."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import AxisType
    from repro.models.transformer import init_params
    from repro.runtime.pipeline import make_pipeline_loss_fn
    cfg = _tiny_dense_cfg("extract-pipe",
                          num_layers=num_devices * layers_per_stage,
                          d_model=d_model)
    mesh = jax.make_mesh((num_devices,), ("pipe",),
                         axis_types=(AxisType.Auto,))
    loss_fn = make_pipeline_loss_fn(cfg, mesh, n_micro=n_micro)
    params = init_params(jax.random.PRNGKey(0), cfg)
    batch_d = {"tokens": jnp.zeros((batch, seq), jnp.int32),
               "labels": jnp.zeros((batch, seq), jnp.int32)}
    return compiled_hlo(loss_fn, params, batch_d)

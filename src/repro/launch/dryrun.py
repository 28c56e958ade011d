import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any jax import: jax locks the device
# count on first init, and the multi-pod dry-run needs 512 host devices to
# build the production mesh.  (Only the dry-run does this; tests and
# benches see the real single CPU device.)

"""Multi-pod dry-run: lower + compile every (architecture x input-shape)
cell on the production meshes and extract the roofline terms.

    PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both

Outputs one JSON per cell under --out (default results/dryrun/).
"""
import argparse
import json
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.models import SHAPES, cell_is_applicable, get_config, list_archs
from repro.models.transformer import init_caches
from repro.launch.mesh import describe_mesh, make_production_mesh
from repro.launch.specs import input_specs
from repro.launch import analytic
from repro.launch.hlo_analysis import collective_stats, roofline
from repro.optim import OptConfig
from repro.runtime.sharding import (cache_specs, state_specs,
                                    train_batch_specs)
from repro.runtime.trainer import (init_train_state, make_rules,
                                   make_serve_steps, make_train_step,
                                   suggest_grad_accum)

ASSIGNED_ARCHS = ["xlstm-350m", "hymba-1.5b", "nemotron-4-15b",
                  "starcoder2-3b", "llama3.2-3b", "gemma3-1b",
                  "internvl2-26b", "qwen3-moe-30b-a3b",
                  "granite-moe-3b-a800m", "whisper-base"]
ASSIGNED_SHAPES = ["train_4k", "prefill_32k", "decode_32k", "long_500k"]


def _named(mesh, spec_tree):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))


def lower_cell(arch: str, shape_name: str, mesh, *, extra_cfg: dict | None = None):
    """Lower + compile one cell; returns (lowered, compiled, meta)."""
    cfg = get_config(arch)
    if extra_cfg:
        import dataclasses
        cfg = dataclasses.replace(cfg, **extra_cfg)
    shape = SHAPES[shape_name]
    rules = make_rules(mesh)
    chips = int(jax.tree_util.tree_reduce(
        lambda a, b: a * b, list(mesh.shape.values()), 1))
    meta = {"arch": arch, "shape": shape_name, "mesh": dict(mesh.shape),
            "chips": chips}
    specs = input_specs(cfg, shape)

    if shape.kind == "train":
        ga = suggest_grad_accum(cfg, shape.global_batch, shape.seq_len,
                                rules.dp_size)
        meta["grad_accum"] = ga
        state_shapes = jax.eval_shape(
            lambda: init_train_state(jax.random.PRNGKey(0), cfg))
        sspecs = _named(mesh, state_specs(state_shapes["params"], cfg, rules))
        bspecs = _named(mesh, train_batch_specs(cfg, rules))
        bspecs = {k: bspecs[k] for k in specs}  # align key sets
        from repro.runtime.sharding import grad_accum_specs
        gspecs = grad_accum_specs(state_shapes["params"], cfg, rules)
        step = make_train_step(cfg, rules, OptConfig(), grad_accum=ga,
                               grad_specs=gspecs)
        jfn = jax.jit(step, in_shardings=(sspecs, bspecs),
                      out_shardings=(sspecs, None), donate_argnums=(0,))
        lowered = jfn.lower(state_shapes, specs)
    elif shape.kind == "prefill":
        params_shapes = jax.eval_shape(
            lambda: __import__("repro.models.transformer",
                               fromlist=["init_params"]).init_params(
                                   jax.random.PRNGKey(0), cfg))
        from repro.runtime.sharding import param_specs
        pspecs = _named(mesh, param_specs(params_shapes, cfg, rules))
        bspecs = _named(mesh, {k: v for k, v in
                               train_batch_specs(cfg, rules).items()
                               if k in specs})
        cspecs = _named(mesh, cache_specs(cfg, rules, shape.global_batch, shape.seq_len))
        prefill_fn, _ = make_serve_steps(cfg, rules, shape.seq_len)
        jfn = jax.jit(prefill_fn, in_shardings=(pspecs, bspecs),
                      out_shardings=(None, cspecs))
        lowered = jfn.lower(params_shapes, specs)
    else:  # decode
        from repro.models.transformer import init_params
        from repro.runtime.sharding import param_specs
        params_shapes = jax.eval_shape(
            lambda: init_params(jax.random.PRNGKey(0), cfg))
        pspecs = _named(mesh, param_specs(params_shapes, cfg, rules))
        cspecs = _named(mesh, cache_specs(cfg, rules, shape.global_batch, shape.seq_len))
        dp = rules.dp if shape.global_batch >= rules.dp_size else None
        tok_spec = NamedSharding(mesh, P(dp, None))
        pos_spec = NamedSharding(mesh, P())
        _, decode_fn = make_serve_steps(cfg, rules, shape.seq_len)
        jfn = jax.jit(decode_fn,
                      in_shardings=(pspecs, tok_spec, cspecs, pos_spec),
                      out_shardings=(None, cspecs), donate_argnums=(2,))
        lowered = jfn.lower(params_shapes, specs["tokens"], specs["caches"],
                            specs["pos"])
    return cfg, shape, lowered, meta


def analyse(cfg, shape, compiled, meta, *, analytic_kw=None) -> dict:
    chips = meta["chips"]
    ca = compiled.cost_analysis() or {}
    ma = compiled.memory_analysis()
    hlo = compiled.as_text()
    coll = collective_stats(hlo, default_group=16)
    cost = analytic.cell_cost(cfg, shape, chips, **(analytic_kw or {}))
    rt = roofline(
        exec_flops_per_dev=cost.exec_flops_total / chips,
        hbm_bytes_per_dev=cost.hbm_bytes_per_dev,
        wire_bytes_per_dev=coll.total_wire_bytes,
        chips=chips,
        model_flops_total=cost.model_flops_total,
        cost_flops=float(ca.get("flops", 0.0)),
        cost_bytes=float(ca.get("bytes accessed", 0.0)))
    mem = {
        "argument_bytes": ma.argument_size_in_bytes,
        "output_bytes": ma.output_size_in_bytes,
        "temp_bytes": ma.temp_size_in_bytes,
        "alias_bytes": ma.alias_size_in_bytes,
        "peak_estimate_bytes": (ma.argument_size_in_bytes
                                + ma.output_size_in_bytes
                                + ma.temp_size_in_bytes
                                - ma.alias_size_in_bytes),
    }
    out = {**meta,
           "ok": True,
           "memory": mem,
           "fits_16gb_hbm": mem["peak_estimate_bytes"] < 16e9,
           "collectives": {
               "counts": coll.counts,
               "raw_gbytes": {k: v / 1e9 for k, v in coll.raw_bytes.items()},
               "wire_gbytes": {k: v / 1e9 for k, v in coll.wire_bytes.items()},
               "total_wire_gbytes_per_dev": coll.total_wire_bytes / 1e9,
           },
           "analytic_notes": cost.notes,
           "roofline": rt.as_dict()}
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: Path,
             *, extra_cfg=None, analytic_kw=None, tag: str = "",
             mesh_shape=None, mesh_axes=None) -> dict:
    """``mesh_shape``/``mesh_axes``: override the logical mesh (same chips,
    re-labeled axes — a sharding-scheme decision; the physical HyperX
    fabric is unchanged, per §5 multi-digit XOR DOR)."""
    mesh_name = "pod2x16x16" if multi_pod else "pod16x16"
    if mesh_shape:
        mesh_name = "x".join(str(s) for s in mesh_shape)
    cell_id = f"{arch}__{shape_name}__{mesh_name}" + (f"__{tag}" if tag else "")
    ok, reason = cell_is_applicable(arch, shape_name)
    if not ok:
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "ok": False, "skipped": True, "reason": reason}
        _write(out_dir, cell_id, rec)
        print(f"[skip] {cell_id}: {reason}")
        return rec
    t0 = time.time()
    try:
        if mesh_shape:
            from jax.sharding import AxisType
            mesh = jax.make_mesh(
                tuple(mesh_shape), tuple(mesh_axes),
                axis_types=(AxisType.Auto,) * len(mesh_axes))
        else:
            mesh = make_production_mesh(multi_pod=multi_pod)
        cfg, shape, lowered, meta = lower_cell(arch, shape_name, mesh,
                                               extra_cfg=extra_cfg)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower
        rec = analyse(cfg, shape, compiled, meta, analytic_kw=analytic_kw)
        rec["lower_s"] = round(t_lower, 1)
        rec["compile_s"] = round(t_compile, 1)
        _write(out_dir, cell_id, rec)
        r = rec["roofline"]
        print(f"[ok]   {cell_id}: compile={t_compile:.0f}s "
              f"dominant={r['dominant']} "
              f"compute={r['compute_s']*1e3:.2f}ms "
              f"memory={r['memory_s']*1e3:.2f}ms "
              f"collective={r['collective_s']*1e3:.2f}ms "
              f"peak={rec['memory']['peak_estimate_bytes']/1e9:.2f}GB")
        return rec
    except Exception as e:  # noqa: BLE001 — record the failure, keep going
        rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
               "ok": False, "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
        _write(out_dir, cell_id, rec)
        print(f"[FAIL] {cell_id}: {type(e).__name__}: {str(e)[:300]}")
        return rec


def _write(out_dir: Path, cell_id: str, rec: dict):
    out_dir.mkdir(parents=True, exist_ok=True)
    (out_dir / f"{cell_id}.json").write_text(json.dumps(rec, indent=1,
                                                        default=str))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="single")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()
    out_dir = Path(args.out)

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    if args.all:
        cells = [(a, s) for a in ASSIGNED_ARCHS for s in ASSIGNED_SHAPES]
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    n_fail = 0
    for multi in meshes:
        for arch, shape in cells:
            rec = run_cell(arch, shape, multi, out_dir)
            if not rec.get("ok") and not rec.get("skipped"):
                n_fail += 1
    print(f"done; failures: {n_fail}")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()

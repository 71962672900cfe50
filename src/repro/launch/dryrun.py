import os
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this driver builds the production step function (photonic
fabric by default), lowers it against ShapeDtypeStruct stand-ins (weak-type
correct, sharded, ZERO device allocation), compiles, and records:

  * compiled.memory_analysis()  -> fits-per-device proof
  * compiled.cost_analysis()    -> HLO FLOPs / bytes for the roofline
  * collective bytes by mesh axis (parsed from the compiled HLO text)
  * the three roofline terms + bottleneck (EXPERIMENTS.md §Roofline)

Usage:
  python -m repro.launch.dryrun --arch yi_9b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--fabric photonic]
Results cached as JSON under results/dryrun/.
"""
import argparse
import json
import sys
import time
import traceback
from pathlib import Path

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import flops as flopsa
from repro.analysis import memmodel
from repro.analysis.hlo_cost import corrected_cost
from repro.analysis.roofline import from_corrected
from repro.configs.base import (ASSIGNED_ARCHS, SHAPES, ShapeConfig,
                                get_config, shape_applicable)
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as tf
from repro.serve.step import (ServeSetup, make_decode_step,
                              make_prefill_step, _cache_specs)
from repro.train import step as st
from repro.train.step import TrainSetup, make_train_step


def _struct(shape, dtype, mesh, spec):
    return jax.ShapeDtypeStruct(shape, dtype,
                                sharding=NamedSharding(mesh, spec))


def _param_structs(cfg, setup, mesh, rng_unused=None):
    tpl = jax.eval_shape(lambda: tf.init_lm(jax.random.PRNGKey(0), cfg))
    specs = st.state_specs(setup, mesh, tpl)
    params = jax.tree_util.tree_map(
        lambda t, s: _struct(t.shape, t.dtype, mesh, s), tpl, specs)
    return tpl, params, specs


def _batch_structs(cfg, shape: ShapeConfig, mesh, dp_axes):
    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    b, s = shape.global_batch, shape.seq_len
    out = {"tokens": _struct((b, s), jnp.int32, mesh, P(ba, None)),
           "targets": _struct((b, s), jnp.int32, mesh, P(ba, None))}
    if cfg.family == "vlm":
        out["patches"] = _struct((b, cfg.frontend.n_tokens,
                                  cfg.frontend.d_embed), jnp.float32, mesh,
                                 P(ba, None, None))
    if cfg.family == "audio":
        out["frames"] = _struct((b, cfg.frontend.n_tokens,
                                 cfg.frontend.d_embed), jnp.float32, mesh,
                                P(ba, None, None))
    return out


def input_specs(arch: str, shape_name: str, mesh, fabric: str = "photonic"):
    """(fn_to_lower, args_structs) for one cell — ShapeDtypeStruct only."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    dp_axes = st.dp_axes_of(mesh)
    n_dp = 1
    for a in dp_axes:
        n_dp *= dict(zip(mesh.axis_names, mesh.devices.shape))[a]

    if shape.kind == "train":
        setup = TrainSetup(cfg=cfg.replace(remat="full"), fabric=fabric)
        tpl, params, specs = _param_structs(cfg.replace(remat="full"),
                                            setup, mesh)
        opt = {"m": jax.tree_util.tree_map(
                   lambda p: _struct(p.shape, jnp.float32, mesh,
                                     p.sharding.spec), params),
               "v": jax.tree_util.tree_map(
                   lambda p: _struct(p.shape, jnp.float32, mesh,
                                     p.sharding.spec), params),
               "step": jax.ShapeDtypeStruct((), jnp.int32)}
        batch = _batch_structs(cfg, shape, mesh, dp_axes)
        step = make_train_step(setup, mesh, tpl)
        return step, (params, opt, {}, batch)

    if shape.kind == "prefill":
        ssetup = ServeSetup(cfg=cfg, fabric=fabric)
        tsetup = TrainSetup(cfg=cfg, fabric=fabric)
        tpl, params, _ = _param_structs(cfg, tsetup, mesh)
        batch = _batch_structs(cfg, shape, mesh, dp_axes)
        batch.pop("targets")
        step = make_prefill_step(ssetup, mesh, tpl)
        return step, (params, batch)

    # decode kinds
    ctx_shard = shape.global_batch < n_dp
    ssetup = ServeSetup(cfg=cfg, fabric=fabric, context_shard=ctx_shard)
    tsetup = TrainSetup(cfg=cfg, fabric=fabric)
    tpl, params, _ = _param_structs(cfg, tsetup, mesh)
    cap = shape.seq_len
    state_tpl = jax.eval_shape(
        lambda: tf.init_decode_state(cfg, shape.global_batch, cap))
    cspecs = _cache_specs(cfg, dp_axes, context_shard=ctx_shard)
    state = jax.tree_util.tree_map(
        lambda t, s: _struct(t.shape, t.dtype, mesh, s), state_tpl,
        jax.tree_util.tree_unflatten(
            jax.tree_util.tree_structure(state_tpl),
            jax.tree_util.tree_leaves(cspecs,
                                      is_leaf=lambda x: isinstance(x, P))))
    ba = dp_axes if len(dp_axes) > 1 else dp_axes[0]
    tok_spec = P() if ctx_shard else P(ba, None)
    token = _struct((shape.global_batch, 1), jnp.int32, mesh, tok_spec)
    pos = jax.ShapeDtypeStruct((), jnp.int32)
    step = make_decode_step(ssetup, mesh, tpl, batch=shape.global_batch,
                            capacity=cap)
    if cfg.encoder is not None:
        # enc-dec: cross-attention KV cached at prefill time
        enc_struct = jax.ShapeDtypeStruct(
            (shape.global_batch, cfg.encoder.n_frontend_tokens, cfg.d_model),
            jnp.dtype(cfg.dtype))
        cross_tpl = jax.eval_shape(
            lambda p, e: tf.init_cross_state(p, e, cfg), tpl, enc_struct)
        cspec = P() if ctx_shard else P(None, ba, None, None, None)
        cross = jax.tree_util.tree_map(
            lambda t: _struct(t.shape, t.dtype, mesh, cspec), cross_tpl)
        return step, (params, state, token, pos, cross)
    return step, (params, state, token, pos)


def plane_record(cfg, shape: ShapeConfig, axis_sizes) -> dict:
    """Control-plane profile of this cell's job: one steady-state
    iteration through the real Shim/Controller/Orchestrator stack
    (via opus_sim.mesh_plane_profile — same mapping as train.py
    --plane-report), recorded next to the roofline so capacity planning
    sees compute AND reconfiguration cost per cell."""
    from repro.sim.opus_sim import mesh_plane_profile
    if shape.kind != "train":
        return {"skipped": "control plane profiles training cells only"}
    return mesh_plane_profile(cfg, axis_sizes,
                              global_batch=shape.global_batch,
                              seq_len=shape.seq_len)


def model_flops_for(cfg, shape: ShapeConfig) -> float:
    tokens = shape.global_batch * shape.seq_len
    if shape.kind == "train":
        return flopsa.model_flops_train(cfg, tokens)
    if shape.kind == "prefill":
        return flopsa.model_flops_prefill(cfg, tokens)
    return flopsa.model_flops_decode(cfg, shape.global_batch, shape.seq_len)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             fabric: str = "photonic", out_dir: str = "results/dryrun"):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    cell_id = f"{arch}__{shape_name}__{mesh_name}__{fabric}"
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    path = out / f"{cell_id}.json"

    ok, why = shape_applicable(cfg, shape)
    if not ok:
        rec = {"cell": cell_id, "status": "skipped", "reason": why}
        path.write_text(json.dumps(rec, indent=1))
        print(f"[skip] {cell_id}: {why}")
        return rec

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.devices.size
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            fn, args = input_specs(arch, shape_name, mesh, fabric)
            lowered = jax.jit(fn).lower(*args)
            t_lower = time.time() - t0
            compiled = lowered.compile()
            t_compile = time.time() - t0 - t_lower
            try:
                mem = compiled.memory_analysis()
                mem_rec = {
                    "argument_size": getattr(mem, "argument_size_in_bytes", None),
                    "output_size": getattr(mem, "output_size_in_bytes", None),
                    "temp_size": getattr(mem, "temp_size_in_bytes", None),
                    "generated_code_size": getattr(
                        mem, "generated_code_size_in_bytes", None),
                }
            except Exception as e:  # some backends lack it
                mem_rec = {"error": str(e)}
            cost = compiled.cost_analysis() or {}
            if isinstance(cost, list):
                cost = cost[0] if cost else {}
            text = compiled.as_text()
            axis_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
            cc = corrected_cost(text, axis_sizes)
            # roofline memory term: analytic min-traffic model; the parsed
            # HLO byte count (CPU-backend upper bound incl. while-carry
            # copies that TPU aliases) is recorded as corrected_bytes
            tp = axis_sizes.get("model", 1)
            dp = chips // tp
            mem_bytes = memmodel.traffic_for(cfg, shape, tp=tp, dp=dp)
            cc_mem = type(cc)(cc.flops, mem_bytes, cc.collective_bytes,
                              cc.n_while, cc.trip_counts)
            rl = from_corrected(arch, shape_name, mesh_name, chips, cc_mem,
                                model_flops_for(cfg, shape))
            rec = {
                "cell": cell_id, "status": "ok",
                "t_lower_s": round(t_lower, 1),
                "t_compile_s": round(t_compile, 1),
                "memory_analysis": mem_rec,
                # raw XLA numbers (while bodies counted once — see
                # analysis.hlo_cost for the corrected accounting)
                "xla_cost_flops": float(cost.get("flops", 0.0)),
                "xla_cost_bytes": float(cost.get("bytes accessed", 0.0)),
                "corrected_flops": cc.flops,
                "corrected_bytes": cc.bytes_accessed,
                "n_while": cc.n_while,
                "collectives": cc.collective_bytes,
                "roofline": rl.row(),
                "control_plane": plane_record(cfg, shape, axis_sizes),
            }
    except Exception as e:
        rec = {"cell": cell_id, "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    path.write_text(json.dumps(rec, indent=1))
    status = rec["status"]
    extra = ""
    if status == "ok":
        r = rec["roofline"]
        extra = (f" bottleneck={r['bottleneck']}"
                 f" frac={r['roofline_fraction']:.3f}"
                 f" lower={rec['t_lower_s']}s compile={rec['t_compile_s']}s")
    else:
        extra = " " + rec.get("reason", rec.get("error", ""))[:120]
    print(f"[{status}] {cell_id}{extra}", flush=True)
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--fabric", default="photonic",
                    choices=["photonic", "eps"])
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ASSIGNED_ARCHS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]

    failures = 0
    for arch, shape in cells:
        mesh_name = "2x16x16" if args.multi_pod else "16x16"
        pth = Path(args.out) / \
            f"{arch}__{shape}__{mesh_name}__{args.fabric}.json"
        if args.skip_existing and pth.exists():
            rec = json.loads(pth.read_text())
            if rec.get("status") in ("ok", "skipped"):
                print(f"[cached] {rec['cell']} {rec['status']}")
                continue
        rec = run_cell(arch, shape, multi_pod=args.multi_pod,
                       fabric=args.fabric, out_dir=args.out)
        if rec["status"] == "error":
            failures += 1
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()

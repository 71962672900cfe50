"""Training driver: end-to-end loop with checkpointing and fault tolerance.

    PYTHONPATH=src python -m repro.launch.train --arch yi_9b --smoke \
        --steps 50 --mesh 4x2 --fabric photonic --ckpt /tmp/ck --ckpt-every 20

Training runs with full rematerialization, as the dry-run's training cells
do: at published widths the saved activations of a whole layer stack do
not fit a chip otherwise.  ``main`` returns the per-step losses and
cross-entropies and the final state.

Features exercised here (and in examples/ + tests):
  * photonic vs eps fabric selection
  * checkpoint save/restore/reshard (restart on a DIFFERENT mesh works)
  * HSDP + int8 gradient compression (--hsdp --compress)
  * deterministic synthetic data (restarts replay identical batches)
"""
from __future__ import annotations

import argparse
import time

import jax

from repro.configs.base import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as tf
from repro.train import checkpoint as ckpt
from repro.train.data import DataConfig, synth_batch
from repro.train.optimizer import OptConfig
from repro.train.step import TrainSetup, init_sharded_state, make_train_step


def parse_mesh(s=None):
    """``DxM`` (data x model) or ``PxDxM``; None puts every device on
    ``data``."""
    dims = ((jax.device_count(), 1) if s is None
            else tuple(int(x) for x in s.split("x")))
    axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
    return jax.make_mesh(dims, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(dims))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--mesh", default=None,
                    help="DxM or PxDxM; default: every device on data")
    ap.add_argument("--fabric", default="photonic", choices=["photonic", "eps"])
    ap.add_argument("--hsdp", action="store_true")
    ap.add_argument("--compress", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--plane-report", action="store_true",
                    help="after training, replay this job's schedule "
                         "through the real photonic control plane "
                         "(repro.core.plane) and print its telemetry")
    ap.add_argument("--ocs-latency", type=float, default=0.05,
                    help="OCS reconfiguration latency for --plane-report")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke).replace(remat="full")
    mesh = parse_mesh(args.mesh)
    setup = TrainSetup(cfg=cfg, fabric=args.fabric, hsdp=args.hsdp,
                       compress_pod_grads=args.compress, accum=args.accum,
                       opt=OptConfig(lr=args.lr, warmup_steps=10))
    dc = DataConfig(seq_len=args.seq, global_batch=args.batch)
    rng = jax.random.PRNGKey(0)
    tpl = jax.eval_shape(lambda: tf.init_lm(rng, cfg))

    with jax.set_mesh(mesh):
        start = 0
        if args.resume and args.ckpt:
            params, opt, ef, extra = ckpt.restore(args.ckpt, setup, mesh, tpl)
            start = int(extra.get("step", 0))
            print(f"resumed from step {start}")
        else:
            params, opt, ef = init_sharded_state(setup, mesh, rng)
        step_fn = jax.jit(make_train_step(setup, mesh, tpl))

        losses, ces = [], []
        for step in range(start, args.steps):
            batch = synth_batch(cfg, dc, step)
            t0 = time.perf_counter()
            params, opt, ef, m = jax.block_until_ready(
                step_fn(params, opt, ef, batch))
            ms = (time.perf_counter() - t0) * 1e3
            losses.append(float(m["loss"]))
            ces.append(float(m["ce"]))
            print(f"step {step:4d} loss {losses[-1]:.4f} "
                  f"ce {ces[-1]:.4f} gnorm "
                  f"{float(m['grad_norm']):.3f} ({ms:.1f} ms"
                  + (", compile included)" if step == start else ")"),
                  flush=True)
            if args.ckpt and args.ckpt_every and \
                    (step + 1) % args.ckpt_every == 0:
                ckpt.save(args.ckpt, params, opt, ef,
                          extra={"step": step + 1})
                print(f"checkpointed @ {step + 1}")
        if args.ckpt:
            ckpt.save(args.ckpt, params, opt, ef, extra={"step": args.steps})
    if args.plane_report:
        plane_report(cfg, mesh, args.batch, args.seq, args.ocs_latency)
    return {"losses": losses, "ce": ces, "params": params, "opt": opt}


def plane_report(cfg, mesh, global_batch: int, seq_len: int,
                 ocs_latency: float):
    """What the photonic control plane would do for this training job:
    one simulated steady-state iteration through the REAL Shim /
    Controller / RailOrchestrator stack (same mesh -> JobConfig mapping
    as launch/dryrun.py records, via opus_sim.mesh_plane_profile)."""
    from repro.sim.opus_sim import mesh_plane_profile

    ax = dict(zip(mesh.axis_names, mesh.devices.shape))
    p = mesh_plane_profile(cfg, ax, global_batch=global_batch,
                           seq_len=seq_len, ocs_latency=ocs_latency)
    print(f"control plane report (TP={p['tp']} FSDP={p['fsdp']}, "
          f"OCS {ocs_latency*1e3:.0f} ms):")
    over = p["overhead_vs_native"]
    print(f"  modeled step {p['modeled_step_s']:.4g}s "
          + (f"({100*over:.2f}% over native EPS), " if over is not None
             else "(TP-only: no scale-out traffic), ")
          + f"{p['n_reconfigs']} reconfigs")
    print(f"  {p['n_barriers']} barriers, {p['n_dispatches']} dispatches, "
          f"{p['n_topo_writes']} topo_writes, "
          f"{p['n_ports_programmed']} ports programmed")
    rm = p["rail_mapping"]
    ports = rm["ports_per_rail"]
    span = (f"port {ports[0]}" if len(ports) == 1
            else f"ports {ports[0]}-{ports[-1]}")
    print(f"  rail mapping: TP={rm['scale_up_ways']} on scale-up, "
          f"{rm['scale_out_ranks']} scale-out rank"
          f"{'' if rm['scale_out_ranks'] == 1 else 's'}/rail ({span}"
          + (", rail-silent)" if rm["rail_silent"] else ")"))
    return p


if __name__ == "__main__":
    main()

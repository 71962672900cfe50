"""Serving driver: batched prefill + decode on the photonic mesh.

    PYTHONPATH=src python -m repro.launch.serve --arch yi_9b --smoke \
        --mesh 4x2 --batch 8 --prompt-len 12 --gen 20 --plane-report

``--plane-report`` replays the job's schedule through the real photonic
control plane after serving (same mesh -> JobConfig mapping as the train
driver, via ``opus_sim.mesh_plane_profile``) — serve/train parity.

``main`` returns the parameters, the prompts, the logits after the last
prompt token and the generated tokens.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config
from repro.launch.compile_cache import use_compile_cache
from repro.launch.train import parse_mesh
from repro.models import transformer as tf
from repro.serve.step import ServeSetup, init_serve_state, make_decode_step
from repro.train.step import TrainSetup, init_sharded_params


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--mesh", default=None,
                    help="DxM or PxDxM; default: every device on data")
    ap.add_argument("--fabric", default="photonic")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--context-shard", action="store_true")
    ap.add_argument("--plane-report", action="store_true",
                    help="after serving, replay this job's schedule "
                         "through the real photonic control plane "
                         "(repro.core.plane) and print its telemetry")
    ap.add_argument("--ocs-latency", type=float, default=0.05,
                    help="OCS reconfiguration latency for --plane-report")
    args = ap.parse_args(argv)

    use_compile_cache()
    cfg = get_config(args.arch, smoke=args.smoke)
    mesh = parse_mesh(args.mesh)
    rng = jax.random.PRNGKey(0)
    tpl = jax.eval_shape(lambda: tf.init_lm(rng, cfg))
    cap = args.prompt_len + args.gen

    with jax.set_mesh(mesh):
        params = init_sharded_params(
            TrainSetup(cfg=cfg, fabric=args.fabric), mesh, rng)
        ssetup = ServeSetup(cfg=cfg, fabric=args.fabric,
                            context_shard=args.context_shard)
        state = init_serve_state(ssetup, mesh, params, args.batch, cap)
        decode = jax.jit(make_decode_step(ssetup, mesh, tpl,
                                          batch=args.batch, capacity=cap))
        prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                     cfg.vocab_size, jnp.int32)
        # teacher-forced prefill through the decode path (cache build)
        t0 = time.perf_counter()
        for t in range(args.prompt_len):
            logits, state = decode(params, state, prompts[:, t:t + 1],
                                   jnp.int32(t))
        prompt_logits = logits[:, -1]
        out = []
        tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
        for t in range(args.prompt_len, cap):
            logits, state = decode(params, state, tok, jnp.int32(t))
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            out.append(tok)
        generated = jax.block_until_ready(
            jnp.concatenate([prompts[:, :0]] + out, axis=1))
        dt = time.perf_counter() - t0
        toks = args.batch * cap
        print(f"served {args.batch} seqs x {cap} steps in {dt:.2f}s "
              f"({toks/dt:.1f} tok/s aggregate, compile included)")
        print("sample continuation:", [int(x) for x in generated[0, :10]])
    if args.plane_report:
        # serve/train parity: the same mesh -> control-plane mapping the
        # train driver prints (launch.train.plane_report), with the
        # decode capacity standing in for the training sequence length
        from repro.launch.train import plane_report
        plane_report(cfg, mesh, args.batch, cap, args.ocs_latency)
    return {"params": params, "prompts": prompts,
            "prompt_logits": prompt_logits, "generated": generated}


if __name__ == "__main__":
    main()

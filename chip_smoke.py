#!/usr/bin/env python3
"""Run training and serving on one TPU at full published width.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # four chips, photonic vs eps rings

One chip, three phases, all through the Pallas kernels:

* train: five ``mamba2_370m`` steps through ``repro.launch.train``
  (the SSD scan kernel);
* serve: ``granite_moe_1b_a400m`` through ``repro.launch.serve`` at
  capacity 4096 (flash-decode in every step), then the prefill step on the
  same prompts (flash attention); its last-token logits must match the
  decode path's;
* kernels: each Pallas kernel against its ``kernels/ref.py`` oracle, once,
  at the shapes served.

``--four-chips`` runs only a few ``granite_moe_1b_a400m`` train steps on a
4x1 (data x model) mesh through the photonic ring datapath, and the same
steps with ``--fabric eps``; the two cross-entropy curves must agree and
the state must be spread over the four chips.

Every finding is printed on an earlier line; the last line is one JSON
object naming the device.  Anything but a TPU is refused.
"""
from __future__ import annotations

import argparse
import json
import logging
import math
import sys
from contextlib import contextmanager
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

# Tolerances, each relative to the largest magnitude of the reference.
# Kernels take bf16 inputs (2^-8 relative rounding) and reduce over
# thousands of terms; the f32 oracle runs at full matmul precision.
KERNEL_RTOL = 2e-2
# Prefill and decode are the same bf16 model summed in different orders
# through 24 layers (flash over the prompt vs flash-decode per token).
# Decode routes one token at a time and never meets the MoE capacity, so
# the prefill it is checked against holds every token too: at capacity
# factor 1.25 the randomly initialized router sends nearly all of a
# 3584-token prompt to the same experts in the deeper layers, drops up to
# 60% of the choices, and moves the logits by 0.31 of their largest
# magnitude (CPU, float32; 0.33 on the chip).
LOGITS_RTOL = 5e-2
# Photonic and eps steps compute the same cross-entropy in another
# reduction order (bf16 collectives); it agreed within 3e-4 on four v5e
# chips.  Their total losses differ by design: photonic takes the MoE
# balance loss per data shard, eps over the global batch (0.39 vs 0.29
# at step 0 on the chip), and that term's gradient moves the parameters
# a little apart after the first step.
CE_ATOL = 5e-3

ONE_CHIP = {
    "train": {"arch": "mamba2_370m", "batch": 4, "seq": 2048, "steps": 5},
    "serve": {"arch": "granite_moe_1b_a400m", "batch": 8,
              "prompt_len": 3584, "gen": 512},
}
FOUR_CHIPS = {"arch": "granite_moe_1b_a400m", "mesh": "4x1", "batch": 8,
              "seq": 1024, "steps": 3}


@contextmanager
def kernel_paths():
    """{kernel: {path}} of every kernel dispatch traced in the block."""
    paths: dict = {}

    class Collect(logging.Handler):
        def emit(self, record):
            kernel, path = record.args
            paths.setdefault(kernel, set()).add(path)

    logger = logging.getLogger("repro.kernels.ops")
    handler, level = Collect(), logger.level
    logger.addHandler(handler)
    logger.setLevel(logging.INFO)
    try:
        yield paths
    finally:
        logger.removeHandler(handler)
        logger.setLevel(level)


@contextmanager
def compile_seconds():
    """Seconds XLA spent compiling in the block, persistent-cache fetches
    included, and the number of persistent-cache hits."""
    import jax
    acc = {"seconds": 0.0, "cache_hits": 0}

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            acc["seconds"] += secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            acc["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    try:
        yield acc
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
        jax.monitoring.unregister_event_listener(on_event)


def rel_err(got, want) -> float:
    """max|got - want| / max|want|, in float32."""
    import jax.numpy as jnp
    got, want = jnp.asarray(got, jnp.float32), jnp.asarray(want, jnp.float32)
    return float(jnp.max(jnp.abs(got - want)) / jnp.max(jnp.abs(want)))


def check(name: str, err: float, tol: float) -> None:
    print(f"{name}: max|diff|/max|ref| = {err:.3e} (tolerance {tol:g})")
    if not err <= tol:
        raise AssertionError(f"{name}: {err:.3e} exceeds {tol:g}")


def compile_report(comp: dict) -> str:
    return (f"compile {comp['seconds']:.2f} s "
            f"({comp['cache_hits']} persistent-cache hits)")


def peak_bytes() -> str:
    import jax
    stats = jax.devices()[0].memory_stats()
    return ("not reported" if not stats
            else f"{stats['peak_bytes_in_use']} B of "
                 f"{stats.get('bytes_limit', 'unreported')} B")


def train_phase(arch: str, *, smoke: bool = False, mesh: str = "1x1",
                fabric: str = "photonic", batch: int, seq: int,
                steps: int) -> dict:
    """``steps`` train steps through the launcher; every loss finite."""
    from repro.launch import train
    res = train.main(["--arch", arch, "--mesh", mesh, "--fabric", fabric,
                      "--batch", str(batch), "--seq", str(seq),
                      "--steps", str(steps)] + (["--smoke"] if smoke else []))
    losses = res["losses"]
    print(f"train {arch} {fabric} mesh {mesh}: losses {losses}")
    if len(losses) != steps or not all(map(math.isfinite, losses)):
        raise AssertionError(f"train {arch}: losses {losses}")
    return res


def serve_phase(arch: str, *, smoke: bool = False, batch: int,
                prompt_len: int, gen: int) -> dict:
    """Serve through the launcher, then check the prefill step's
    last-token logits against the decode path's at the same position."""
    import dataclasses

    import jax
    from repro.configs.base import get_config
    from repro.launch import serve
    from repro.launch.train import parse_mesh
    from repro.serve.step import ServeSetup, make_prefill_step

    res = serve.main(["--arch", arch, "--mesh", "1x1",
                      "--batch", str(batch), "--prompt-len", str(prompt_len),
                      "--gen", str(gen)] + (["--smoke"] if smoke else []))
    if res["generated"].shape != (batch, gen):
        raise AssertionError(f"generated {res['generated'].shape}")
    cfg = get_config(arch, smoke=smoke)
    if cfg.moe is not None:  # a capacity that no routing can overflow
        cfg = cfg.replace(moe=dataclasses.replace(
            cfg.moe, capacity_factor=cfg.moe.n_experts / cfg.moe.top_k))
    mesh = parse_mesh("1x1")
    tpl = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), res["params"])
    with jax.set_mesh(mesh):
        prefill = jax.jit(make_prefill_step(ServeSetup(cfg=cfg), mesh, tpl))
        logits = prefill(res["params"], {"tokens": res["prompts"]})[:, -1]
    check(f"prefill vs decode logits at position {prompt_len - 1}",
          rel_err(logits, res["prompt_logits"]), LOGITS_RTOL)
    return res


def kernel_phase(*, flash: tuple, decode: tuple, ssd: tuple,
                 interpret: bool = False) -> None:
    """Each Pallas kernel once against its oracle.

    flash (B, S, H, KV, dh); decode (B, C, H, KV, dh, valid);
    ssd (B, S, H, P, G, N, chunk).
    """
    import jax
    import jax.numpy as jnp
    from repro.kernels import decode_attention as da
    from repro.kernels import flash_attention as fa
    from repro.kernels import ref, ssd_scan

    ks = iter(jax.random.split(jax.random.PRNGKey(1), 16))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=jnp.float32):
        return jax.random.normal(next(ks), shape).astype(dtype)

    def compare(name, shape, kernel, oracle, *args):
        got = jax.jit(kernel)(*args)
        with jax.default_matmul_precision("highest"):
            want = jax.jit(oracle)(*args)
        for g, w in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            check(f"kernel {name} {list(shape)} vs ref", rel_err(g, w),
                  KERNEL_RTOL)

    b, s, h, kv, dh = flash
    compare("flash_attention", flash,
            lambda q, k, v: fa.flash_attention(q, k, v, interpret=interpret),
            ref.mha, normal((b, s, h, dh), bf16), normal((b, s, kv, dh), bf16),
            normal((b, s, kv, dh), bf16))

    b, c, h, kv, dh, valid = decode
    mask = jnp.broadcast_to(jnp.arange(c) < valid, (b, c))
    compare("decode_attention", decode,
            lambda q, k, v, m: da.decode_attention(q, k[None], v[None], m,
                                                   0, interpret=interpret),
            ref.decode_attention, normal((b, 1, h, dh), bf16),
            normal((b, kv, dh, c), bf16), normal((b, kv, dh, c), bf16), mask)

    b, s, h, p, g, n, chunk = ssd
    dt = jax.nn.softplus(normal((b, s, h)) - 4.0)     # mamba2's dt range
    a = -jnp.arange(1, h + 1, dtype=jnp.float32)     # mamba2's A init
    compare("ssd", ssd,
            lambda *o: ssd_scan.ssd(*o, chunk, interpret=interpret),
            lambda *o: ref.ssd_chunked(*o, chunk),
            normal((b, s, h, p)), dt, a, normal((b, s, g, n)),
            normal((b, s, g, n)))


def served_kernel_shapes() -> dict:
    """kernel_phase's shapes: those the one-chip train and serve ran."""
    from repro.configs.base import get_config
    from repro.models.ssm import ssm_dims
    t, s = ONE_CHIP["train"], ONE_CHIP["serve"]
    attn, ssm = get_config(s["arch"]), get_config(t["arch"])
    heads = (attn.n_heads, attn.n_kv_heads, attn.resolved_head_dim)
    _, h, p, n = ssm_dims(ssm)
    return {"flash": (s["batch"], s["prompt_len"]) + heads,
            "decode": (s["batch"], s["prompt_len"] + s["gen"]) + heads
            + (s["prompt_len"],),
            "ssd": (t["batch"], t["seq"], h, p, ssm.ssm.n_groups, n,
                    ssm.ssm.chunk_size)}


def state_bytes_per_device(*trees) -> dict:
    """{device id: bytes of the trees' shards held on it}."""
    import jax
    out: dict = {}
    for leaf in jax.tree_util.tree_leaves(trees):
        for shard in leaf.addressable_shards:
            out[shard.device.id] = out.get(shard.device.id, 0) \
                + shard.data.nbytes
    return dict(sorted(out.items()))


def four_chip_phase(arch: str, *, smoke: bool = False, mesh: str,
                    batch: int, seq: int, steps: int) -> None:
    """Photonic ring datapath vs XLA collectives, same seed and batches."""
    import jax
    ces = {}
    for fabric in ("photonic", "eps"):
        res = train_phase(arch, smoke=smoke, mesh=mesh, fabric=fabric,
                          batch=batch, seq=seq, steps=steps)
        per_dev = state_bytes_per_device(res["params"], res["opt"])
        total = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(
            (res["params"], res["opt"])))
        print(f"state bytes per device ({fabric}): {per_dev} "
              f"of {total} B in all")
        # spread: no device holds much more than its even share
        if len(per_dev) < 2 or max(per_dev.values()) > 1.1 * total / len(
                per_dev):
            raise AssertionError(f"state not spread: {per_dev}")
        ces[fabric] = res["ce"]
        print(f"{fabric}: ce {res['ce']}, balance loss "
              f"{[l - c for l, c in zip(res['losses'], res['ce'])]}")
        del res
    diff = max(abs(p - e) for p, e in zip(ces["photonic"], ces["eps"]))
    print(f"photonic vs eps cross-entropy: max|diff| = {diff:.3e} "
          f"(tolerance {CE_ATOL:g})")
    if not diff <= CE_ATOL:
        raise AssertionError(f"photonic ce {ces['photonic']} vs "
                             f"eps ce {ces['eps']}")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the photonic-vs-eps train steps on a "
                         "4x1 mesh")
    args = ap.parse_args(argv)

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, found {dev.platform} "
                 f"({dev.device_kind})")
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    print(f"jax {jax.__version__}, {len(jax.devices())} x {dev.device_kind}")

    if args.four_chips:
        with compile_seconds() as comp:
            four_chip_phase(**FOUR_CHIPS)
        print(f"four chips: {compile_report(comp)}")
    else:
        with kernel_paths() as paths:
            for name, phase in (("train", train_phase),
                                ("serve", serve_phase)):
                with compile_seconds() as comp:
                    phase(**ONE_CHIP[name])
                print(f"{name}: {compile_report(comp)}; peak device bytes "
                      f"so far {peak_bytes()}")
        print("kernel paths: " + ", ".join(
            f"{k}={'/'.join(sorted(v))}" for k, v in sorted(paths.items())))
        want = {"ssd", "flash_attention", "decode_attention"}
        if set(paths) != want or any(v != {"pallas"} for v in paths.values()):
            raise AssertionError(f"kernel paths {paths}, want pallas for "
                                 f"{sorted(want)}")
        kernel_phase(**served_kernel_shapes())
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()

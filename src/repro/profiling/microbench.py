"""Time the real kernels and compiled step phases (DESIGN.md §15).

Every sample pairs a trimmed-mean wall time (jit + ``block_until_ready``,
warmup discarded) with the trip-count-corrected FLOPs/bytes that
:mod:`repro.analysis.hlo_cost` extracts from the SAME compiled module, so
the fit in :mod:`repro.analysis.calibrate` regresses measured seconds
against exactly the work XLA scheduled — not an analytic estimate.

Three case families:

* **kernel cases** — ``ops.mha`` / ``ops.decode_attention`` / ``ops.ssd``
  through the :mod:`repro.kernels.ops` dispatcher (Pallas on TPU, the
  blocked-jnp oracles elsewhere) over the attention/SSD shape classes the
  configs/ catalog exercises, swept over sequence length;
* **phase cases** — ``lm_loss`` forward, its grad step, last-only prefill
  and one-token decode on catalog configs, measured at TWO depths and
  depth-differenced so the per-layer cost is clean of embed/unembed;
* **sharded step** — the distributed photonic train step.

``run_suite`` returns a :class:`TimingArtifact` with provenance (host,
backend, jax version, kernel source hash) — commit it like a BENCH
baseline and CI replays the record instead of timing live.
"""
from __future__ import annotations

import hashlib
import os
import platform
import time
from dataclasses import dataclass
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.analysis.calibrate import TimingArtifact, TimingRecord
from repro.analysis.hlo_cost import corrected_cost
from repro.configs.base import ASSIGNED_ARCHS, get_config
from repro.kernels import ops
from repro.models import transformer as tf

KEY = jax.random.PRNGKey(0)

#: catalog names the kernel shape classes are derived from
CATALOG = ASSIGNED_ARCHS + ("llama3_8b", "llama_80b")

#: configs the step phases are measured on (dense / MoE / SSM coverage)
DEFAULT_PHASE_CONFIGS = ("llama3_8b", "deepseek_moe_16b", "mamba2_370m")

_HASHED_SOURCES = (
    "kernels/flash_attention.py", "kernels/ssd_scan.py",
    "kernels/decode_attention.py", "kernels/ref.py", "kernels/ops.py",
    "models/attention.py", "models/ssm.py", "models/transformer.py",
    "train/step.py", "serve/step.py",
)


def kernel_hash() -> str:
    """sha256 (truncated) over the kernel/model sources a timing depends
    on — artifact provenance, so a stale table is detectable."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for rel in _HASHED_SOURCES:
        with open(os.path.join(root, rel), "rb") as f:
            h.update(f.read())
    return h.hexdigest()[:16]


# ---------------------------------------------------------------------------
# measurement core
# ---------------------------------------------------------------------------


def _time(jfn, args, *, repeats: int, warmup: int,
          trim: int) -> Tuple[float, float]:
    """(trimmed-mean, min) wall seconds per call."""
    for _ in range(warmup):
        jax.block_until_ready(jfn(*args))
    ts = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args))
        ts.append(time.perf_counter() - t0)
    ts.sort()
    core = ts[trim:len(ts) - trim] or ts
    return sum(core) / len(core), ts[0]


def _cost(jfn, args):
    """Trip-count-corrected cost of the compiled module (no execution)."""
    text = jfn.lower(*args).compile().as_text()
    return corrected_cost(text, {"data": 1, "model": 1})


@dataclass
class BenchCase:
    """One timeable (kernel, shape) cell; ``make`` builds (fn, args)."""

    key: str
    shape_class: str
    shape: Dict[str, object]
    make: Callable[[], Tuple[Callable, tuple]]


def measure_case(case: BenchCase, *, repeats: int = 5, warmup: int = 2,
                 trim: int = 1) -> TimingRecord:
    """Measure one case; failures degrade to a skipped record."""
    try:
        fn, args = case.make()
        jfn = jax.jit(fn)
        cc = _cost(jfn, args)
        t_mean, t_min = _time(jfn, args, repeats=repeats, warmup=warmup,
                              trim=trim)
    except Exception as e:  # pragma: no cover - host-dependent skips
        return TimingRecord(case.key, case.shape_class, case.shape,
                            0.0, 0.0, 0.0, 0.0, 0, skipped=True,
                            skip_reason=f"{type(e).__name__}: {e}")
    return TimingRecord(case.key, case.shape_class, case.shape,
                        float(cc.flops), float(cc.bytes_accessed),
                        t_mean, t_min, repeats)


# ---------------------------------------------------------------------------
# kernel cases from the configs/ catalog
# ---------------------------------------------------------------------------


def _attn_classes(smoke: bool) -> List[Tuple[int, int, int]]:
    seen = []
    for name in CATALOG:
        cfg = get_config(name, smoke=smoke)
        if not cfg.n_heads:
            continue
        cls = (cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim)
        if cls not in seen:
            seen.append(cls)
    return sorted(seen)


def _ssd_classes(smoke: bool) -> List[Tuple[int, int, int, int, int]]:
    seen = []
    for name in CATALOG:
        cfg = get_config(name, smoke=smoke)
        if cfg.ssm is None:
            continue
        d_inner = cfg.ssm.expand * cfg.d_model
        h = d_inner // cfg.ssm.head_dim
        cls = (h, cfg.ssm.head_dim, cfg.ssm.state_dim, cfg.ssm.n_groups,
               cfg.ssm.chunk_size)
        if cls not in seen:
            seen.append(cls)
    return sorted(seen)


def kernel_cases(smoke: bool = True) -> List[BenchCase]:
    """Kernel cells over the catalog's attention/SSD shape classes.

    ``smoke=True`` (the CPU-container default) uses the catalog's smoke
    shapes so a full suite records in ~a minute; ``smoke=False`` uses the
    full-config classes for real-hardware recalibration."""
    cases: List[BenchCase] = []
    seqs = (128, 256, 512) if smoke else (512, 1024, 2048)
    b = 4 if smoke else 1

    for (h, kv, dh) in _attn_classes(smoke):
        cls = f"h{h}kv{kv}d{dh}"
        for s in seqs:
            def mk(s=s, h=h, kv=kv, dh=dh):
                ks = jax.random.split(KEY, 3)
                q = jax.random.normal(ks[0], (b, s, h, dh),
                                      jnp.float32) * 0.5
                k = jax.random.normal(ks[1], (b, s, kv, dh),
                                      jnp.float32) * 0.5
                v = jax.random.normal(ks[2], (b, s, kv, dh),
                                      jnp.float32) * 0.5

                def fn(q, k, v):
                    return ops.mha(q, k, v, causal=True)
                return fn, (q, k, v)
            cases.append(BenchCase("flash_attention", cls,
                                   {"b": b, "s": s, "h": h, "kv": kv,
                                    "dh": dh}, mk))
        for c in seqs:
            def mk(c=c, h=h, kv=kv, dh=dh):
                ks = jax.random.split(KEY, 3)
                q = jax.random.normal(ks[0], (2 * b, 1, h, dh),
                                      jnp.float32) * 0.5
                kc = jax.random.normal(ks[1], (2 * b, kv, dh, c),
                                       jnp.float32) * 0.5
                vc = jax.random.normal(ks[2], (2 * b, kv, dh, c),
                                       jnp.float32) * 0.5
                valid = jnp.ones((2 * b, c), jnp.bool_)

                def fn(q, kc, vc, valid):
                    return ops.decode_attention(q, kc, vc, valid)
                return fn, (q, kc, vc, valid)
            cases.append(BenchCase("decode_attention", cls,
                                   {"b": 2 * b, "c": c, "h": h, "kv": kv,
                                    "dh": dh}, mk))

    for (h, p, n, g, chunk) in _ssd_classes(smoke):
        cls = f"h{h}p{p}n{n}g{g}c{chunk}"
        for s in seqs:
            if s % chunk:
                continue
            def mk(s=s, h=h, p=p, n=n, g=g, chunk=chunk):
                ks = jax.random.split(KEY, 5)
                x = jax.random.normal(ks[0], (b, s, h, p), jnp.float32)
                dt = jax.nn.softplus(
                    jax.random.normal(ks[1], (b, s, h), jnp.float32))
                a = -jnp.exp(
                    jax.random.normal(ks[2], (h,), jnp.float32) * 0.5)
                bm = jax.random.normal(ks[3], (b, s, g, n), jnp.float32)
                cm = jax.random.normal(ks[4], (b, s, g, n), jnp.float32)

                def fn(x, dt, a, bm, cm):
                    return ops.ssd(x, dt, a, bm, cm, chunk)
                return fn, (x, dt, a, bm, cm)
            cases.append(BenchCase("ssd_scan", cls,
                                   {"b": b, "s": s, "h": h, "p": p,
                                    "n": n, "g": g, "chunk": chunk}, mk))
    return cases


# ---------------------------------------------------------------------------
# step phases: depth-differenced per-layer measurements
# ---------------------------------------------------------------------------


def _measure_at_depth(cfg, depth: int, batch, which: str, *, repeats,
                      warmup, trim):
    """(t_mean, CorrectedCost) of one phase at ``n_layers=depth``."""
    dcfg = cfg.replace(n_layers=depth)
    params = tf.init_lm(jax.random.PRNGKey(0), dcfg)

    if which == "fwd":
        def fn(p_, b_):
            return tf.lm_loss(p_, b_, dcfg)[0]
        args = (params, batch)
    elif which == "step":
        def fn(p_, b_):
            return jax.grad(lambda pp: tf.lm_loss(pp, b_, dcfg)[0])(p_)
        args = (params, batch)
    elif which == "prefill":
        def fn(p_, b_):
            return tf.lm_forward(p_, b_, dcfg, last_only=True)[0]
        args = (params, {"tokens": batch["tokens"]})
    else:  # decode
        bsz = int(batch["tokens"].shape[0])
        state = tf.init_decode_state(dcfg, bsz, 256)
        token = jnp.zeros((bsz, 1), jnp.int32)
        pos = jnp.asarray(64, jnp.int32)

        def fn(p_, st_, tok_, pos_):
            return tf.decode_step(p_, st_, tok_, pos_, dcfg)
        args = (params, state, token, pos)

    jfn = jax.jit(fn)
    cc = _cost(jfn, args)
    t_mean, _ = _time(jfn, args, repeats=repeats, warmup=warmup, trim=trim)
    return t_mean, cc


_PHASE_OF = {"fwd": "train_fwd", "prefill": "prefill", "decode": "decode"}


def phase_records(configs: Sequence[str] = DEFAULT_PHASE_CONFIGS, *,
                  smoke: bool = True, repeats: int = 5, warmup: int = 2,
                  trim: int = 1) -> List[TimingRecord]:
    """Per-layer phase samples for each config, by depth-differencing.

    Each phase is measured at 2 and 4 periods deep; the per-layer slope
    ``(t_deep - t_shallow) / Δlayers`` cancels the embed/unembed/loss
    work that doesn't scale with depth — the same cancellation applied
    to the hlo_cost FLOPs/bytes, so time and work stay paired.
    ``train_bwd`` is derived as (grad step − forward) per layer.
    """
    out: List[TimingRecord] = []
    for name in configs:
        cfg = get_config(name, smoke=smoke)
        if cfg.family in ("vlm", "audio"):
            continue          # extra modality inputs; not phase-calibrated
        period = len(tf.period_spec(cfg))
        d1, d2 = 2 * period, 4 * period
        bsz, seq = (2, 256) if smoke else (1, 1024)
        ks = jax.random.split(KEY, 2)
        batch = {
            "tokens": jax.random.randint(ks[0], (bsz, seq), 0,
                                         cfg.vocab_size, jnp.int32),
            "targets": jax.random.randint(ks[1], (bsz, seq), 0,
                                          cfg.vocab_size, jnp.int32),
        }
        shape = {"config": name, "batch": bsz, "seq": seq,
                 "depths": [d1, d2]}
        per_layer: Dict[str, Tuple[float, float, float]] = {}
        for which in ("fwd", "step", "prefill", "decode"):
            key = _PHASE_OF.get(which, which)
            try:
                t1, c1 = _measure_at_depth(cfg, d1, batch, which,
                                           repeats=repeats, warmup=warmup,
                                           trim=trim)
                t2, c2 = _measure_at_depth(cfg, d2, batch, which,
                                           repeats=repeats, warmup=warmup,
                                           trim=trim)
            except Exception as e:  # pragma: no cover - host-dependent
                out.append(TimingRecord(key, name, shape, 0.0, 0.0, 0.0,
                                        0.0, 0, skipped=True,
                                        skip_reason=f"{type(e).__name__}: "
                                                    f"{e}"))
                continue
            dl = d2 - d1
            t_l = (t2 - t1) / dl
            f_l = (c2.flops - c1.flops) / dl
            b_l = (c2.bytes_accessed - c1.bytes_accessed) / dl
            per_layer[which] = (t_l, f_l, b_l)
            if which == "step":
                continue      # only its difference vs fwd is recorded
            if t_l <= 0.0 or f_l <= 0.0:
                out.append(TimingRecord(key, name, shape, 0.0, 0.0, 0.0,
                                        0.0, repeats, skipped=True,
                                        skip_reason="non-positive depth "
                                                    "difference"))
                continue
            out.append(TimingRecord(key, name, shape, f_l, max(b_l, 0.0),
                                    t_l, t_l, repeats))
        if "fwd" in per_layer and "step" in per_layer:
            tf_l, ff_l, bf_l = per_layer["fwd"]
            ts_l, fs_l, bs_l = per_layer["step"]
            tb, fb, bb = ts_l - tf_l, fs_l - ff_l, bs_l - bf_l
            if tb > 0.0 and fb > 0.0:
                out.append(TimingRecord("train_bwd", name, shape, fb,
                                        max(bb, 0.0), tb, tb, repeats))
            else:
                out.append(TimingRecord("train_bwd", name, shape, 0.0,
                                        0.0, 0.0, 0.0, repeats,
                                        skipped=True,
                                        skip_reason="non-positive "
                                                    "step-minus-fwd"))
    return out


def sharded_step_records(*, repeats: int = 3, warmup: int = 1,
                         trim: int = 0) -> List[TimingRecord]:
    """The distributed photonic train step on a (n/2, 2) mesh."""
    from repro.train.step import (TrainSetup, init_sharded_state,
                                  make_train_step)
    n = jax.device_count()
    mesh = jax.make_mesh((n // 2, 2), ("data", "model"))
    cfg = get_config("llama3_8b", smoke=True)
    setup = TrainSetup(cfg)
    with jax.set_mesh(mesh):
        params, opt, ef = init_sharded_state(
            setup, mesh, jax.random.PRNGKey(0))
        tpl = jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), params)
        step = jax.jit(make_train_step(setup, mesh, tpl))
        ks = jax.random.split(KEY, 2)
        batch = {"tokens": jax.random.randint(ks[0], (8, 128), 0,
                                              cfg.vocab_size,
                                              jnp.int32),
                 "targets": jax.random.randint(ks[1], (8, 128), 0,
                                               cfg.vocab_size,
                                               jnp.int32)}
        text = step.lower(params, opt, ef, batch).compile().as_text()
        cc = corrected_cost(text, {"data": n // 2, "model": 2})
        t_mean, t_min = _time(step, (params, opt, ef, batch),
                              repeats=repeats, warmup=warmup,
                              trim=trim)
    return [TimingRecord(
        "train_step_sharded", "llama3_8b_smoke",
        {"mesh": [n // 2, 2], "batch": 8, "seq": 128},
        float(cc.flops), float(cc.bytes_accessed), t_mean, t_min, repeats)]


# ---------------------------------------------------------------------------
# suite
# ---------------------------------------------------------------------------


def run_suite(*, smoke: bool = True, repeats: int = 5, warmup: int = 2,
              trim: int = 1, target_gpu: str = "h200",
              phase_configs: Sequence[str] = DEFAULT_PHASE_CONFIGS,
              include_sharded: bool = True,
              progress: Callable[[str], None] = lambda s: None
              ) -> TimingArtifact:
    """Measure everything and return the provenance-stamped artifact."""
    records: List[TimingRecord] = []
    for case in kernel_cases(smoke):
        progress(f"{case.key} {case.shape_class} {case.shape}")
        records.append(measure_case(case, repeats=repeats, warmup=warmup,
                                    trim=trim))
    progress("phases: " + ", ".join(phase_configs))
    records += phase_records(phase_configs, smoke=smoke, repeats=repeats,
                             warmup=warmup, trim=trim)
    if include_sharded and jax.device_count() >= 2:
        progress("sharded train step")
        records += sharded_step_records()
    provenance = {
        "host": platform.node(),
        "machine": platform.machine(),
        "backend": jax.default_backend(),
        "n_devices": jax.device_count(),
        "jax_version": jax.__version__,
        "kernels_mode": ops._mode(),
        "kernel_hash": kernel_hash(),
        "target_gpu": target_gpu,
        "smoke": smoke,
        "repeats": repeats,
    }
    return TimingArtifact(provenance=provenance, records=records)

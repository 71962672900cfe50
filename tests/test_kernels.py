"""Per-kernel allclose vs the pure-jnp oracles, swept over shapes/dtypes.

Pallas kernels run in interpret mode on the CPU (TPU is the target;
tests/test_tpu_compile.py compiles them for the chip)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import get_config
from repro.kernels import ref
from repro.kernels.decode_attention import decode_attention as pl_decode
from repro.kernels.flash_attention import flash_attention as pl_flash
from repro.kernels.ssd_scan import ssd as pl_ssd
from repro.models.attention import _repeat_kv, make_mask, sdpa
from repro.models.ssm import ssd_chunked, ssm_apply, ssm_dims, ssm_init

KEY = jax.random.PRNGKey(0)


def _qkv(b, sq, sk, h, kv, dh, dtype):
    ks = jax.random.split(KEY, 3)
    q = (jax.random.normal(ks[0], (b, sq, h, dh)) * 0.5).astype(dtype)
    k = (jax.random.normal(ks[1], (b, sk, kv, dh)) * 0.5).astype(dtype)
    v = (jax.random.normal(ks[2], (b, sk, kv, dh)) * 0.5).astype(dtype)
    return q, k, v


@pytest.mark.parametrize("b,s,h,kv,dh,causal,window,bq,bk", [
    (2, 64, 4, 4, 16, True, None, 16, 16),
    (2, 64, 8, 2, 16, True, None, 16, 32),
    (2, 96, 4, 2, 16, True, 24, 32, 16),
    (1, 60, 4, 1, 8, True, None, 16, 16),    # ragged => padding path
    (2, 64, 4, 4, 16, False, None, 16, 16),
])
def test_ref_mha_vs_sdpa(b, s, h, kv, dh, causal, window, bq, bk):
    q, k, v = _qkv(b, s, s, h, kv, dh, jnp.float32)
    got = ref.mha(q, k, v, causal=causal, window=window, block_q=bq,
                  block_k=bk)
    mask = make_mask(s, s, causal=causal, window=window)
    want = sdpa(q, _repeat_kv(k, h), _repeat_kv(v, h), mask=mask)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_ref_mha_grads_match_sdpa():
    b, s, h, kv, dh = 1, 64, 4, 2, 16
    q, k, v = _qkv(b, s, s, h, kv, dh, jnp.float32)

    def f_ref(q, k, v):
        return jnp.sum(jnp.sin(ref.mha(q, k, v, block_q=16, block_k=16)))

    def f_ora(q, k, v):
        m = make_mask(s, s, causal=True, window=None)
        return jnp.sum(jnp.sin(sdpa(q, _repeat_kv(k, h), _repeat_kv(v, h),
                                    mask=m)))

    g1 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ora, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g1, g2):
        np.testing.assert_allclose(a, b_, atol=1e-4)


@pytest.mark.parametrize("s", [128, 100])  # 100: padded to the blocks
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("h,kv,dh,window", [
    (4, 4, 64, None), (8, 2, 64, None), (4, 1, 32, 48),
])
def test_pallas_flash_vs_ref(dtype, h, kv, dh, window, s):
    b = 2
    q, k, v = _qkv(b, s, s, h, kv, dh, dtype)
    got = pl_flash(q, k, v, causal=True, window=window, block_q=32,
                   block_k=32, interpret=True)
    want = ref.mha(q, k, v, causal=True, window=window, block_q=32,
                   block_k=32)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol)


def test_pallas_flash_grad_path():
    b, s, h, kv, dh = 1, 64, 4, 2, 32
    q, k, v = _qkv(b, s, s, h, kv, dh, jnp.float32)
    g1 = jax.grad(lambda q: jnp.sum(jnp.sin(pl_flash(
        q, k, v, block_q=32, block_k=32, interpret=True))))(q)
    g2 = jax.grad(lambda q: jnp.sum(jnp.sin(ref.mha(
        q, k, v, block_q=32, block_k=32))))(q)
    np.testing.assert_allclose(g1, g2, atol=1e-4)


@pytest.mark.parametrize("c,block_k,valid_len", [
    (256, 64, 37), (256, 64, 100), (256, 64, 256),
    (256, 128, 200),                     # lane-aligned mask tiles
    (300, 128, 150), (300, 128, 300),    # ragged: padded with invalid slots
])
def test_decode_kernel_vs_ref(c, block_k, valid_len):
    b, h, kv, dh = 2, 8, 2, 64
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh)) * 0.5
    kc = jax.random.normal(ks[1], (b, kv, dh, c)) * 0.5   # [B,KV,dh,C]
    vc = jax.random.normal(ks[2], (b, kv, dh, c)) * 0.5
    valid = (jnp.arange(c) < valid_len)[None, :].repeat(b, 0)
    got = pl_decode(q, kc, vc, valid, block_k=block_k, interpret=True)
    want = ref.decode_attention(q, kc, vc, valid, block_k=64)
    np.testing.assert_allclose(got, want, atol=1e-5)


def _naive_ssd(x, dt, a, bm, cm):
    b, s, h, p = x.shape
    g, n = bm.shape[2], bm.shape[3]
    bh = jnp.repeat(bm, h // g, 2)
    ch = jnp.repeat(cm, h // g, 2)
    hs = jnp.zeros((b, h, p, n))
    ys = []
    for t in range(s):
        dA = jnp.exp(dt[:, t] * a[None, :])
        hs = dA[..., None, None] * hs + jnp.einsum(
            "bh,bhp,bhn->bhpn", dt[:, t], x[:, t], bh[:, t])
        ys.append(jnp.einsum("bhn,bhpn->bhp", ch[:, t], hs))
    return jnp.stack(ys, 1), hs


@pytest.mark.parametrize("chunk", [4, 8, 16])
@pytest.mark.parametrize("g", [1, 2])
def test_ssd_chunked_vs_naive(chunk, g):
    b, s, h, p, n = 2, 16, 4, 8, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    y_naive, h_naive = _naive_ssd(x, dt, a, bm, cm)
    y_c, h_c = ssd_chunked(x, dt, a, bm, cm, chunk)
    np.testing.assert_allclose(y_c, y_naive, atol=1e-4)
    np.testing.assert_allclose(h_c, h_naive, atol=1e-4)


@pytest.mark.parametrize("with_h0", [False, True])  # carried-in state
@pytest.mark.parametrize("chunk", [8, 16, 32])
def test_pallas_ssd_vs_chunked(chunk, with_h0):
    b, s, h, p, g, n = 2, 64, 4, 16, 2, 8
    ks = jax.random.split(KEY, 6)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))
    h0 = jax.random.normal(ks[5], (b, h, p, n)) if with_h0 else None
    y_p, st_p = pl_ssd(x, dt, a, bm, cm, chunk, h_init=h0, interpret=True)
    y_r, st_r = ssd_chunked(x, dt, a, bm, cm, chunk, h_init=h0)
    np.testing.assert_allclose(y_p, y_r, atol=5e-4)
    np.testing.assert_allclose(st_p, st_r, atol=5e-4)


def test_decode_stats_merge_equals_full():
    """Split-K merge (context-parallel decode) == single-pass decode."""
    b, c, h, kv, dh = 1, 64, 4, 2, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh))
    kc = jax.random.normal(ks[1], (b, kv, dh, c))
    vc = jax.random.normal(ks[2], (b, kv, dh, c))
    valid = jnp.ones((b, c), bool)
    full = ref.decode_attention(q, kc, vc, valid)
    # two shards of the cache (split on its last axis, the position),
    # merged via flash-decoding combine
    acc1, m1, l1 = ref.decode_attention(q, kc[..., :32], vc[..., :32],
                                        valid[:, :32], return_stats=True)
    acc2, m2, l2 = ref.decode_attention(q, kc[..., 32:], vc[..., 32:],
                                        valid[:, 32:], return_stats=True)
    mg = jnp.maximum(m1, m2)
    l = l1 * jnp.exp(m1 - mg) + l2 * jnp.exp(m2 - mg)
    acc = acc1 * jnp.exp(m1 - mg)[..., None] + \
        acc2 * jnp.exp(m2 - mg)[..., None]
    merged = (acc / l[..., None]).reshape(b, 1, h, dh)
    np.testing.assert_allclose(merged, full, atol=1e-5)


def test_pallas_ssd_grads_match_oracle():
    # pallas_call has no AD rule; ssd carries a custom_vjp that recomputes
    # through the jnp oracle.  Before it, SSM archs crashed in jax.grad
    # under REPRO_KERNELS=pallas (defect exposed by the §15 calibration
    # microbenchmarks).
    b, s, h, p, g, n = 1, 32, 4, 16, 2, 8
    chunk = 16
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (b, s, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, s, h)))
    a = -jnp.exp(jax.random.normal(ks[2], (h,)))
    bm = jax.random.normal(ks[3], (b, s, g, n))
    cm = jax.random.normal(ks[4], (b, s, g, n))

    def loss(fn):
        def f(x, dt, a, bm, cm):
            y, st = fn(x, dt, a, bm, cm)
            return jnp.sum(jnp.sin(y)) + jnp.sum(jnp.cos(st))
        return f

    g1 = jax.grad(loss(lambda *o: pl_ssd(*o, chunk, interpret=True)),
                  argnums=(0, 1, 2, 3, 4))(x, dt, a, bm, cm)
    g2 = jax.grad(loss(lambda *o: ssd_chunked(*o, chunk)),
                  argnums=(0, 1, 2, 3, 4))(x, dt, a, bm, cm)
    for got, want in zip(g1, g2):
        np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)


def test_pallas_decode_grads_match_ref():
    # same defect class as ssd: the decode kernel's custom_vjp recomputes
    # through ref.decode_attention; the bool valid_mask gets a float0
    # cotangent
    b, c, h, kv, dh = 1, 128, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (b, 1, h, dh)) * 0.5
    kc = jax.random.normal(ks[1], (b, kv, dh, c)) * 0.5
    vc = jax.random.normal(ks[2], (b, kv, dh, c)) * 0.5
    valid = (jnp.arange(c) < 100)[None, :].repeat(b, 0)

    def loss(fn):
        return lambda q, kc, vc: jnp.sum(jnp.sin(fn(q, kc, vc)))

    g1 = jax.grad(loss(lambda q_, k_, v_: pl_decode(
        q_, k_, v_, valid, block_k=64, interpret=True)),
        argnums=(0, 1, 2))(q, kc, vc)
    g2 = jax.grad(loss(lambda q_, k_, v_: ref.decode_attention(
        q_, k_, v_, valid)), argnums=(0, 1, 2))(q, kc, vc)
    for got, want in zip(g1, g2):
        np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("s,with_h0", [(24, False), (20, False), (24, True)])
def test_ssm_apply_kernel_path_matches_oracle(monkeypatch, s, with_h0):
    """``ssm_apply`` through ``ops.ssd`` on the Pallas kernel (interpret
    mode) equals the jnp oracle path; s=20 is padded to the chunk."""
    cfg = get_config("mamba2_370m", smoke=True).replace(dtype="float32")
    ks = jax.random.split(KEY, 3)
    p = ssm_init(ks[0], cfg, jnp.float32)
    x = jax.random.normal(ks[1], (2, s, cfg.d_model))
    _, h, pdim, n = ssm_dims(cfg)
    h0 = jax.random.normal(ks[2], (2, h, pdim, n)) if with_h0 else None
    monkeypatch.setenv("REPRO_KERNELS", "ref")
    want = ssm_apply(p, x, cfg, h_init=h0)
    monkeypatch.setenv("REPRO_KERNELS", "pallas_interpret")
    got = ssm_apply(p, x, cfg, h_init=h0)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-4)

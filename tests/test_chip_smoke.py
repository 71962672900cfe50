"""chip_smoke.py's phases at the SMOKE configs on the CPU, and its refusal
to run anywhere but on a TPU."""
import importlib.util
import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, use_compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_train_phase(smoke):
    with smoke.kernel_paths() as paths:
        res = smoke.train_phase("mamba2_370m", smoke=True, batch=2, seq=32,
                                steps=2)
    assert len(res["losses"]) == 2
    assert paths == {"ssd": {"ref"}}


def test_serve_phase(smoke):
    res = smoke.serve_phase("granite_moe_1b_a400m", smoke=True, batch=2,
                            prompt_len=12, gen=3)
    assert res["generated"].shape == (2, 3)


def test_kernel_phase(smoke):
    smoke.kernel_phase(flash=(1, 96, 4, 2, 64),
                       decode=(2, 300, 4, 2, 64, 200),
                       ssd=(1, 64, 4, 16, 1, 16, 16), interpret=True)


def test_four_chip_phase(smoke, capsys):
    smoke.four_chip_phase("granite_moe_1b_a400m", smoke=True, mesh="4x1",
                          batch=8, seq=32, steps=2)
    out = capsys.readouterr().out
    assert "state bytes per device (photonic): {0: " in out
    assert "photonic vs eps cross-entropy" in out


def test_served_kernel_shapes_follow_the_configs(smoke):
    assert smoke.served_kernel_shapes() == {
        "flash": (8, 3584, 16, 8, 64),
        "decode": (8, 4096, 16, 8, 64, 3584),
        "ssd": (4, 2048, 32, 64, 1, 128, 64)}


def test_refuses_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, str(ROOT / "chip_smoke.py")],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs a TPU, found cpu" in r.stderr
    for line in r.stdout.splitlines():
        with pytest.raises(ValueError):
            json.loads(line)


def test_compile_cache_location(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        use_compile_cache()                 # CPU: no cache
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        use_compile_cache()                 # JAX's own setting stands
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
        assert DEFAULT_DIR == ROOT / ".jax_cache"
    finally:
        jax.config.update("jax_compilation_cache_dir", before)

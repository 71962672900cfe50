"""What the benchmark takes from the program: the system under test, its
kernel dispatch log and JAX's compile events.  Nothing here measures.
"""
from __future__ import annotations

import dataclasses
import logging
import sys
from contextlib import contextmanager

from chipbench.spec import ROOT

SRC = ROOT / "src"


def import_program() -> None:
    """Put the program (``src/repro``) on the path."""
    if str(SRC) not in sys.path:
        sys.path.insert(0, str(SRC))
    import repro  # noqa: F401  (fails here, before any run, where absent)


def model_config(conf: dict):
    """The program's ModelConfig for a configuration file's ``model``."""
    import_program()
    from repro.configs.base import ModelConfig, MoEConfig, SSMConfig
    m = dict(conf["model"])
    if m.get("moe"):
        m["moe"] = MoEConfig(**m["moe"])
    if m.get("ssm"):
        m["ssm"] = SSMConfig(**m["ssm"])
    fields = {f.name for f in dataclasses.fields(ModelConfig)}
    unknown = set(m) - fields
    if unknown:
        raise KeyError(f"{conf['name']}: keys the program does not know: "
                       f"{sorted(unknown)}")
    return ModelConfig(name=conf["name"], **m)


@contextmanager
def kernel_paths():
    """{kernel: {path}} of every kernel dispatch traced in the block, from
    the program's ``repro.kernels.ops`` log."""
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


class CompileLog:
    """Seconds XLA spends compiling (persistent-cache fetches included)
    and persistent-cache hits, from JAX's monitoring events."""

    def __init__(self):
        import jax
        self.seconds, self.compiles, self.cache_hits = 0.0, 0, 0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(
            self._duration)
        self._jax.monitoring.unregister_event_listener(self._event)

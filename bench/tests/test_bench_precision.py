"""The stated matmul precision reaches the programs the runtime's engine
thread traces.

``jax.default_matmul_precision`` used as a context manager is local to the
thread that enters it, and the engine thread of ``CoServingRuntime``
traces its fused segment programs itself; the Pallas kernel and XLA's dots
both read the setting while tracing.  So the benchmark sets the precision
process-wide (``run.set_precision``) before any thread starts.  Each test
records the setting each fused segment program was traced under, and on
which thread.
"""
import threading
import time

import jax
import numpy as np
import pytest

from bench import program
from bench import run as bench_run
from bench.tests import tiny


@pytest.fixture
def restore_precision():
    was = jax.config.jax_default_matmul_precision
    yield
    jax.config.update("jax_default_matmul_precision", was)
    jax.clear_caches()


def traced_on_engine_thread(cfg, monkeypatch, around=None):
    """Serve one request through a threaded runtime; return the (thread
    name, precision setting) of each fused segment program traced."""
    from repro.models import transformer as tf
    from repro.serving.api import Frontend
    from repro.serving.runtime import CoServingRuntime

    seen = []
    inner = tf.run_tokens_paged_at

    def spy(*a, **kw):
        seen.append((threading.current_thread().name,
                     jax.config.jax_default_matmul_precision))
        return inner(*a, **kw)

    monkeypatch.setattr(tf, "run_tokens_paged_at", spy)
    jax.clear_caches()
    eng = program.build_engine(cfg, 1)
    rt = CoServingRuntime(eng)
    fe = Frontend(rt, clock=rt.now)
    if around is None:
        rt.start()
    else:
        with around:
            rt.start()
    try:
        h = fe.stream(np.arange(1, 20, dtype=np.int32), 3)
        deadline = time.time() + 120
        while not h.finished and time.time() < deadline:
            time.sleep(0.01)
        assert h.finished
    finally:
        rt.stop(drain=False)
    assert seen
    return seen


def test_stated_precision_reaches_engine_thread(monkeypatch, restore_precision):
    cfg = tiny.config()
    bench_run.set_precision(cfg)
    seen = traced_on_engine_thread(cfg, monkeypatch)
    assert {name for name, _p in seen} == {"coserve-engine"}
    assert {p for _n, p in seen} == {cfg["precision"]["matmul"]}


def test_process_wide_setting_reaches_engine_thread(monkeypatch,
                                                   restore_precision):
    """Any precision set the benchmark's way -- the reference's "highest"
    or one below the stated one -- is what the engine thread traces."""
    for setting in ("highest", "bfloat16"):
        cfg = tiny.config()
        cfg["precision"] = dict(cfg["precision"], matmul=setting)
        bench_run.set_precision(cfg)
        seen = traced_on_engine_thread(cfg, monkeypatch)
        assert {p for _n, p in seen} == {setting}


def test_thread_local_setting_does_not(monkeypatch, restore_precision):
    """The trap the process-wide setting closes: a context manager around
    ``start()`` leaves the engine thread at the global setting."""
    cfg = tiny.config()
    bench_run.set_precision(cfg)
    seen = traced_on_engine_thread(
        cfg, monkeypatch, around=jax.default_matmul_precision("highest")
    )
    assert {p for _n, p in seen} == {cfg["precision"]["matmul"]}

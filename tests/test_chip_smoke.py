"""CPU rehearsal of ``chip_smoke.py``: its phases at ``.reduced()`` size.

The script itself refuses to run without a TPU; here the device check is
left out by calling the phase functions directly, so a wrong path,
argument or check shows up before any chip time is spent.
"""
import os
import subprocess
import sys

import jax
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run_script(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "chip_smoke.py")],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120,
    )


def test_script_refuses_without_tpu(tmp_path):
    """Non-zero exit and no result line, from the checkout and from a
    directory holding the script alone."""
    lone = tmp_path / "lone"
    lone.mkdir()
    (lone / "chip_smoke.py").write_text(
        open(os.path.join(ROOT, "chip_smoke.py")).read()
    )
    for cwd in (ROOT, str(lone)):
        proc = _run_script(cwd)
        assert proc.returncode != 0, proc.stdout
        assert '"ok"' not in proc.stdout


def test_one_chip_phases_at_reduced_size():
    from repro.core.profiler import CalibrationGrid

    eng = chip_smoke.build(reduced=True, max_model_len=256, num_blocks=96,
                           chunk=32)
    assert eng.cfg.name == "qwen2-0.5b-smoke"
    # the CPU dispatches the jnp reference, so no TPU kernel is lowered
    assert chip_smoke.segment_has_kernel(eng) is False
    eng.calibrate(CalibrationGrid(
        chunk_sizes=(8, 16, 32), decode_buckets=(1, 2, 4),
        ctx_fractions=(0.25,), repeats=1, swap_block_counts=(1,),
    ))
    stats = chip_smoke.serve(
        eng, online=(24, 40), online_new=4, offline=(64, 64, 48),
        offline_new=6, arrival_gap_s=0.05, timeout_s=120.0,
    )
    assert stats["requests"] == 5
    assert stats["generated_tokens"] == 2 * 4 + 3 * 6
    errs = chip_smoke.logit_errors(eng, prompt_len=40, steps=3)
    # float32 on the CPU: the paged path and the reference differ only in
    # summation order
    assert errs["highest"] < 1e-4 and errs["served"] < 1e-4, errs
    assert not eng.blocks.has_seq(-1)  # the check's sequence was freed


def test_mesh_phase_at_reduced_size(monkeypatch, capsys):
    """The --mesh phase (tp=4 on four virtual devices, else tp=1): tokens
    match one device, the pool is sharded, and the first leg's arrays are
    freed before the one-chip leg builds its own."""
    tp = 1 if len(jax.devices()) < chip_smoke.MESH_TP else chip_smoke.MESH_TP
    live_at_leg = []
    leg = chip_smoke.mesh_tokens

    def spy(tp_, **kw):
        live_at_leg.append(sum(a.nbytes for a in jax.live_arrays()))
        return leg(tp_, **kw)

    monkeypatch.setattr(chip_smoke, "mesh_tokens", spy)
    chip_smoke.run_mesh(jax.devices(), tp, reduced=True, max_model_len=256,
                        num_blocks=96)
    out = capsys.readouterr().out
    assert f"tokens_tp{tp}_equal_tp1=True" in out, out
    assert "pool_shards_per_chip_ok=True" in out, out
    assert live_at_leg[1] <= live_at_leg[0] + (1 << 20), live_at_leg


def test_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="boom"):
        chip_smoke.check(False, "boom")

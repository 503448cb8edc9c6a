"""chip_smoke.py off the chip: it must refuse to run without a TPU, and
its phases must be right at a small size on the CPU (where the kernel
engagement checks cannot hold, so they are switched off)."""

import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def _run(script, cwd, extra_env=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(extra_env or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run([sys.executable, script], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=300)


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_refuses_without_chip(where, tmp_path):
    """No accelerator (or no repo next to the script): non-zero exit and
    no ok line."""
    if where == "repo":
        proc = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT)
    else:
        shutil.copy(os.path.join(ROOT, "chip_smoke.py"), tmp_path)
        proc = _run(str(tmp_path / "chip_smoke.py"), str(tmp_path))
    assert proc.returncode != 0
    assert '"ok"' not in proc.stdout
    if where == "repo":
        assert "not a TPU" in proc.stdout


def test_refuses_interpret_env():
    proc = _run(os.path.join(ROOT, "chip_smoke.py"), ROOT,
                {"SPFFT_TPU_FUSED_INTERPRET": "1"})
    assert proc.returncode != 0
    assert "SPFFT_TPU_FUSED_INTERPRET" in proc.stdout


def test_local_and_serving_phases_small(capsys):
    plan, triplets = chip_smoke.run_local(n=16, require_kernels=False)
    chip_smoke.run_serving(plan, triplets, n=16, requests=3,
                           require_kernels=False)
    out = capsys.readouterr().out
    for label in ("C2C backward vs dense ifftn", "R2C backward vs dense",
                  "C2C apply_pointwise", "serving: 3 requests matched"):
        assert label in out


def test_engagement_check_names_inactive_kernels():
    """On the CPU the kernels are not engaged, and the check says so."""
    import jax

    from spfft_tpu import TransformType, make_local_plan
    from spfft_tpu.utils import as_interleaved
    triplets, values = chip_smoke.sphere_values(8, seed=0)
    plan = make_local_plan(TransformType.C2C, 8, 8, 8, triplets,
                           precision="single")
    vil = jax.device_put(as_interleaved(values, "single"))
    space = plan.backward(vil)
    plan.forward(space)
    problems = chip_smoke.engagement_problems(plan, vil, space)
    assert "Pallas gather kernel inactive" in problems
    assert "pair was never executed" in problems


def test_distributed_phase_small(capsys):
    chip_smoke.run_distributed(n=16, shards=4, require_kernels=False)
    out = capsys.readouterr().out
    assert "DEFAULT: shards on 4 devices" in out
    assert "COMPACT_BUFFERED: shards on 4 devices" in out


def test_check_close_rejects_a_miss():
    import numpy as np
    want = np.ones(4)
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.check_close("x", want * (1 + 1e-4), want)

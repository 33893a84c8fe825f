"""``chip_smoke.py`` refuses to report without a TPU and without the repo,
and its phases meet their bounds at small n on the CPU.

The refusals run the script as a subprocess with ``JAX_PLATFORMS=cpu``,
which never loads the TPU library.
"""
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


def _run(script: Path, cwd: Path) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, str(script)], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=300,
    )


def test_chip_smoke_refuses_cpu():
    res = _run(SCRIPT, ROOT)
    assert res.returncode != 0
    assert "'cpu'" in res.stderr  # names the platform it found
    assert '"ok"' not in res.stdout


def test_chip_smoke_needs_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    res = _run(alone, tmp_path)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout


def _chip_smoke():
    sys.path.insert(0, str(ROOT))
    try:
        import chip_smoke
    finally:
        sys.path.remove(str(ROOT))
    return chip_smoke


@pytest.mark.parametrize("phase,n", [("shampoo_refresh", 128), ("dense_4096", 256)])
def test_phase_bounds_hold_at_small_n(capsys, phase, n):
    # The phases' data, host references and bounds, at a size the CPU runs
    # quickly.  Off the TPU the kernels are interpreted, so only the
    # accuracy lines are checked, not the kernel lists.
    import json

    import numpy as np

    cs = _chip_smoke()
    getattr(cs, phase)(cs.Phase(), np.random.default_rng(0), n=n)
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()]
    checks = [l for l in lines if "metric" in l]
    assert checks and all(c["ok"] for c in checks), checks


@pytest.mark.parametrize(
    "line,name",
    [
        ('  ROOT %syr2k_lower.1 = f32[512,512]{1,0:T(8,128)} custom-call(%a), '
         'custom_call_target="tpu_custom_call"', "syr2k_lower"),
        ('  %fused_panel_update = (f32[8,8]) custom-call(%b), '
         'custom_call_target="tpu_custom_call", backend_config="x"', "fused_panel_update"),
    ],
)
def test_kernels_in_reads_custom_call_names(line, name):
    other = '  %dot.3 = f32[8,8] dot(%a, %b)'
    assert _chip_smoke().kernels_in("\n".join([other, line])) == [name]


@pytest.mark.parametrize(
    "n,eigenvectors,kernels",
    [
        (4096, False, {"syr2k_lower", "fused_panel_update", "bulge_chase_strip"}),
        (4096, True, {"syr2k_lower", "fused_panel_update", "bulge_chase_strip",
                      "backtransform_wy"}),
        (1024, False, {"syr2k_lower", "fused_panel_update", "bulge_chase_wavefront"}),
        (1024, True, {"syr2k_lower", "fused_panel_update", "bulge_chase_wavefront",
                      "backtransform_wy"}),
    ],
)
def test_expected_kernels_read_the_plans_record(monkeypatch, n, eigenvectors, kernels):
    # On a TPU: the plan's tables and no interpreter.  At n = 4096 the
    # padded matrix is over the VMEM budget and its band strip is not, so
    # the chase holds the strip; the first stage takes syr2k_lower above
    # m = 1280.
    import jax.numpy as jnp

    from repro.solver import EvdConfig, plan

    monkeypatch.setattr("repro.backend.probe.platform", lambda: "tpu")
    cfg = EvdConfig(backend="pallas")
    assert _chip_smoke().expected_kernels(n, cfg, eigenvectors) == kernels
    pl = plan(n, jnp.float32, cfg)
    assert pl.kernels(eigenvectors) == kernels
    lines = pl.describe().splitlines()
    for stage in ("first_stage", "bulge_chase", "backtransform_q2"):
        line = next(l for l in lines if l.startswith(f"  {stage}: "))
        for p in pl.paths:
            if p.stage == stage:
                assert p.describe() in line
    # The XLA reference backend runs no kernel.
    assert not plan(n, jnp.float32, EvdConfig(backend="jnp")).kernels(eigenvectors)

"""``chip_smoke.py`` off the chip: it must refuse, and its phases must pass.

The script's checks are meant for a TPU; here the phases run at tiny sizes
on the CPU (kernels in interpret mode) so a later change that breaks the
main path fails in tier-1 before it costs chip time.  The four-chip path
runs in a subprocess over four virtual CPU devices.
"""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402

TINY = chip_smoke.Sizes(
    k_boot=64, k_kernel=256, n_features=64, ref_rows=16, joins=8, movers=4,
    query_batches=2, query_batch=8, fl_clients=8, fl_train=400,
)


def _run(args, cwd, env_extra=None):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env_extra or {})}
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, *args], cwd=cwd, env=env,
        capture_output=True, text=True, timeout=600,
    )


def _json_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            out.append(json.loads(line))
        except ValueError:
            pass
    return out


def test_refuses_without_tpu():
    proc = _run([str(ROOT / "chip_smoke.py")], cwd=ROOT)
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr
    assert not _json_lines(proc.stdout)


def test_fails_alone_outside_the_repo(tmp_path):
    shutil.copy(ROOT / "chip_smoke.py", tmp_path / "chip_smoke.py")
    proc = _run(["chip_smoke.py"], cwd=tmp_path)
    assert proc.returncode != 0
    assert not _json_lines(proc.stdout)


def test_default_phases_tiny_on_cpu(capsys):
    chip_smoke.run_default(0, TINY)
    lines = capsys.readouterr().out.splitlines()
    for tag in ("(a)", "(b) bootstrap eq3", "(b) bootstrap eq2",
                "(b) kernel alone eq3", "(b) kernel alone eq2",
                "(c) admission eq3", "(c) admission eq2",
                "(d) serving eq3", "(d) serving eq2", "(e)"):
        assert any(l.startswith(tag) for l in lines), (tag, lines)


_FOUR = """
import sys
sys.path.insert(0, {root!r})
import chip_smoke
import test_chip_smoke
chip_smoke.run_four_chips(0, test_chip_smoke.TINY)
"""


def test_four_chip_path_on_four_cpu_devices():
    proc = _run(
        ["-c", _FOUR.format(root=str(ROOT))], cwd=ROOT / "tests",
        env_extra={"XLA_FLAGS": "--xla_force_host_platform_device_count=4"},
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = [l for l in proc.stdout.splitlines() if l.startswith("four chips")]
    assert len(lines) == 2 and "split over 4 devices" in lines[0], proc.stdout


@pytest.mark.parametrize("measure", ["eq3", "eq2"])
def test_reference_rows_match_dense_backend(measure):
    import jax.numpy as jnp
    import numpy as np

    from repro.core.angles import proximity_matrix

    pop = chip_smoke.Population(1, 48)
    U = pop.bases(np.arange(20) % chip_smoke.N_STYLES)
    rows = np.array([0, 7, 19])
    ref = chip_smoke.reference_rows(U.astype(np.float64), rows, measure)
    A = np.asarray(proximity_matrix(jnp.asarray(U), measure, backend="jnp"))
    np.testing.assert_allclose(A[rows], ref, atol=1e-3)

"""chip_smoke.py off the chip: its phases and numpy checks at a tiny size
with the kernels in interpret mode, and its refusal to report a result
without a TPU."""

import os
import subprocess
import sys

import numpy as np
import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.core import DDFContext  # noqa: E402
from repro.kernels import registry  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402


@pytest.fixture
def phases():
    prev = registry.set_backend("pallas")
    try:
        yield chip_smoke.Phases(DDFContext(mesh=make_host_mesh(), axes=("data",)),
                                rows_per_worker=3000, seed=0)
    finally:
        registry.set_backend(prev)


def test_phases_agree_with_numpy(phases, capsys):
    joined_ddf, joined = phases.join()
    phases.sort(joined_ddf, joined)
    phases.lazy()
    phases.stream()
    phases.dtypes()
    out = capsys.readouterr().out
    for name in ("a_join", "b_sort", "c_lazy", "d_stream", "e_dtypes"):
        assert f"check {name}:" in out
    assert "phase d_stream: batches=5" in out


def test_checks_catch_a_wrong_answer(phases):
    exp = chip_smoke.pipeline_reference(phases.left, phases.right, phases.n_keys)
    got = {k: v.copy() for k, v in exp.items()}
    got["c1_max"][0] -= 1
    with pytest.raises(chip_smoke.SmokeFailure, match="c1_max"):
        chip_smoke.check_grouped(got, exp, ["c1_sum", "c1_max"], "c_lazy")


def test_int32_sums_wrap_like_the_device():
    x = np.array([2**31, 2**32 + 5, -(2**31) - 1], np.int64)
    np.testing.assert_array_equal(chip_smoke._wrap32(x, np.int32),
                                  np.array([-(2**31), 5, 2**31 - 1], np.int32))


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                         capture_output=True, text=True, timeout=300, env=env)
    assert res.returncode != 0
    assert '"ok"' not in res.stdout
    assert "needs a TPU" in res.stderr

"""chip_smoke.py on the CPU: it must refuse to run here, and its
statement list, oracle comparison and checks must hold at sf=0.01 when
its functions are called directly. Plus the import sweep that turns the
next dead import into one named failure instead of a thousand."""

import importlib
import importlib.machinery
import importlib.util
import os
import pathlib
import pkgutil
import subprocess
import sys

import pytest

REPO = pathlib.Path(__file__).resolve().parent.parent
SF = 0.01
ROWS = 20_000  # lineitem cap: the shapes tests/test_tpch.py compiles


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", REPO / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def data(smoke):
    return smoke.oracle_data(SF, rows=ROWS)


def test_refuses_to_run_without_accelerator():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run([sys.executable, str(REPO / "chip_smoke.py")],
                         cwd=str(REPO), env=env, capture_output=True,
                         text=True, timeout=300)
    assert out.returncode != 0
    assert "'cpu'" in out.stderr, out.stderr[-2000:]
    assert '"ok"' not in out.stdout


def test_phases_at_small_scale(smoke, data, capsys):
    # the four-chip form runs every one-chip phase too
    smoke.run_phases(SF, data, smoke.MESH_CHIPS, rows=ROWS)
    out = capsys.readouterr().out
    for name in smoke.ONE_CHIP:
        assert f"# {name}: rows=" in out
    for name in smoke.ON_MESH:
        assert f"# mesh[4] {name}: rows=" in out
    assert "lane_hits=" in out and "builds.large+=" in out
    assert "int MIN/MAX exact" in out
    assert "rows equal one chip's" in out


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_int_minmax_through_the_kernel_is_exact(smoke, seed):
    # the check chip_smoke.py makes through Mosaic, interpreted here:
    # why integer MIN/MAX is inside `auto`'s envelope
    assert smoke.int_minmax_is_exact(seed, n=512, groups=64,
                                     interpret=True)


def test_a_wrong_answer_fails(smoke):
    with pytest.raises(smoke.SmokeFailure, match="row 0 col 1"):
        smoke.compare_rows("t", [("1", "2.5001")], [(1, 2.5)],
                           rel=1e-9)
    smoke.compare_rows("t", [("1", "2.5")], [(1, 2.5)], rel=1e-9)


def test_every_module_imports():
    import cockroach_tpu
    # source modules only: the ctypes libraries native/ builds beside
    # its sources (_keyenc-*.so) look like extension modules to pkgutil
    names = [m.name for m in pkgutil.walk_packages(
        cockroach_tpu.__path__, "cockroach_tpu.")
        if not m.name.endswith("__main__")
        and isinstance(m.module_finder.find_spec(m.name).loader,
                       importlib.machinery.SourceFileLoader)]
    assert len(names) > 100
    for name in names:
        importlib.import_module(name)

"""chip_smoke.py refuses to run anywhere but on enough TPU chips."""

import importlib.util
import os
from types import SimpleNamespace

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location(
        "chip_smoke", os.path.join(ROOT, "chip_smoke.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tpus(n):
    return [SimpleNamespace(platform="tpu", device_kind="TPU v5 lite", id=i)
            for i in range(n)]


@pytest.mark.parametrize("have", [1, 2, 3])
def test_four_chips_with_fewer_devices_fails_loudly(smoke, have):
    with pytest.raises(SystemExit, match=f"needs 4 TPU devices.*has {have}"):
        smoke.check_devices(_tpus(have), 4)


def test_enough_tpu_devices_pass(smoke):
    assert len(smoke.check_devices(_tpus(4), 4)) == 4
    assert len(smoke.check_devices(_tpus(4), 1)) == 1


@pytest.mark.parametrize("argv", [[], ["--chips", "4"]])
def test_cpu_run_exits_without_result(smoke, argv, capsys):
    """On the CPU backend the smoke fails before any work and prints no
    result line."""
    with pytest.raises(SystemExit, match="no TPU found"):
        smoke.main(argv)
    assert '"ok"' not in capsys.readouterr().out

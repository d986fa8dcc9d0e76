"""chip_smoke.py's control flow on CPU: the one-chip phases (train, kernel
against reference, checkpoint round trip) at reduce_for_smoke size with
interpret-mode kernels, the four-device data-parallel phase on virtual CPU
devices, and the refusal to report without a TPU.

The platform check is bypassed here by monkeypatching the script's device
phase; the script itself has no option for it."""
import dataclasses
import importlib.util
import json
import os
import pathlib
import re
import subprocess
import sys

import pytest

from repro.training.optimizer import OptSettings

pytestmark = pytest.mark.slow  # XLA-compile-heavy; excluded from the smoke lane

ROOT = pathlib.Path(__file__).resolve().parents[1]
FAKE_DEVICE = {"platform": "cpu", "kind": "cpu-interpret", "count": 1}


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def smoke(monkeypatch):
    from repro import configs

    mod = _load()
    small = dataclasses.replace(
        configs.reduce_for_smoke(configs.get(mod.ARCH)), use_pallas=True
    )
    monkeypatch.setattr(mod, "model_config", lambda: small)
    monkeypatch.setattr(mod, "SEQ_LEN", 64)
    monkeypatch.setattr(mod, "BATCH", 4)
    monkeypatch.setattr(mod, "TRAIN_STEPS", 10)
    monkeypatch.setattr(mod, "phase_device", lambda chips: dict(FAKE_DEVICE))
    # Interpret-mode kernels lower to plain HLO: no Mosaic call to find.
    monkeypatch.setattr(mod, "check_kernel_compiled", lambda text: None)
    monkeypatch.setattr(mod, "enable_compile_cache", lambda: "off")
    # A toy model learns the stream's repeats in ten steps only at a larger
    # rate than the 3e-4 the full-width run uses (as test_training_loop).
    monkeypatch.setattr(
        OptSettings, "auto", classmethod(lambda cls, n_params: cls(lr=3e-3))
    )
    return mod


def test_one_chip_phases_pass_and_print_the_result_last(smoke, capsys):
    assert smoke.main([]) == 0
    out = capsys.readouterr().out.splitlines()
    assert json.loads(out[-1]) == {"ok": True, "device": FAKE_DEVICE}
    assert sum(re.match(r"step \d+ loss ", line) is not None for line in out) == 10
    assert any(line.startswith("step compiles: 1") for line in out)
    assert any(line.startswith("kernel vs reference: ") for line in out)
    assert any(line.startswith("checkpoint: step 10 restored bitwise") for line in out)


def test_a_failed_check_exits_before_the_result(smoke, capsys, monkeypatch):
    monkeypatch.setattr(smoke, "KERNEL_RTOL", -1.0)
    with pytest.raises(SystemExit) as exc:
        smoke.main([])
    assert "Pallas and XLA losses disagree" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


def test_without_a_tpu_the_script_fails_and_prints_no_result(capsys):
    with pytest.raises(SystemExit) as exc:
        _load().main([])
    assert "JAX finds no TPU" in str(exc.value.code)
    assert '"ok"' not in capsys.readouterr().out


_FOUR_DEVICES = """
import dataclasses, importlib.util, sys
spec = importlib.util.spec_from_file_location("chip_smoke", sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
from repro import configs
mod.model_config = lambda: configs.reduce_for_smoke(configs.get(mod.ARCH))
mod.SEQ_LEN, mod.BATCH, mod.PARALLEL_STEPS = 64, 8, 3
mod.phase_device = lambda chips: {"platform": "cpu", "kind": "cpu", "count": chips}
mod.enable_compile_cache = lambda: "off"
sys.exit(mod.main(["--chips", "4"]))
"""


def test_four_device_data_parallel_phase_on_virtual_cpus():
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=4",
        PYTHONPATH=str(ROOT / "src"),
    )
    run = subprocess.run(
        [sys.executable, "-c", _FOUR_DEVICES, str(ROOT / "chip_smoke.py")],
        env=env, capture_output=True, text=True, timeout=600,
    )
    assert run.returncode == 0, run.stderr[-4000:]
    out = run.stdout.splitlines()
    assert json.loads(out[-1])["device"]["count"] == 4
    assert sum(line.startswith("device ") for line in out) == 4
    assert sum(" sharded " in line for line in out) == 3

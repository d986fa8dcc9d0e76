"""Whole runs of the harness on the CPU at smoke size, in one file so that
one test worker runs them one after another: the contract's result line,
the refusal without a TPU, the control and each planted fault failing the
check."""
import json
import os
import subprocess
import sys

import jax
import pytest

from bench import faults, harness, reference, run
from bench.tests.smoke import smoke_cell, smoke_run
from bench.tests.test_files import CHECKS

KEYS = ["correct", "attempted", "failed", "metrics", "device", "checks"]
BM = harness.load_json(harness.ROOT / "BENCHMARK.json")
WORKLOADS = [c["name"] for c in BM["workloads"]]


def _first_cell_of_each_model_type():
    """``{model_type: cell}``, the first cell of ``BENCHMARK.json`` whose
    configuration names each model type.  Cells of one model type run the
    same code at smoke size, so one of them stands for all."""
    files = {c["name"]: c["file"] for c in BM["configs"]}
    cells = {}
    for cell in BM["workloads"]:
        conf = harness.load_json(harness.ROOT / files[cell["config"]])
        cells.setdefault(conf["model_type"], cell["name"])
    return cells


MODEL_CELLS = _first_cell_of_each_model_type()


@pytest.mark.parametrize("workload", WORKLOADS)
def test_smoke_run_prints_the_contract_line(workload, monkeypatch, capsys):
    # The look for a chip is the one thing the test takes away.
    monkeypatch.setattr(harness, "require_devices", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "load_cell", smoke_cell)
    run.main(["--workload", workload, "--seed", str(2 ** 31 + 11), "--seconds", "0.3"])
    out, err = capsys.readouterr()
    line = json.loads(out.strip().splitlines()[-1])
    assert list(line) == KEYS
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {m["name"] for m in harness.load_cell(workload).end_to_end} == set(line["metrics"])
    assert line["device"]["count"] == len(jax.devices())
    assert set(line["checks"]) == set(CHECKS)
    assert err.strip().splitlines()[-1].startswith("check data_mismatch 0 limit 0")


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, str(harness.BENCH / "run.py"), "--workload",
         "mamba2-130m.bucket-direct", "--seed", "1", "--seconds", "1"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert proc.returncode != 0
    assert "no TPU here" in proc.stderr
    assert not proc.stdout.strip()


@pytest.mark.parametrize("workload", list(MODEL_CELLS.values()), ids=list(MODEL_CELLS))
def test_control_fails_the_check(workload):
    """The control, the reference computed with float8 matmuls and put in
    the program's place, comes out as not correct against the cell's limits
    (one cell of each model type)."""
    sound_run = smoke_run(workload)
    ref = harness.reference_readings(sound_run, "fp32")
    sound = harness.checks(sound_run, ref)
    control = reference.compare(harness.reference_readings(sound_run, "fp8"), ref)
    limits = sound_run.cell.conf["limits"]
    assert harness.passed(sound), sound
    assert any(control[k] > limits[k] for k in control), (control, limits)


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("workload", list(MODEL_CELLS.values()), ids=list(MODEL_CELLS))
def test_each_fault_fails_the_check(workload, fault):
    """With the timed path broken underneath, the check comes out false
    (one cell of each model type)."""
    with faults.FAULTS[fault]():
        broken = smoke_run(workload)
    checked = harness.checks(broken)
    assert not harness.passed(checked), checked

"""A model of a new type joins the benchmark by added files alone.

In a copy of the benchmark's tree (``BENCHMARK.json`` and ``bench/``), a
throwaway model type is added: a copy of the mamba2 model file under another
name, a configuration file naming it, and a cell over an existing traffic
mix, with their entries appended to ``BENCHMARK.json``.  The harness,
pointed at the copy, runs the new cell at smoke size and prints a correct
result line, and no file that the copy shares with the benchmark was edited.
"""
import json
import pathlib
import shutil

import jax

from bench import harness, run
from bench.tests.smoke import smoke_cell

MODEL_TYPE = "throwaway_ssm"
CONFIG = "throwaway-130m"
CELL = "throwaway-130m.bucket-direct"


def _files(root: pathlib.Path):
    return {
        p.relative_to(root): p.read_bytes()
        for p in root.rglob("*")
        if p.is_file() and "__pycache__" not in p.parts
    }


def _add_model_type(root: pathlib.Path) -> None:
    """What a change that brings a model of a new type adds, and nothing else."""
    bench = root / "bench"
    shutil.copyfile(bench / "models" / "mamba2.py", bench / "models" / f"{MODEL_TYPE}.py")
    conf = harness.load_json(bench / "configs" / "mamba2-130m.json")
    conf.update(name=CONFIG, model_type=MODEL_TYPE)
    (bench / "configs" / f"{CONFIG}.json").write_text(json.dumps(conf, indent=2))
    bm = harness.load_json(root / "BENCHMARK.json")
    bm["configs"].append(
        dict(bm["configs"][0], name=CONFIG, file=f"bench/configs/{CONFIG}.json")
    )
    bm["workloads"].append(
        {"name": CELL, "config": CONFIG, "traffic": "bucket-direct", "chips": 1,
         "why": "a model type that no cell of the benchmark had, on the demand path"}
    )
    (root / "BENCHMARK.json").write_text(json.dumps(bm, indent=2))


def test_a_new_model_type_runs_from_added_files(tmp_path, monkeypatch, capsys):
    repo = harness.ROOT
    shutil.copyfile(repo / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(harness.BENCH, tmp_path / "bench", ignore=shutil.ignore_patterns("__pycache__"))
    _add_model_type(tmp_path)

    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    monkeypatch.setattr(harness, "require_devices", lambda chips: jax.devices())
    monkeypatch.setattr(harness, "load_cell", smoke_cell)
    run.main(["--workload", CELL, "--seed", str(2 ** 31 + 23), "--seconds", "0.3"])
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] > 0 and line["failed"] == 0
    cell = harness.load_cell(CELL)
    assert set(line["metrics"]) == {m["name"] for m in cell.end_to_end}
    assert pathlib.Path(cell.model.__file__) == tmp_path / "bench" / "models" / f"{MODEL_TYPE}.py"

    # Every file of the copy that the benchmark has is as the benchmark has it ...
    ours = _files(repo / "bench")
    for rel, data in _files(tmp_path / "bench").items():
        if rel in ours:
            assert data == ours[rel], rel
    # ... and BENCHMARK.json only gained entries at the ends of its lists.
    before = harness.load_json(repo / "BENCHMARK.json")
    after = harness.load_json(tmp_path / "BENCHMARK.json")
    assert set(after) == set(before)
    for key, value in before.items():
        if isinstance(value, list):
            assert after[key][: len(value)] == value, key
        else:
            assert after[key] == value, key

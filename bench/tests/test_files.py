"""Every file the benchmark names loads, and every name keeps the format."""
import json
import re

import pytest

from bench import harness
from bench.peaks import peak

BM = harness.load_json(harness.ROOT / "BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CHECKS = ("loss_gap", "grad_gap", "grad_diff", "update_gap", "data_mismatch")
WIDTHS = ("d_model", "hidden_size", "intermediate_size", "d_state", "headdim", "expand")


def test_top_level_keys():
    assert set(BM) == {
        "command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"
    }
    assert BM["paths"] == ["bench"] and BM["command"][1] == "bench/run.py"
    assert 1 <= BM["run_seconds"] <= 51
    assert len(json.dumps(BM)) < 64 * 1024


@pytest.mark.parametrize("cell", BM["workloads"], ids=lambda c: c["name"])
def test_cell_loads(cell):
    loaded = harness.load_cell(cell["name"])
    assert cell["chips"] in (1, 4)
    assert loaded.model.arch_config(loaded.conf).n_layers >= 1
    names = {m["name"] for m in loaded.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert loaded.per_layer
    assert 0 < len(cell["why"]) <= 200


@pytest.mark.parametrize(
    "path", sorted((harness.BENCH / "configs").glob("*.json")), ids=lambda p: p.stem
)
def test_config_file(path):
    """Every configuration file agrees with its entry in ``BENCHMARK.json``
    and keeps its widths."""
    conf = harness.load_json(path)
    assert conf["name"] == path.stem
    config = {c["name"]: c for c in BM["configs"]}[conf["name"]]
    assert conf["source"] == config["source"] and conf["reduced"] == config["reduced"]
    assert not set(conf["reduced"]) & set(WIDTHS)
    assert set(conf["limits"]) == set(CHECKS)
    assert all(v is not None for v in conf["limits"].values())


def test_names_and_units():
    named = BM["configs"] + BM["workloads"] + BM["end_to_end"] + BM["per_layer"]
    for entry in named:
        assert NAME.match(entry["name"]), entry["name"]
    for cell in BM["workloads"]:
        assert NAME.match(cell["config"]) and NAME.match(cell["traffic"])
    for metric in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(metric["unit"]) and metric["better"] in ("lower", "higher")
        assert (harness.BENCH / "metrics" / f"{metric['name']}.py").is_file()
    for metric in BM["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.25
        assert metric["source"] in ("host_clock", "device_trace")
    layers = {m["layer"] for m in BM["per_layer"]}
    assert all(0 < len(layer) <= 200 for layer in layers)
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert all(m["moves"] in e2e for m in BM["per_layer"])
    for entries in (BM["configs"], BM["workloads"], BM["end_to_end"] + BM["per_layer"]):
        assert len({e["name"] for e in entries}) == len(entries)


def test_traffic_files_load():
    for traffic in {c["traffic"] for c in BM["workloads"]}:
        t = harness.load_json(harness.BENCH / "traffic" / f"{traffic}.json")
        assert t["name"] == traffic and t["warmup_steps"] >= 4


def test_peaks_known_and_unknown():
    v5e = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9}
    assert peak("TPU v5 lite") == v5e
    with pytest.raises(KeyError):
        peak("cpu")

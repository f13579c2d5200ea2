"""Everything a cell needs is found by name, and a new configuration, mix,
cell or metric is new files plus entries, with no edit to a file there."""

import json
import re
import shutil

import pytest

from bench_testlib import ROOT

from bench.harness import Bench
from bench.run import LOOPS

SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("cell", CELLS)
def test_every_cell_resolves_to_its_files(cell):
    bench = Bench(ROOT)
    entry = bench.cell(cell)
    conf = bench.config(entry["config"])
    assert conf["name"] == entry["config"]
    traffic = bench.traffic(cell)
    assert traffic["loop"] in LOOPS and "trace_seconds" in traffic
    if traffic["loop"] == "train":
        ref = bench.config_module(entry["config"], "ref")
        flops = bench.config_module(entry["config"], "flops")
        assert callable(ref.init_params) and callable(ref.loss)
        assert flops.train_flops_per_token(conf) > 0
        assert {"zipf_s", "revoke_every_steps"} <= set(traffic)
    assert bench.limits(cell)
    for metric in bench.per_layer(cell):
        assert callable(bench.reader(metric["name"]))
    names = {m["name"] for m in bench.end_to_end(cell)}
    assert "setup_s" in names and len(names) >= 2
    assert bench.per_layer(cell)
    assert all(m["moves"] in names for m in bench.per_layer(cell))


def test_spec_keeps_its_shape():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs", "workloads",
                         "end_to_end", "per_layer"}
    entries = SPEC["configs"] + SPEC["workloads"] + SPEC["end_to_end"] + SPEC["per_layer"]
    assert all(NAME.match(e["name"]) for e in entries)
    assert len({e["name"] for e in entries}) == len(entries)
    for m in SPEC["end_to_end"] + SPEC["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in SPEC["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25 and m["source"] in ("host_clock", "device_trace")
    e2e = {m["name"] for m in SPEC["end_to_end"]}
    assert all(m["moves"] in e2e for m in SPEC["per_layer"])
    for c in SPEC["configs"]:
        assert (ROOT / c["file"]).is_file()
        assert c["reduced"] == json.loads((ROOT / c["file"]).read_text())["reduced"]


def test_new_config_mix_cell_and_metric_are_found_without_edits(tmp_path):
    """Copy the benchmark, add one of each as new files and entries, and
    check that every existing file is byte for byte what it was."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    conf = json.loads((b / "configs" / "mamba2-130m.json").read_text())
    conf.update(name="mamba2-tmp", n_layer=2)
    (b / "configs" / "mamba2-tmp.json").write_text(json.dumps(conf))
    for kind in ("ref", "flops"):
        shutil.copy(b / "configs" / f"mamba2-130m_{kind}.py", b / "configs" / f"mamba2-tmp_{kind}.py")
    (b / "traffic" / "bursty.json").write_text(json.dumps(
        {"loop": "train", "zipf_s": 1.2, "revoke_every_steps": 0, "trace_seconds": 2}))
    (b / "workloads" / "trial.tmp.bursty.json").write_text(json.dumps(
        {"config": "mamba2-tmp", "traffic": "bursty", "limits": {"loss_gap": 1.0}}))
    (b / "metrics" / "steps_seen.train.py").write_text(
        "def read(run):\n    return float(len(run['spans']))\n")
    spec = json.loads((tmp_path / "BENCHMARK.json").read_text())
    spec["configs"].append({"name": "mamba2-tmp", "source": "test", "reduced": ["n_layer"],
                            "file": "bench/configs/mamba2-tmp.json", "why": "test"})
    spec["workloads"].append({"name": "trial.tmp.bursty", "config": "mamba2-tmp",
                              "traffic": "bursty", "chips": 1, "why": "test"})
    spec["per_layer"].append({"name": "steps_seen.train", "unit": "1", "better": "higher",
                              "source": "host_clock", "layer": "train step",
                              "moves": "revocation_stall_s",
                              "workloads": ["trial.tmp.bursty"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    before.pop(tmp_path / "BENCHMARK.json")

    bench = Bench(tmp_path)
    assert bench.config("mamba2-tmp")["n_layer"] == 2
    assert bench.config_module("mamba2-tmp", "flops").train_flops_per_token(
        bench.config("mamba2-tmp")) > 0
    assert bench.traffic("trial.tmp.bursty")["zipf_s"] == 1.2
    assert [m["name"] for m in bench.per_layer("trial.tmp.bursty")][-1] == "steps_seen.train"
    assert bench.reader("steps_seen.train")({"spans": [1, 2, 3]}) == 3.0
    assert all(p.read_bytes() == data for p, data in before.items())


def test_cell_file_must_agree_with_the_spec(tmp_path):
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "testdata"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    path = tmp_path / "bench" / "workloads" / f"{CELLS[0]}.json"
    data = json.loads(path.read_text())
    path.write_text(json.dumps(dict(data, traffic="elsewhere")))
    with pytest.raises(ValueError, match="names"):
        Bench(tmp_path).traffic(CELLS[0])

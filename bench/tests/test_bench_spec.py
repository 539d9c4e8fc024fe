"""Every cell, configuration and metric is found by its file name, and a new
cell is taken from files alone."""

import json
import re
import shutil

import pytest

from bench import spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return spec.load()


def test_declaration_keys_and_names(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["paths"] == ["bench"]
    assert bench["command"] == ["python3", "bench/run.py"]
    assert 1 <= bench["run_seconds"] <= 51
    named = bench["configs"] + bench["workloads"] + bench["end_to_end"] \
        + bench["per_layer"]
    names = [e["name"] for e in named]
    assert all(NAME.match(n) for n in names), names
    assert len(set(names)) == len(names)
    for e in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for e in bench["end_to_end"]:
        assert 0.01 <= e["bound"] <= 0.25 and e["source"] in (
            "host_clock", "device_trace")
    assert "setup_s" in {e["name"] for e in bench["end_to_end"]}
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(bench["workloads"]) // 2)


def test_every_cell_resolves_by_file_name(bench):
    e2e = {e["name"] for e in bench["end_to_end"]}
    configs = {c["name"] for c in bench["configs"]}
    used = set()
    for w in bench["workloads"]:
        assert w["config"] in configs and w["chips"] in (1, 4)
        used.add(w["config"])
        cell = spec.cell(w["name"])
        assert cell.traffic["kind"] == "train"
        assert set(cell.limits) >= {"loss_gap", "grad_gap", "change_gap"}
        assert "setup_s" in {m["name"] for m in cell.end_to_end}
        assert len(cell.end_to_end) >= 2 and cell.per_layer
        for m in cell.per_layer:
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
    assert used == configs
    for m in bench["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in bench["workloads"]}
        assert callable(spec.metric_reader(m["name"]))


def test_config_files_hold_what_they_declare(bench):
    for c in bench["configs"]:
        assert c["file"].startswith("bench/configs/")
        conf = spec._json(spec.ROOT / c["file"])
        assert conf["source"] == c["source"]
        for key in c["reduced"]:
            assert key in conf["published"] and conf[key] != \
                conf["published"][key]
        for field, key in conf["program_from"].items():
            assert conf["program"][field] == conf[key], (c["name"], field)


def test_new_cell_is_taken_from_files_alone(tmp_path):
    shutil.copytree(spec.BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    bench = spec.load()
    bench["workloads"].append(
        {"name": "qwen3-0.6b.train_2k", "config": "qwen3-0.6b",
         "traffic": "train_2k", "chips": 1, "why": "a new cell"})
    bench["per_layer"].append(
        {"name": "steps_seen", "unit": "count", "better": "higher",
         "source": "host_clock", "layer": "train step",
         "moves": "train_tokens_per_s", "workloads": ["qwen3-0.6b.train_2k"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    traffic = spec._json(spec.BENCH / "traffic" / "train_4k.json")
    traffic.update(seq_len=2048, batch=4)
    (tmp_path / "bench/traffic/train_2k.json").write_text(json.dumps(traffic))
    shutil.copy(spec.BENCH / "limits" / "qwen3-0.6b.train_4k.json",
                tmp_path / "bench/limits/qwen3-0.6b.train_2k.json")
    (tmp_path / "bench/metrics/steps_seen.py").write_text(
        "def read(m):\n    return m.steps\n")

    cell = spec.cell("qwen3-0.6b.train_2k", root=tmp_path)
    assert cell.traffic["seq_len"] == 2048 and cell.chips == 1
    assert cell.config["registry"] == "qwen3-0.6b"
    assert [m["name"] for m in cell.per_layer][-1] == "steps_seen"
    read = spec.metric_reader("steps_seen", root=tmp_path)
    assert read(type("M", (), {"steps": 7})) == 7
    # the cells already declared are unchanged
    assert "steps_seen" not in {
        m["name"] for m in spec.cell("qwen3-0.6b.train_4k",
                                     root=tmp_path).per_layer}

"""``BENCHMARK.json`` keeps to the benchmark's contract, and the harness
finds a cell defined only by new files without an edit to existing
ones."""
import json
import re
import shutil

import pytest

from tiny_cells import ROOT

from bench import harness

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")


@pytest.fixture(scope="module")
def bench():
    return harness.load_json(ROOT / "BENCHMARK.json")


def _text(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s \
        and "\t" not in s


def test_top_level_keys_and_limits(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert bench["command"][:2] == ["python3", "bench/run.py"]
    assert len(bench["command"]) <= 32 and all(map(_text, bench["command"]))
    assert 1 <= len(bench["paths"]) <= 16
    for p in bench["paths"]:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    cells = len(bench["workloads"])
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert 1 <= cells <= 24
    assert len(json.dumps(bench)) <= 64 * 1024


def test_names_units_and_text_fields(bench):
    names = []
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.fullmatch(c["name"]) and _text(c["source"])
        assert _text(c["why"]) and len(c["reduced"]) <= 16
        assert all(NAME.fullmatch(k) for k in c["reduced"])
        assert (ROOT / c["file"]).is_file()
        assert c["file"].startswith(tuple(p + "/" for p in bench["paths"]))
        names.append(c["name"])
    assert len(set(c["file"] for c in bench["configs"])) == len(names)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.fullmatch(w["name"]) and NAME.fullmatch(w["traffic"])
        assert w["config"] in names and w["chips"] in (1, 4)
        assert _text(w["why"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(pairs) // 2)
    assert {w["config"] for w in bench["workloads"]} == set(names)
    metric_names = []
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.fullmatch(m["name"]) and UNIT.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        metric_names.append(m["name"])
    all_names = names + [w["name"] for w in bench["workloads"]] \
        + metric_names
    assert len(set(names)) == len(names)
    assert len(set(metric_names)) == len(metric_names)
    assert len(set(w["name"] for w in bench["workloads"])) == len(pairs)
    assert all(NAME.fullmatch(n) for n in all_names)


def test_metrics_cover_every_cell(bench):
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    layers = {}
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert _text(m["layer"]) and m["moves"] in e2e
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
        layers.setdefault(m["name"], m["layer"])
        if m["unit"] == "%" and "roofline" in m["name"]:
            assert m["name"].split(".")[0].endswith("_roofline")
    for w in bench["workloads"]:
        mine = [m for m in bench["end_to_end"]
                if w["name"] in m.get("workloads", [w["name"]])]
        assert "setup_s" in {m["name"] for m in mine} and len(mine) >= 2
        assert any(w["name"] in m.get("workloads", [w["name"]])
                   for m in bench["per_layer"])
        assert (ROOT / "bench" / "traffic" / f"{w['traffic']}.json"
                ).is_file()
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()


def test_a_cell_of_new_files_is_found(tmp_path, bench):
    """A later change adds a mix, its limits and a workload entry; the
    harness finds them by name, and no existing file changes."""
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    traffic = harness.load_json(ROOT / "bench" / "traffic"
                                / "fused-b4096-t16.json")
    traffic["env_batch"] = 8192
    (tmp_path / "bench" / "traffic" / "fused-b8192-t16.json").write_text(
        json.dumps(traffic))
    (tmp_path / "bench" / "limits" / "ppo-fused-b8192.json").write_text(
        (ROOT / "bench" / "limits" / "ppo-fused-b4096.json").read_text())
    extended = json.loads(json.dumps(bench))
    extended["workloads"].append(
        {"name": "ppo-fused-b8192", "config": "walle-mlp-ppo",
         "traffic": "fused-b8192-t16", "chips": 1, "why": "larger batch"})
    for m in extended["end_to_end"] + extended["per_layer"]:
        if "ppo-fused-b4096" in m.get("workloads", []):
            m["workloads"].append("ppo-fused-b8192")
    cell = harness.Cell(extended, "ppo-fused-b8192", tmp_path)
    assert cell.traffic["env_batch"] == 8192
    assert cell.config["algo"] == "ppo"
    assert {m["name"] for m in cell.end_to_end} == {"env_steps_per_s",
                                                    "setup_s"}
    assert "gae_roofline" in {m["name"] for m in cell.per_layer}
    for p, data in before.items():
        assert p.read_bytes() == data


def test_unknown_cell_is_refused(bench):
    with pytest.raises(harness.Refused):
        harness.Cell(bench, "no-such-cell", ROOT)


def test_no_tpu_prints_no_result(capsys):
    assert harness.main(["--workload", "ppo-fused-b4096", "--seed", "3",
                         "--seconds", "1"]) == 1
    assert capsys.readouterr().out == ""

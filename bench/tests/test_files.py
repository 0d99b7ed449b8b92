"""Every cell's files are found by name, and BENCHMARK.json keeps to the
shape the harness and the contract expect."""
import json
import re
from pathlib import Path

from common import BENCH, ROOT, load_spec

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_every_cell_finds_its_files():
    spec = load_spec()
    configs = {c["name"]: c for c in spec["configs"]}
    for cell in spec["workloads"]:
        cfg = configs[cell["config"]]
        assert (ROOT / cfg["file"]).is_file()
        assert cfg["file"].startswith("bench/")
        mix = json.loads((BENCH / "traffic" / f"{cell['traffic']}.json")
                         .read_text())
        assert (BENCH / f"{mix['driver']}.py").is_file()
        assert cell["chips"] == 1


def test_every_per_layer_metric_has_a_reader():
    spec = load_spec()
    for m in spec["per_layer"]:
        path = BENCH / "metrics" / f"{m['name']}.py"
        assert path.is_file(), path
        assert "def read(ctx)" in path.read_text()


def test_spec_shape():
    spec = load_spec()
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    cells = {c["name"] for c in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in spec[k]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in spec["end_to_end"] + spec["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in spec["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in spec["per_layer"]:
        assert m["moves"] in e2e and "bound" not in m
    for cell in cells:
        mine = [m for m in spec["end_to_end"]
                if cell in m.get("workloads", cells)]
        assert len(mine) >= 2
        assert any(cell in m.get("workloads", cells)
                   for m in spec["per_layer"])
    pairs = [(c["config"], c["traffic"]) for c in spec["workloads"]]
    assert len(pairs) == len(set(pairs))
    assert len(json.dumps(spec)) < 64 * 1024


def test_no_file_outside_bench_is_named():
    spec = load_spec()
    for word in spec["command"][1:]:
        assert word.startswith("bench/") and ".." not in word
    assert all(Path(p).parts[0] == "bench" for p in spec["paths"])

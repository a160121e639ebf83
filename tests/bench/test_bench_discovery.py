"""A new traffic mix is a data file plus a ``workloads`` entry: the
harness finds it by name, with no edit to any file that is there."""

import json
import pathlib
import shutil

import harness

ROOT = pathlib.Path(__file__).resolve().parents[2]


def _copy(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    return tmp_path


def _digest(root):
    return {p.relative_to(root): p.read_bytes()
            for p in sorted(root.rglob("*")) if p.is_file()}


def test_new_traffic_file_is_found_by_name(tmp_path):
    root = _copy(tmp_path)
    before = _digest(root)
    spec = json.loads((root / "BENCHMARK.json").read_text())
    bursty = dict(json.loads((root / "bench/traffic/chat.json").read_text()),
                  rate_rps=2.5)
    (root / "bench/traffic/chat-slow.json").write_text(json.dumps(bursty))
    spec["workloads"].append({"name": "phi3-mini.chat-slow",
                              "config": "phi3-mini-3.8b",
                              "traffic": "chat-slow", "chips": 1,
                              "why": "a test cell"})
    (root / "BENCHMARK.json").write_text(json.dumps(spec))
    c = harness.cell("phi3-mini.chat-slow", root)
    assert c["traffic"]["rate_rps"] == 2.5
    assert c["config"]["name"] == "phi3-mini-3.8b"
    # a metric without a workloads list applies to the new cell too; one
    # with a list applies to the cells it names
    assert "setup_s" in {m["name"] for m in c["end_to_end"]}
    assert all("workloads" not in m or "phi3-mini.chat-slow" in m["workloads"]
               for m in c["end_to_end"] + c["per_layer"])
    after = _digest(root)
    changed = {p for p in before if before[p] != after.get(p)}
    assert changed == {pathlib.Path("BENCHMARK.json")}


def test_every_metric_has_a_reader_and_every_cell_its_files():
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    for m in spec["per_layer"]:
        assert callable(harness.metric_reader(m["name"], ROOT))
    for w in spec["workloads"]:
        c = harness.cell(w["name"], ROOT)
        assert c["config"]["name"] == w["config"]
        for m in c["per_layer"] + c["end_to_end"]:
            assert "workloads" not in m or w["name"] in m["workloads"]

"""BENCHMARK.json and the files each cell is found by."""
import json
import os
import re
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import harness, roofline, traffic  # noqa: E402

with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
    BENCH = json.load(f)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert BENCH["paths"] == ["bench"]
    assert 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its time
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_names_units_and_entries():
    seen = set()
    for group, keys in (("configs", {"name", "source", "file", "reduced",
                                     "why"}),
                        ("workloads", {"name", "config", "traffic", "chips",
                                       "why"})):
        for e in BENCH[group]:
            assert set(e) == keys, e["name"]
            assert NAME.match(e["name"]) and e["name"] not in seen
            seen.add(e["name"])
            assert 1 <= len(e["why"]) <= 200 and "\n" not in e["why"]
    for e in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(e["name"]) and e["name"] not in seen
        seen.add(e["name"])
        assert UNIT.match(e["unit"]) and e["better"] in ("lower", "higher")
    for e in BENCH["end_to_end"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert e["source"] in ("host_clock", "device_trace")
        assert 0.01 <= e["bound"] <= 0.25
    for e in BENCH["per_layer"]:
        assert set(e) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert e["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert e["moves"] in {m["name"] for m in BENCH["end_to_end"]}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.25
               for m in BENCH["end_to_end"])


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name_and_load(name):
    cell = harness.load_cell(name)
    entry = next(w for w in BENCH["workloads"] if w["name"] == name)
    assert cell.chips == entry["chips"] == 1
    names = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in names and len(names) >= 2
    assert cell.per_layer
    for m in cell.per_layer:
        assert m["moves"] in names
        reader = harness.load_reader(m["name"])
        assert callable(reader.read)
    conf = next(c for c in BENCH["configs"] if c["name"] == entry["config"])
    assert conf["file"].startswith("bench/configs/")
    assert cell.config["reduced"] == conf["reduced"]
    assert cell.config["name"] == conf["name"]
    for kind in cell.workload["mix"]:
        assert kind in traffic.OPS


def test_every_reader_and_config_is_used():
    readers = {f[:-3] for f in os.listdir(os.path.join(ROOT, "bench",
                                                       "metrics"))
               if f.endswith(".py")}
    assert readers == {m["name"] for m in BENCH["per_layer"]}
    confs = {f for f in os.listdir(os.path.join(ROOT, "bench", "configs"))}
    assert confs == {os.path.basename(c["file"]) for c in BENCH["configs"]}
    used = {w["config"] for w in BENCH["workloads"]}
    assert used == {c["name"] for c in BENCH["configs"]}


def test_peaks_table_is_keyed_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9 and p["source"]
    with pytest.raises(KeyError):
        roofline.peaks("TPU v9 imaginary")


def test_a_new_cell_needs_only_new_files(tmp_path):
    """A cell added as a traffic file and a BENCHMARK.json entry loads and
    generates its traffic with no other file changed."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({
        "name": "kv1k-uniform.put", "config": "kv1k-uniform",
        "traffic": "put", "chips": 1,
        "why": "closed loop of 4096 puts of new keys, uniform"})
    for m in bench["end_to_end"] + bench["per_layer"]:
        if m["name"] in ("put_p95_ms", "serving.keys_per_stage"):
            m["workloads"].append("kv1k-uniform.put")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "bench" / "workloads" / "kv1k-uniform.put.json").write_text(
        json.dumps({"config": "kv1k-uniform", "loop": "closed",
                    "outstanding": 64, "mix": {"put": 1.0},
                    "keys": {"distribution": "uniform"},
                    "absent_share": 0.0, "put_keys": "new",
                    "sequence_blocks": 4,
                    "warmup": {"requests": 64}}))
    cell = harness.load_cell("kv1k-uniform.put", root=str(tmp_path))
    assert {m["name"] for m in cell.end_to_end} == {"setup_s", "ops_per_s",
                                                    "put_p95_ms"}
    assert [m["name"] for m in cell.per_layer] == ["serving.keys_per_stage"]
    cell.config["records"] = 256
    data = traffic.make_dataset(cell.config, 1)
    seq = traffic.make_sequence(cell.config, cell.workload, data, 1)
    assert (seq.op == traffic.OPS.index("put")).all()
    assert not set(seq.key) & set(data.keys)
    assert len(seq) == 256

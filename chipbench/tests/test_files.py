"""BENCHMARK.json and the files it names: each cell's files load, names
and units keep to their characters, every metric's cells report what it
moves, and a new cell is found by adding files and entries only."""
import json
import re
import shutil

import pytest

from chipbench import harness as H
from chipbench.traffic import load_traffic

BENCH = H.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
CONFIG_KEYS = {"name", "source", "file", "reduced", "why"}
CELL_KEYS = {"name", "config", "traffic", "chips", "why"}
E2E_KEYS = {"name", "unit", "better", "bound", "source"}
LAYER_KEYS = {"name", "unit", "better", "source", "layer", "moves"}
CELLS = [w["name"] for w in BENCH["workloads"]]


def test_top_level_keys_and_command():
    assert set(BENCH) == TOP_KEYS
    assert 1 <= BENCH["run_seconds"] <= 51
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    assert (H.ROOT / BENCH["command"][1]).is_file()
    assert len(json.dumps(BENCH)) < 64 * 1024


def test_entries_have_just_their_keys():
    for c in BENCH["configs"]:
        assert set(c) == CONFIG_KEYS, c["name"]
    for w in BENCH["workloads"]:
        assert set(w) == CELL_KEYS and w["chips"] in (1, 4), w["name"]
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == E2E_KEYS, m["name"]
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == LAYER_KEYS, m["name"]
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


def test_names_units_and_lines():
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in BENCH[group]:
            names.append((group, e["name"]))
            assert NAME.match(e["name"]), e["name"]
            for key in ("why", "layer", "source"):
                if key in e:
                    assert 1 <= len(e[key]) <= 200 and "\n" not in e[key] \
                        and "\t" not in e[key], (e["name"], key)
            if "unit" in e:
                assert UNIT.match(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
    for w in BENCH["workloads"]:
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    for c in BENCH["configs"]:
        assert all(NAME.match(k) for k in c["reduced"])
    metric_names = [n for g, n in names if g in ("end_to_end", "per_layer")]
    assert len(set(metric_names)) == len(metric_names)
    assert len({n for g, n in names if g == "workloads"}) == len(CELLS)
    assert any(m["name"] == "setup_s" for m in BENCH["end_to_end"])


@pytest.mark.parametrize("cell", CELLS)
def test_each_cells_files_load(cell):
    w, entry, config, mix = H.find_cell(BENCH, cell)
    assert entry["file"].startswith(BENCH["paths"][0] + "/")
    assert H.runner_class(mix["runner"]) is not None
    assert mix["correct"], "every mix states the limits it is held to"
    for m in H.per_layer_of(BENCH, w):
        assert callable(H.load_reader(m["name"]))
    e2e = {m["name"] for m in H.end_to_end_of(BENCH, w)}
    assert "setup_s" in e2e and len(e2e) >= 2
    assert H.per_layer_of(BENCH, w)


def test_every_metric_moves_what_its_cells_report():
    for m in BENCH["per_layer"]:
        for cell in m.get("workloads", CELLS):
            w = next(x for x in BENCH["workloads"] if x["name"] == cell)
            reported = {e["name"] for e in H.end_to_end_of(BENCH, w)}
            assert m["moves"] in reported, (m["name"], cell)
    layers = {}
    for m in BENCH["per_layer"]:
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())


def test_configuration_files_differ_from_the_source_only_where_reduced():
    for c in BENCH["configs"]:
        cfg = json.loads((H.ROOT / c["file"]).read_text())
        bring_up = cfg.get("bring_up", {})
        assert set(bring_up) == set(c["reduced"]), c["name"]
        for key, value in bring_up.items():
            assert cfg.get(key) != value, (c["name"], key)


def test_a_new_cell_is_found_from_new_files_and_entries(tmp_path):
    for sub in ("configs", "traffic"):
        shutil.copytree(H.BENCH_DIR / sub, tmp_path / "chipbench" / sub)
    mix = load_traffic("fig7-campaign")
    mix["reps"] = 3
    (tmp_path / "chipbench" / "traffic" / "fig7-reps3.json").write_text(
        json.dumps(mix))
    bench = json.loads(json.dumps(BENCH))
    bench["workloads"].append({"name": "spmxv.fig7-reps3",
                               "config": "spmxv-ell-band16",
                               "traffic": "fig7-reps3", "chips": 1,
                               "why": "three repetitions a point"})
    for m in bench["end_to_end"]:
        if "spmxv.fig7-campaign" in m.get("workloads", []):
            m["workloads"].append("spmxv.fig7-reps3")
    bench["per_layer"].append({"name": "campaign.points.per_rep",
                               "unit": "points", "better": "lower",
                               "source": "program_counter",
                               "layer": "Controller and fleet",
                               "moves": "campaign_s"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    (tmp_path / "chipbench" / "metrics").mkdir()
    (tmp_path / "chipbench" / "metrics" / "campaign.points.per_rep.py"
     ).write_text("def read(run):\n    return 42.0\n")
    found = H.load_benchmark(tmp_path)
    w, _, config, got = H.find_cell(found, "spmxv.fig7-reps3", tmp_path)
    assert got["reps"] == 3 and config["rows"] == 32768
    assert {m["name"] for m in H.end_to_end_of(found, w)} == {
        "setup_s", "campaign_s"}
    # a metric without a workloads key reaches every cell reporting its
    # moves, the new one too
    assert "campaign.points.per_rep" in {m["name"] for m in
                                         H.per_layer_of(found, w)}
    # and its reader is a new file found by name
    assert H.load_reader("campaign.points.per_rep", tmp_path)(None) == 42.0

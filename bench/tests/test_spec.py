"""Discovery by name: every entry of BENCHMARK.json resolves to its files,
and a cell, configuration, traffic mix and metric added as new files and
new entries are found without editing any file that is there."""
import json
import shutil

import pytest

from bench.lib import spec as SP

BENCH = SP.load_benchmark()


@pytest.mark.parametrize("name", [w["name"] for w in BENCH["workloads"]])
def test_every_cell_resolves(name):
    cell = SP.load_cell(name)
    kind = SP.load_kind(cell.kind)
    assert callable(kind.run) and callable(kind.calibrate)
    assert SP.load_reference(cell.config["reference"])
    assert {m.name for m in cell.end_to_end} >= {"setup_s"}
    assert len(cell.end_to_end) >= 2 and cell.per_layer
    for m in cell.per_layer:
        assert callable(SP.load_metric_reader(m.name))
        assert m.moves in {e.name for e in cell.end_to_end}
    assert "window_compiles" in cell.limits["limits"]


def test_contract_shape():
    names = [m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/")
    assert all(w["chips"] == 1 for w in BENCH["workloads"])


def test_new_entries_found_by_name(tmp_path):
    root = tmp_path
    shutil.copytree(SP.ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", ".trace"))
    b = json.loads(json.dumps(BENCH))
    old = b["workloads"][0]
    b["configs"].append(dict(b["configs"][0], name="new-config",
                             file="bench/configs/new-config.json"))
    b["workloads"].append(dict(old, name="new-cell", config="new-config",
                               traffic="new-mix"))
    b["per_layer"].append({"name": "new.metric", "unit": "ms",
                           "better": "lower", "source": "host_clock",
                           "layer": "PPO pipeline", "moves": "ppo_iter_s",
                           "workloads": ["new-cell"]})
    for m in b["end_to_end"]:
        if "workloads" in m and old["name"] in m["workloads"]:
            m["workloads"].append("new-cell")
    (root / "BENCHMARK.json").write_text(json.dumps(b))
    cfg = json.loads((root / b["configs"][0]["file"]).read_text())
    (root / "bench/configs/new-config.json").write_text(
        json.dumps(dict(cfg, name="new-config")))
    mix = json.loads(
        (root / f"bench/traffic/{old['traffic']}.json").read_text())
    (root / "bench/traffic/new-mix.json").write_text(
        json.dumps(dict(mix, batch=8)))
    shutil.copy(root / f"bench/cells/{old['name']}.json",
                root / "bench/cells/new-cell.json")
    (root / "bench/metrics/new.metric.py").write_text(
        "def read(rec):\n    return 1.0\n")
    cell = SP.load_cell("new-cell", root)
    assert cell.config["name"] == "new-config"
    assert cell.traffic["batch"] == 8
    assert "new.metric" in [m.name for m in cell.per_layer]
    assert SP.load_metric_reader("new.metric", root)({}) == 1.0
    assert SP.load_kind(cell.kind, root).__name__.endswith(cell.kind)


def test_unknown_names_are_refused():
    with pytest.raises(SP.SpecError):
        SP.load_cell("no-such-cell")
    with pytest.raises(SP.SpecError):
        SP.load_metric_reader("no.such.metric")

"""The benchmark's data files: they load, their names keep to the allowed
characters, the manifest and the files agree, and a new cell, configuration
and per-layer metric are found by name as new files only."""

import json
import re
import shutil
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "benchmark"
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def manifest():
    return json.loads((ROOT / "BENCHMARK.json").read_text())


DATA_FILES = sorted(p for d in ("configs", "workloads", "metrics", "traffic")
                    for p in (BENCH / d).glob("*.json"))


@pytest.mark.parametrize("path", DATA_FILES, ids=lambda p: f"{p.parent.name}/{p.name}")
def test_data_file_loads_and_is_named_lawfully(path):
    assert isinstance(json.loads(path.read_text()), dict)
    assert NAME.match(path.stem), path.stem


def test_manifest_names_units_and_references():
    m = manifest()
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in m[k]]
    assert all(NAME.match(n) for n in names)
    assert len(set(n for x in m["end_to_end"] + m["per_layer"] for n in [x["name"]])) \
        == len(m["end_to_end"]) + len(m["per_layer"])
    for x in m["end_to_end"] + m["per_layer"]:
        assert UNIT.match(x["unit"]) and x["better"] in ("lower", "higher")
    cells = {w["name"] for w in m["workloads"]}
    e2e = {x["name"] for x in m["end_to_end"]}
    assert "setup_s" in e2e
    for w in m["workloads"]:
        assert NAME.match(w["traffic"]) and w["chips"] in (1, 4)
        assert len(w["why"]) <= 200
        assert (BENCH / "workloads" / f"{w['name']}.json").exists()
        assert (BENCH / "traffic" / f"{w['traffic']}.json").exists()
        assert w["config"] in {c["name"] for c in m["configs"]}
    for c in m["configs"]:
        assert (ROOT / c["file"]).exists()
    for x in m["per_layer"]:
        assert x["moves"] in e2e and set(x["workloads"]) <= cells


def test_every_per_layer_entry_is_its_metric_file():
    for x in manifest()["per_layer"]:
        f = json.loads((BENCH / "metrics" / f"{x['name']}.json").read_text())
        assert {k: f[k] for k in x} == x
        assert (BENCH / "readers" / f"{f['reader']}.py").exists()


def test_every_cell_reports_setup_another_metric_and_a_layer():
    m = manifest()
    for w in m["workloads"]:
        mine = [x["name"] for x in m["end_to_end"]
                if w["name"] in x.get("workloads", [w["name"]])]
        assert "setup_s" in mine and len(mine) >= 2
        cell = json.loads((BENCH / "workloads" / f"{w['name']}.json").read_text())
        assert cell["rate_metric"] in mine
        assert any(w["name"] in x["workloads"] for x in m["per_layer"])


def test_new_cell_config_and_metric_are_new_files_only(tmp_path, monkeypatch):
    """A later PR adds a configuration, a traffic mix, a cell, a metric and its
    reader as new files and manifest entries; no file that is there changes."""
    root = tmp_path / "copy"
    shutil.copytree(BENCH, root / "benchmark")
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in (root / "benchmark").rglob("*") if p.is_file()}
    b = root / "benchmark"
    cfg = json.loads((b / "configs" / "resnet50.json").read_text())
    cfg["stage_sizes"] = [2, 2, 2, 2]
    (b / "configs" / "resnet26.json").write_text(json.dumps(cfg))
    mix = json.loads((b / "traffic" / "cached.json").read_text())
    mix["shape"]["batch"] = 128
    (b / "traffic" / "cached-b128.json").write_text(json.dumps(mix))
    cell = json.loads((b / "workloads" / "rn50-cached.json").read_text())
    cell["name"] = "rn26-cached"
    (b / "workloads" / "rn26-cached.json").write_text(json.dumps(cell))
    (b / "readers" / "span_count.py").write_text(
        "def read(ctx, span):\n"
        "    n = sum(s['name'] == span for s in ctx.spans)\n"
        "    return float(n) if n else None\n")
    metric = {"name": "steps_spanned", "layer": "train step", "unit": "steps",
              "better": "higher", "source": "program_span",
              "moves": "images_per_s_per_chip", "workloads": ["rn26-cached"],
              "reader": "span_count", "args": {"span": "step"}}
    (b / "metrics" / "steps_spanned.json").write_text(json.dumps(metric))
    m = json.loads((root / "BENCHMARK.json").read_text())
    m["configs"].append({"name": "resnet26", "source": "x", "reduced": [],
                         "file": "benchmark/configs/resnet26.json", "why": "x"})
    m["workloads"].append({"name": "rn26-cached", "config": "resnet26",
                           "traffic": "cached-b128", "chips": 1, "why": "x"})
    m["per_layer"].append({k: metric[k] for k in (
        "name", "unit", "better", "source", "layer", "moves", "workloads")})
    (root / "BENCHMARK.json").write_text(json.dumps(m))

    import importlib
    import sys

    monkeypatch.syspath_prepend(str(root))
    saved = {k: sys.modules.pop(k) for k in list(sys.modules)
             if k == "benchmark" or k.startswith("benchmark.")}
    try:
        run = importlib.import_module("benchmark.run")
        assert Path(run.__file__).is_relative_to(root)
        c = run.load_cell("rn26-cached", rehearse=False)
        assert c["config"]["model"]["stage_sizes"] == [2, 2, 2, 2]
        assert c["mix"]["shape"]["batch"] == 128
        readers = importlib.import_module("benchmark.readers")
        found = readers.metric_files(c["manifest"], "rn26-cached")
        assert [f["name"] for f in found] == ["steps_spanned"]
        reader = importlib.import_module(f"benchmark.readers.{found[0]['reader']}")
        ctx = readers.Context(c["config"], c["mix"], 1,
                              [{"name": "step"}, {"name": "step"}], None, None,
                              "_step_fn", None)
        assert reader.read(ctx, **found[0]["args"]) == 2.0
    finally:
        for k in list(sys.modules):
            if k == "benchmark" or k.startswith("benchmark."):
                del sys.modules[k]
        sys.modules.update(saved)
    after = {p: p.read_bytes() for p in before}
    assert after == before

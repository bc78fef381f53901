"""``BENCHMARK.json`` against the contract's limits, and every name in it
against the files the harness finds by that name."""
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTHS = re.compile(r"(hidden|intermediate|latent|state|proj|head)_size|(_dim|_rank)$|"
                    r"expansion|experts_per_tok")


def load(path):
    with open(os.path.join(ROOT, path)) as f:
        return json.load(f)


@pytest.fixture(scope="module", params=["BENCHMARK.json", "benchmark/rehearse/BENCHMARK.json"])
def bench(request):
    return load(request.param)


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text \
        and "\t" not in text


def test_top_level(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= len(bench["command"]) <= 32 and all(line(w) for w in bench["command"])
    assert bench["paths"] == ["benchmark"] and all(PATH.match(p) for p in bench["paths"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert len(json.dumps(bench)) < 64 * 1024


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in bench["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert PATH.match(c["file"]) and c["file"].startswith("benchmark/")
        assert c["name"] in used
        assert len(c["reduced"]) <= 16
        held = load(c["file"])
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTHS.search(key), key
            assert key in held.get("reduced", {}), "%s: the file does not say why" % key
        # whatever the file keeps beside its published copy is either equal
        # to the published value or listed as reduced
        for key, value in held.get("published", {}).items():
            if key in held and held[key] != value:
                assert key in c["reduced"], "%s differs from the source" % key


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    configs = {c["name"]: c for c in bench["configs"]}
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4) and w["config"] in configs
        base = os.path.dirname(os.path.dirname(configs[w["config"]]["file"]))
        traffic = load(os.path.join(base, "traffic", w["traffic"] + ".json"))
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "runners",
                                           traffic["runner"] + ".py"))
        assert "limits" in load(os.path.join(base, "workloads", w["name"] + ".json"))
    assert sum(w["chips"] == 4 for w in bench["workloads"]) <= max(1, len(names) // 4)


def test_metrics(bench):
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    assert 1 <= len(e2e) <= 16 and len(e2e) == len(bench["end_to_end"])
    assert "setup_s" in e2e and len(bench["per_layer"]) <= 128
    every = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(every)) == len(every)
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer",
                                          "moves"}
        assert m["source"] in SOURCES and line(m["layer"]) and m["moves"] in e2e
        spec = load("benchmark/metrics/%s.json" % m["name"])
        assert spec["layer"] == m["layer"] and spec["moves"] == m["moves"]
        assert os.path.isfile(os.path.join(ROOT, "benchmark", "readers",
                                           spec["reader"] + ".py"))
        reporting = set(e2e[m["moves"]].get("workloads", cells))
        assert set(m.get("workloads", cells)) <= reporting
        if m["name"].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if cell in m.get("workloads", cells)]
        assert len(mine) >= 2, "%s needs setup_s and one more" % cell
        if bench["per_layer"]:
            assert any(cell in m.get("workloads", cells) for m in bench["per_layer"])


def test_files_under_paths_are_named_from_names():
    for dirpath, dirs, files in os.walk(os.path.join(ROOT, "benchmark")):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), ROOT)
            assert PATH.match(rel), rel

"""``BENCHMARK.json`` against the contract's limits, and against the files
it names."""

import json
import os
import re

import pytest

from benchmark.harness import cells

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
DATA_ENDINGS = (".json", ".jsonl", ".toml", ".txt", ".csv")


@pytest.fixture(scope="module")
def bench():
    return cells.load_benchmark()


def _line(text) -> bool:
    return (isinstance(text, str) and 1 <= len(text) <= 200
            and "\n" not in text and "\t" not in text)


def test_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(cells.REPO, "BENCHMARK.json")) \
        <= 64 * 1024
    assert 1 <= len(bench["paths"]) <= 16
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32 and all(map(_line, bench["command"]))
    assert isinstance(bench["run_seconds"], int)
    assert 1 <= bench["run_seconds"] <= 51
    assert 1 <= len(bench["configs"]) <= 24
    assert 2 <= len(bench["workloads"]) <= 24
    assert 1 <= len(bench["end_to_end"]) <= 16
    assert 1 <= len(bench["per_layer"]) <= 128
    # a full check of 24 cells fits the driver's 43200 s
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_configs(bench):
    names = [c["name"] for c in bench["configs"]]
    files = [c["file"] for c in bench["configs"]]
    assert len(set(names)) == len(names) and len(set(files)) == len(files)
    used = {w["config"] for w in bench["workloads"]}
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["name"] in used
        assert _line(c["source"]) and _line(c["why"])
        assert any(c["file"].startswith(p + "/") for p in bench["paths"])
        assert len(c["reduced"]) <= 16
        with open(os.path.join(cells.REPO, c["file"])) as f:
            body = json.load(f)
        assert body["source"] == c["source"]
        assert body["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert NAME.match(key) and key in body and key in body["cuts"]
            # a cut of scale, never of a shape
            assert not key.endswith(("_dim", "_rank")) and key not in (
                "cols", "size", "options")
        assert body["guarantee"] and body["deployment"]
        assert os.path.exists(os.path.join(
            cells.BENCH_DIR, "runners", body["runner"] + ".py"))


def test_workloads(bench):
    names = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in bench["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 4)
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert all(NAME.match(w[k]) for k in ("name", "config", "traffic"))
        assert w["chips"] in (1, 4) and _line(w["why"])
        mixes = [f for f in os.listdir(os.path.join(cells.BENCH_DIR,
                                                    "traffic"))
                 if f.rsplit(".", 1)[0] == w["traffic"]]
        assert len(mixes) == 1 and mixes[0].endswith(DATA_ENDINGS)
        cell = cells.load_cell(w["name"])       # every file is found
        own = cell.workload
        assert (own["config"], own["traffic"], own["chips"], own["why"]) == (
            w["config"], w["traffic"], w["chips"], w["why"])
        assert own["item"]
        reported = [m["name"] for m in cell.end_to_end]
        assert "setup_s" in reported and len(reported) >= 2
        assert cell.per_layer


def test_metrics(bench):
    cellnames = {w["name"] for w in bench["workloads"]}
    every = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in every]
    assert len(set(names)) == len(names)
    e2e = {m["name"] for m in bench["end_to_end"]}
    assert "setup_s" in e2e
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
        assert os.path.exists(os.path.join(cells.BENCH_DIR, "end_to_end",
                                           m["name"] + ".py"))
    layers = set()
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in e2e and _line(m["layer"])
        # reported only where the metric it moves is: the driver reads the
        # lists as written, and no list means every cell
        moved = next(e for e in bench["end_to_end"]
                     if e["name"] == m["moves"])
        assert set(m.get("workloads", cellnames)) <= set(
            moved.get("workloads", cellnames)), m["name"]
        layers.add(m["layer"])
        assert os.path.exists(os.path.join(cells.BENCH_DIR, "layer_metrics",
                                           m["name"] + ".py"))
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    for m in every:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
        assert set(m.get("workloads", cellnames)) <= cellnames
    with open(os.path.join(cells.REPO, "PERF.md")) as f:
        perf = f.read()
    for layer in layers:            # PERF.md's list of layers has the name
        assert f"**{layer}**" in perf, layer


def test_files_under_paths_are_named_from_allowed_characters(bench):
    for base in bench["paths"]:
        for root, dirs, files in os.walk(os.path.join(cells.REPO, base)):
            dirs[:] = [d for d in dirs if d != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(root, f), cells.REPO)
                assert PATH.match(rel), rel

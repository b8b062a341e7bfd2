"""The harness's last line against the contract, from a rehearsal of the
smallest cell (a subprocess: a run owns its JAX), and the refusals."""

import json
import os
import subprocess
import sys

import pytest

from benchmark.harness import cells

RUN = [sys.executable, os.path.join(cells.BENCH_DIR, "run.py")]


def _run(*args, env=None):
    return subprocess.run(RUN + list(args), capture_output=True, text=True,
                          timeout=300, cwd=cells.REPO,
                          env={**os.environ, **(env or {})})


@pytest.mark.parametrize("name,traced", [("mt_host_verbs", 0),
                                         ("tables_rounds_4c", 1)])
def test_rehearsal_prints_the_contract_line(name, traced):
    res = _run("--workload", name, "--seed", "2", "--seconds",
               "1", "--trace", str(traced), "--rehearsal")
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    line = json.loads(res.stdout.strip().splitlines()[-1])
    want = {"correct", "attempted", "failed", "metrics", "device",
            "rehearsal"} | ({"breakdown"} if traced else set())
    assert set(line) == want
    assert line["correct"] is True and line["rehearsal"] is True
    assert line["device"]["platform"] == "cpu"      # never a chip result
    assert {"platform", "kind", "count", "memory_peak_bytes"} <= set(
        line["device"])
    cell = cells.load_cell(name)
    allowed = {m["name"]: m["unit"] for m in (
        cell.per_layer if traced else cell.end_to_end)}
    assert line["metrics"] and set(line["metrics"]) <= set(allowed)
    for name, m in line["metrics"].items():
        assert set(m) == {"value", "unit"} and m["unit"] == allowed[name]
        assert isinstance(m["value"], float)
    if traced:
        assert line["device"]["busy_s"] > 0
        assert line["device"]["window_s"] > 0
        assert set(line["breakdown"]) == {"device_ops", "idle_gaps"}
        assert len(line["breakdown"]["device_ops"]) <= 10
    else:
        assert set(line["metrics"]) == set(allowed)


def test_a_cpu_is_refused_without_a_result():
    res = _run("--workload", "mt_host_verbs", "--seed", "1", "--seconds",
               "1", "--trace", "0", env={"JAX_PLATFORMS": "cpu"})
    assert res.returncode != 0
    assert not res.stdout.strip().splitlines()[-1].startswith("{")


def test_an_unknown_cell_is_refused():
    res = _run("--workload", "no_such_cell", "--seed", "1", "--seconds",
               "1", "--trace", "0", "--rehearsal")
    assert res.returncode != 0

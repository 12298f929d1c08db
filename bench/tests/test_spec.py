"""``BENCHMARK.json`` against the benchmark's contract, and every file it
names found where the harness looks for it."""
import os
import re

import pytest

from harness import common

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


@pytest.fixture(scope="module")
def spec():
    return common.benchmark_spec()


def test_top_level_keys(spec):
    assert set(spec) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert spec["command"] == ["python3", "bench/run.py"]
    assert spec["paths"] == ["bench"]
    assert 1 <= spec["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(common.ROOT, "BENCHMARK.json")) \
        <= 64 * 1024


def test_configs_and_cells(spec):
    names = [c["name"] for c in spec["configs"]]
    assert len(set(names)) == len(names)
    used = {w["config"] for w in spec["workloads"]}
    assert used == set(names)
    for c in spec["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and c["file"].startswith("bench/")
        assert os.path.exists(os.path.join(common.ROOT, c["file"]))
        ref = os.path.splitext(c["file"])[0] + ".py"
        assert os.path.exists(os.path.join(common.ROOT, ref))
        assert 1 <= len(c["source"]) <= 200 and 1 <= len(c["why"]) <= 200
    pairs = set()
    for w in spec["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["chips"] == 1
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        assert len(w["why"]) <= 200 and "\n" not in w["why"]
        traffic = common.load_json("traffic", f"{w['traffic']}.json")
        assert os.path.exists(os.path.join(common.BENCH, "kinds",
                                           f"{traffic['kind']}.py"))


def test_metrics(spec):
    cells = {w["name"] for w in spec["workloads"]}
    e2e = {m["name"]: m for m in spec["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in spec["end_to_end"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
        assert set(m.get("workloads", cells)) <= cells
    layers = set()
    for m in spec["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["moves"] in e2e
        assert set(m["workloads"]) <= set(e2e[m["moves"]].get("workloads",
                                                              cells))
        assert os.path.exists(os.path.join(common.BENCH, "metrics",
                                           f"{m['name']}.py"))
        layers.add(m["layer"])
    for cell in cells:
        reported = [m for m in spec["end_to_end"]
                    if cell in m.get("workloads", cells)]
        assert len(reported) >= 2
        assert any(cell in m["workloads"] for m in spec["per_layer"])

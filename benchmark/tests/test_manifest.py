"""Every name in BENCHMARK.json resolves to a file of its own and holds
only legal characters; the contract's limits on the file hold."""

import json
import os
import re

import pytest

from benchmark import harness

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
        "end_to_end", "per_layer"}


@pytest.fixture(scope="module")
def manifest():
    return harness.load_manifest()


def test_top_level(manifest):
    assert set(manifest) == KEYS
    assert 1 <= manifest["run_seconds"] <= 51
    assert len(json.dumps(manifest)) < 64 * 1024
    cells = len(manifest["workloads"])
    assert (2 + 14 * 24) * (manifest["run_seconds"] + 60) + 24 * 180 \
        + 1200 <= 43200, "run_seconds must fit a check of the full 24 cells"
    assert sum(w["chips"] == 4 for w in manifest["workloads"]) \
        <= max(1, cells // 4)
    for word in manifest["command"]:
        assert not word.startswith("/") and ".." not in word


def test_configs_resolve(manifest):
    for c in manifest["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"])
        assert c["file"] == f"benchmark/configs/{c['name']}.json"
        cfg = json.load(open(os.path.join(harness.ROOT, c["file"])))
        assert os.path.exists(os.path.join(
            harness.HERE, "drivers", f"{cfg['kind']}.py"))
        assert os.path.exists(os.path.join(
            harness.HERE, "reference", f"{cfg['reference']}.py"))
        for key in c["reduced"]:
            assert NAME.match(key) and key in cfg
            assert not re.search(r"(_dim|_rank|_size)$", key), \
                "a width may never be reduced"
            assert cfg[key] != cfg["published"][key]
        for key, val in cfg["published"].items():
            if key in cfg and key not in c["reduced"]:
                assert cfg[key] == val, f"{key} differs from the source"
        assert "limits" in cfg
    used = {w["config"] for w in manifest["workloads"]}
    assert used == {c["name"] for c in manifest["configs"]}


def test_workloads_resolve(manifest):
    seen = set()
    for w in manifest["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
        assert (w["config"], w["traffic"]) not in seen
        seen.add((w["config"], w["traffic"]))
        t = harness.load_json("traffic", f"{w['traffic']}.json")
        assert t["generator"] in ("closed_loop_lm", "image_classification")


def test_metrics_resolve(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.1
    for m in manifest["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.1
        assert m["source"] in ("host_clock", "device_trace")
    layers = set()
    for m in manifest["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert m["moves"] in e2e
        spec = harness.load_json("metrics", f"{m['name']}.json")
        for key in ("name", "unit", "layer", "moves", "better", "source"):
            assert spec[key] == m[key], (m["name"], key)
        assert os.path.exists(os.path.join(
            harness.HERE, "readers", f"{spec['reader']}.py"))
        for cell in m.get("workloads", []):
            assert cell in cells
            moved = e2e[m["moves"]]
            assert cell in moved.get("workloads", cells)
        layers.add(m["layer"])
    # beside every kernel's roofline the whole step's share of the peak,
    # moving the same end-to-end metric in the same cells
    for m in manifest["per_layer"]:
        if m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
            assert any(
                "mfu" in re.split(r"[._\-]", o["name"])
                and o["moves"] == m["moves"]
                and set(m["workloads"]) <= set(o["workloads"])
                for o in manifest["per_layer"]), m["name"]
    perf = open(os.path.join(harness.ROOT, "PERF.md")).read()
    for layer in layers:
        assert f"**{layer}**" in perf, f"PERF.md has no layer {layer!r}"
    for cell in cells:
        assert len(harness.cell_metrics(manifest, cell, "end_to_end")) >= 2
        assert harness.cell_metrics(manifest, cell, "per_layer")

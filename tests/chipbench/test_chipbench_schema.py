"""``BENCHMARK.json`` against the benchmark's contract, and every file it
names found by name."""
import json
import math
import pathlib
import re

import pytest

ROOT = pathlib.Path(__file__).resolve().parents[2]
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
WIDTH = re.compile(r"(_dim|_rank)$|hidden|intermediate|latent|state|projection|head|expansion")


def line(text):
    return isinstance(text, str) and 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_top_level_keys_and_command():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(line(w) for w in cmd)
    assert (ROOT / cmd[1]).is_file()
    assert any(cmd[1].startswith(p + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_run_seconds_fits_a_full_check_of_24_cells():
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200


def test_configs():
    names = [c["name"] for c in BENCH["configs"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    files = [c["file"] for c in BENCH["configs"]]
    assert len(set(files)) == len(files)
    used = {w["config"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and line(c["source"]) and line(c["why"])
        assert c["source"].startswith("https://")
        assert c["name"] in used
        assert any(c["file"].startswith(p + "/") for p in BENCH["paths"])
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key) and not WIDTH.search(key)
            assert key in cfg["reduced"] and key in cfg["published"]
            assert cfg[key] != cfg["published"][key]
        assert set(cfg["published"]) == set(c["reduced"])
        assert (ROOT / "chipbench" / "gen" / f"{cfg['generator']}.py").is_file()


def test_workloads():
    names = [w["name"] for w in BENCH["workloads"]]
    assert 1 <= len(names) <= 24 and len(set(names)) == len(names)
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(names) // 2)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"]) and line(w["why"])
        assert w["chips"] in (1, 4)
        traffic = json.loads(
            (ROOT / "chipbench" / "traffic" / f"{w['traffic']}.json").read_text())
        assert (ROOT / "chipbench" / "ops" / f"{traffic['op']}.py").is_file()
        limits = json.loads(
            (ROOT / "chipbench" / "cells" / f"{w['name']}.json").read_text())["limits"]
        assert limits and all(v > 0 for v in limits.values())


def cells_reporting(metric):
    return metric.get("workloads", [w["name"] for w in BENCH["workloads"]])


def test_metrics():
    e2e, per = BENCH["end_to_end"], BENCH["per_layer"]
    assert 1 <= len(e2e) <= 16 and 1 <= len(per) <= 128
    names = [m["name"] for m in e2e + per]
    assert len(set(names)) == len(names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for m in e2e:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25 and not math.isnan(m["bound"])
    setup = {m["name"]: m for m in e2e}["setup_s"]
    assert "workloads" not in setup and setup["bound"] <= 0.25
    for m in per:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert line(m["layer"]) and m["source"] in SOURCES
        moved = {x["name"]: x for x in e2e}[m["moves"]]
        for cell in cells_reporting(m):
            assert cell in cells_reporting(moved)
    for m in e2e + per:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
        assert (ROOT / "chipbench" / "metrics" / f"{m['name']}.py").is_file()
    for cell in cells:
        reported = [m["name"] for m in e2e if cell in cells_reporting(m)]
        assert "setup_s" in reported and len(reported) >= 2
        assert any(cell in cells_reporting(m) for m in per)


@pytest.mark.parametrize("name,cells", [
    ("op_p95_ms", ["g500-s19.spmv"]),
    ("collective.device_ms", ["g500-s19.spmv.4chip"]),
])
def test_metrics_limited_to_their_cells(name, cells):
    m = {x["name"]: x for x in BENCH["end_to_end"] + BENCH["per_layer"]}[name]
    assert m["workloads"] == cells

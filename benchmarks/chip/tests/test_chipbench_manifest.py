"""``BENCHMARK.json``: its shape, its names and units, and the files it
names."""

import json
import re

import pytest

from chipbench_testing import BENCH
from chipbench import manifest

MAN = json.loads((BENCH.parents[1] / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
METRICS = MAN["end_to_end"] + MAN["per_layer"]


def test_top_level_keys():
    assert set(MAN) == {"command", "paths", "run_seconds", "configs",
                        "workloads", "end_to_end", "per_layer"}
    assert MAN["paths"] == ["benchmarks/chip"]
    assert MAN["command"][1] == "benchmarks/chip/run.py"
    assert isinstance(MAN["run_seconds"], int) and 1 <= MAN["run_seconds"] <= 51


@pytest.mark.parametrize("m", METRICS, ids=lambda m: m["name"])
def test_metric_entry(m):
    keys = {"name", "unit", "better", "source"}
    if m in MAN["end_to_end"]:
        keys |= {"bound"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    else:
        keys |= {"layer", "moves"}
        assert m["moves"] in {e["name"] for e in MAN["end_to_end"]}
        assert "\n" not in m["layer"] and 1 <= len(m["layer"]) <= 200
    assert set(m) - {"workloads"} == keys
    assert NAME.match(m["name"]) and UNIT.match(m["unit"])
    assert m["better"] in ("lower", "higher")
    assert m["source"] in ("device_trace", "program_span", "program_counter", "host_clock")
    for w in m.get("workloads", []):
        manifest.cell(MAN, w)
    assert (BENCH / "metrics" / f"{m['name']}.py").is_file()
    if m["name"].endswith("_roofline") or "mfu" in m["name"]:
        assert m["unit"] == "%"


def test_names_are_unique():
    for group in (METRICS, MAN["workloads"], MAN["configs"]):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names))


@pytest.mark.parametrize("c", MAN["configs"], ids=lambda c: c["name"])
def test_config_entry_and_file(c):
    assert set(c) == {"name", "source", "file", "reduced", "why"}
    assert NAME.match(c["name"]) and c["source"].startswith("https://")
    assert c["file"].startswith("benchmarks/chip/configs/")
    cfg = manifest.config(MAN, c["name"], BENCH.parents[1])
    assert cfg["reduced"] == c["reduced"]
    assert (BENCH / "reference" / f"{cfg['reference']}.py").is_file()
    assert (BENCH / "engines" / f"{cfg['engine']}.py").is_file()
    assert any(w["config"] == c["name"] for w in MAN["workloads"])


@pytest.mark.parametrize("w", MAN["workloads"], ids=lambda w: w["name"])
def test_cell_entry(w):
    assert set(w) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(w["name"]) and NAME.match(w["traffic"])
    assert w["chips"] in (1, 4)
    assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    assert (BENCH / "traffic" / f"{w['traffic']}.json").is_file()
    e2e = manifest.metrics_of(MAN, w["name"], "end_to_end")
    assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
    assert manifest.metrics_of(MAN, w["name"], "per_layer")


def test_every_file_name_is_made_of_name_characters():
    for p in BENCH.rglob("*"):
        if "__pycache__" in p.parts or not p.is_file():
            continue
        rel = p.relative_to(BENCH.parents[1]).as_posix()
        assert re.match(r"^[A-Za-z0-9_./-]+$", rel), rel

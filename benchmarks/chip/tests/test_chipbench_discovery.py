"""A configuration, a traffic mix and a metric are added by adding files
and manifest entries: nothing that exists is edited."""

import json
import shutil

import pytest

from chipbench_testing import BENCH, run_smoke, smoke_root
from chipbench import manifest


@pytest.fixture(scope="module")
def added(tmp_path_factory):
    """A copy of the benchmark directory with one new file of each kind
    and a root whose manifest names them."""
    tmp = tmp_path_factory.mktemp("added")
    bench = tmp / "bench"
    shutil.copytree(BENCH, bench, ignore=shutil.ignore_patterns("__pycache__"))
    mix = json.loads((bench / "traffic" / "longanswer.json").read_text())
    mix.update(name="tiny", wave_size=4,
               prompt_tokens={"dist": "uniform", "min": 8, "max": 40},
               output_tokens={"dist": "uniform", "min": 5, "max": 9})
    (bench / "traffic" / "tiny.json").write_text(json.dumps(mix))
    (bench / "metrics" / "prompt_tokens.new.py").write_text(
        "def read(rec):\n"
        "    return sum(int(r['prompt'].size) for r in rec.requests)\n")
    root = smoke_root(tmp)
    man = json.loads((root / "BENCHMARK.json").read_text())
    cfg = json.loads((BENCH / "tests" / "data" / "phi3-mini-3.8b-smoke.json").read_text())
    cfg.update(name="phi3-twin-added")
    (tmp / "added.json").write_text(json.dumps(cfg))
    man["configs"].append({"name": "phi3-twin-added", "source": cfg["source"],
                           "file": str(tmp / "added.json"),
                           "reduced": cfg["reduced"], "why": "added"})
    man["workloads"].append({"name": "added.tiny", "config": "phi3-twin-added",
                             "traffic": "tiny", "chips": 1, "why": "added"})
    man["end_to_end"].append({"name": "prompt_tokens.new", "unit": "tokens",
                              "better": "higher", "bound": 0.01,
                              "source": "host_clock", "workloads": ["added.tiny"]})
    (root / "BENCHMARK.json").write_text(json.dumps(man))
    return root, bench


def test_found_by_name(added):
    root, bench = added
    man = manifest.load(root)
    assert manifest.config(man, "phi3-twin-added", root)["name"] == "phi3-twin-added"
    assert manifest.traffic("tiny", bench)["wave_size"] == 4
    assert manifest.module("metrics", "prompt_tokens.new", bench).read
    names = [m["name"] for m in manifest.metrics_of(man, "added.tiny", "end_to_end")]
    assert "prompt_tokens.new" in names and "setup_s" in names
    assert "prompt_tokens.new" not in [
        m["name"] for m in manifest.metrics_of(man, "qwen2.5-3b.longprompt", "end_to_end")]


def test_added_cell_runs_and_reports_the_added_metric(added):
    root, bench = added
    out, rec = run_smoke(root, "added.tiny", bench_dir=bench)
    assert out["correct"], out["checks"]
    assert out["metrics"]["prompt_tokens.new"]["value"] == sum(
        r["prompt"].size for r in rec.requests)
    # the serving metrics list their cells; the added cell is not among them
    assert set(out["metrics"]) == {"prompt_tokens.new", "setup_s"}


def test_unknown_names_are_errors(added):
    root, bench = added
    man = manifest.load(root)
    with pytest.raises(KeyError):
        manifest.cell(man, "no.such.cell")
    with pytest.raises(FileNotFoundError):
        manifest.module("metrics", "no_such_metric", bench)

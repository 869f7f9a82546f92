"""The benchmark's data: every cell is found by name, and BENCHMARK.json
keeps to the shape its readers expect."""
import json
import re
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "bench"))
sys.path.insert(0, str(ROOT / "src"))

from harness import cell  # noqa: E402

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
CELLS = [w["name"] for w in BENCH["workloads"]]


@pytest.mark.parametrize("workload", CELLS)
def test_cell_found_by_name(workload):
    c, config, traffic, limits, driver, e2e, layer = cell.lookup(workload, ROOT)
    assert c["config"] == config["name"]
    for fn in ("setup", "window", "end_to_end", "counts", "answers"):
        assert callable(getattr(driver, fn))
    assert {"setup_s"} < {m["name"] for m in e2e}
    assert layer, "every cell reports a per-layer metric"
    for m in layer:
        reader = cell.load_module(ROOT / "bench" / "metrics" / f"{m['name']}.py", "m")
        assert callable(reader.read)
    assert set(limits) >= {"unanswered", "bad_hosts", "stray", "mass_err", "J_gap", "no_gain_pct",
                           "polish_gain"}


def test_unknown_cell_is_an_error():
    with pytest.raises(KeyError):
        cell.lookup("no-such.cell", ROOT)


def test_benchmark_json_shape():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert (ROOT / p).is_dir()
    names = [c["name"] for c in BENCH["configs"]] + CELLS + [
        m["name"] for m in BENCH["end_to_end"] + BENCH["per_layer"]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert json.loads((ROOT / c["file"]).read_text())["reduced"] == c["reduced"]
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for w in m["workloads"]:
            assert w in CELLS
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        assert (ROOT / "bench" / "limits" / f"{w['name']}.json").is_file()
    assert 1 <= BENCH["run_seconds"] <= 51


def test_sub_seeds_take_large_seeds():
    a = cell.sub_seeds(2**31 + 17, 3)
    assert a == cell.sub_seeds(2**31 + 17, 3)
    assert a != cell.sub_seeds(2**31 + 18, 3)
    assert all(0 <= s < 2**31 for s in a)

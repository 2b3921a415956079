"""Every cell and metric of BENCHMARK.json resolves by name to its files,
and the file keeps the benchmark's contract on keys, names and limits."""
import importlib
import importlib.util
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


BENCH = _load("BENCHMARK.json")


def test_top_level_keys_and_paths():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert not p.startswith("/") and ".." not in p.split("/")
    script = BENCH["command"][1]
    assert any(script.startswith(p + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int)
    assert 1 <= BENCH["run_seconds"] <= 51


def test_names_units_and_bounds():
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for item in BENCH[group]:
            assert NAME.match(item["name"]), item["name"]
            assert (group, item["name"]) not in seen
            seen.add((group, item["name"]))
    ends = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in ends and ends["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    for m in BENCH["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in BENCH["per_layer"]:
        assert m["moves"] in ends
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")


@pytest.mark.parametrize("cell", BENCH["workloads"], ids=lambda c: c["name"])
def test_cell_resolves_to_its_files(cell):
    confs = {c["name"]: c for c in BENCH["configs"]}
    conf = confs[cell["config"]]
    entry = _load(conf["file"])
    assert entry["name"] == conf["name"]
    assert os.path.exists(os.path.join(ROOT, "bench", "reference",
                                       entry["reference"] + ".py"))
    assert set(conf["reduced"]) == set(entry["reduced"])
    mix = _load("bench", "traffic", cell["traffic"] + ".json")
    assert os.path.exists(os.path.join(ROOT, "bench", "jobs",
                                       mix["kind"] + ".py"))
    limits = _load("bench", "limits", cell["name"] + ".json")
    job = importlib.import_module(f"bench.jobs.{mix['kind']}")
    assert set(limits) >= set(job.NUMBERS)
    assert cell["chips"] in (1, 4) and len(cell["why"]) <= 200
    listed = [m for m in BENCH["per_layer"]
              if cell["name"] in m.get("workloads", [cell["name"]])]
    assert listed, "every cell reports a per-layer metric"


@pytest.mark.parametrize("metric", BENCH["per_layer"],
                         ids=lambda m: m["name"])
def test_metric_has_a_reader(metric):
    path = os.path.join(ROOT, "bench", "metrics", metric["name"] + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.read({}) is None          # nothing to read: no number
    cells = {w["name"] for w in BENCH["workloads"]}
    assert set(metric.get("workloads", [])) <= cells


def test_every_reader_file_returns_nothing_without_input():
    folder = os.path.join(ROOT, "bench", "metrics")
    for name in sorted(os.listdir(folder)):
        if not name.endswith(".py"):
            continue
        spec = importlib.util.spec_from_file_location(
            "reader", os.path.join(folder, name))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        assert mod.read({}) is None, name


def test_peaks_table_names_its_source():
    peaks = _load("bench", "peaks.json")
    assert "TPU v5e" in peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9

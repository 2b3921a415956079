"""The harness refuses to run where JAX finds no TPU: non-zero exit, no
result line."""
import json
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def test_run_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cell = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))[
        "workloads"][0]["name"]
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", cell, "--seed", str(2**31 + 3), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=env, capture_output=True, text=True,
        timeout=300)
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_unknown_device_kind_is_an_error(monkeypatch):
    import jax
    from bench import run as R

    class Chip:
        platform, device_kind = "tpu", "TPU v99"

    monkeypatch.setattr(jax, "devices", lambda *a: [Chip()])
    peaks = R.load_json("bench", "peaks.json")
    try:
        R.device_info(1, peaks)
    except SystemExit as e:
        assert "not in bench/peaks.json" in str(e)
    else:
        raise AssertionError("device_info accepted a kind with no peaks")
    Chip.device_kind = "TPU v5 lite"
    assert R.device_info(1, peaks) == {"platform": "tpu",
                                       "kind": "TPU v5 lite", "count": 1}

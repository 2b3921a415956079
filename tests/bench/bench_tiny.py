"""A benchmark cell at a size the CPU test suite can hold: the cell's own
configuration, traffic and limits with the widths, depth, vocabulary and
rows cut, in float32 (the CPU has no bf16 x bf16 -> f32 dot)."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def tiny(cell_name: str) -> dict:
    bench = load("BENCHMARK.json")
    cell = {w["name"]: w for w in bench["workloads"]}[cell_name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    entry = copy.deepcopy(load(conf["file"]))
    mix = copy.deepcopy(load("bench", "traffic", cell["traffic"] + ".json"))
    m = entry["model"]
    m.update(dtype="float32", num_layers=3, d_model=64, d_ff=128,
             vocab_size=256)
    if m["family"] == "ssm":
        m.update(rwkv_head_dim=16)
    else:
        m.update(num_heads=8, num_kv_heads=2)
    mix.update(batch=4, seq=32, channel_block=16, feed_batches=8)
    limits = load("bench", "limits", cell_name + ".json")
    return {"cell": cell, "entry": entry, "mix": mix, "limits": limits}


def cells(kind: str) -> list:
    bench = load("BENCHMARK.json")
    listed = [w["name"] for w in bench["workloads"]
              if load("bench", "traffic", w["traffic"] + ".json")["kind"]
              == kind]
    return listed


def run_cell(cell_name: str, seed: int, fault=None, seconds: float = 0.5,
             trace: int = 0):
    from bench import run as R
    ov = tiny(cell_name)
    ov["fault"] = fault
    return R.main(["--workload", cell_name, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, overrides=ov)

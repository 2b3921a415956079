"""A benchmark cell at a size the CPU test suite can hold: the cell's own
configuration, traffic and limits with the widths, depth, vocabulary and
rows cut, in float32 (the CPU has no bf16 x bf16 -> f32 dot).

Beside the cells of BENCHMARK.json, a tiny mixture-of-experts cell made
here alone, from no file of the benchmark: the program's `moe` family in
the DeepSeekMoE layout (one dense layer, then two expert layers of 8
experts, top-2, 2 shared) against bench/reference/moe_lm.py, with a
capacity factor of E / top_k so that the dispatch drops no token. It shows
that such a configuration needs new files only: a config, a reference
module, a traffic mix and a limits file."""
import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


TINY_MOE = {"moe-tiny.train-sgd": "train-sparse-sgd-b2s2048",
            "moe-tiny.train-adamw": "train-sparse-adamw-b4s1024"}


def tiny_moe(cell_name: str) -> dict:
    """The tiny MoE cell under the optimizer of the named existing mix."""
    entry = {"name": "moe-tiny", "reference": "moe_lm",
             "reduced": [], "model": {
                 "family": "moe", "num_layers": 3, "d_model": 64,
                 "num_heads": 4, "num_kv_heads": 2, "d_ff": 32,
                 "vocab_size": 256, "mlp_kind": "swiglu",
                 "norm_kind": "rmsnorm", "rope_theta": 10000.0,
                 "dtype": "float32",
                 "moe": {"num_experts": 8, "top_k": 2,
                         "num_shared_experts": 2, "capacity_factor": 4.0,
                         "layout": "all_but_first"}}}
    mix = copy.deepcopy(load("bench", "traffic",
                             TINY_MOE[cell_name] + ".json"))
    mix.update(batch=4, seq=32, channel_block=16, feed_batches=8)
    cell = {"name": cell_name, "config": "moe-tiny", "chips": 1,
            "traffic": TINY_MOE[cell_name], "why": "CPU test cell"}
    limits = {"loss_gap": 0.03, "grad_gap": 0.05, "change_gap": 0.05}
    return {"cell": cell, "entry": entry, "mix": mix, "limits": limits}


def tiny(cell_name: str) -> dict:
    if cell_name in TINY_MOE:
        return tiny_moe(cell_name)
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
    """The cells of BENCHMARK.json whose mix is of `kind`, and for training
    the tiny MoE cells."""
    bench = load("BENCHMARK.json")
    listed = [w["name"] for w in bench["workloads"]
              if load("bench", "traffic", w["traffic"] + ".json")["kind"]
              == kind]
    if kind == "train":
        listed += sorted(TINY_MOE)
    return listed


def run_cell(cell_name: str, seed: int, fault=None, seconds: float = 0.5,
             trace: int = 0):
    from bench import run as R
    ov = tiny(cell_name)
    ov["fault"] = fault
    return R.main(["--workload", cell_name, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", str(trace)],
                  require_tpu=False, overrides=ov)

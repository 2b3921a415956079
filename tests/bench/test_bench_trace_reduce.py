"""trace_reduce on a small event file made by hand with known busy, idle,
kernel and step times, laid out as `load_events` reads a TPU trace."""
import json
import os

import pytest

from bench import trace_reduce as T

DATA = os.path.join(os.path.dirname(__file__), "data")


def test_handmade_trace_known_times():
    events = json.load(open(os.path.join(DATA,
                                         "trace_events_handmade.json")))
    span = T.host_span(events, "bench_window")
    assert span == (1000, 51000)
    red = T.reduce(events, span, ["masked_dw", "fused_block_opt"],
                   "train_step")
    # ops 1000..21000 (two overlapping and one clipped), 31000..41000,
    # 43000..45000: 32 us busy of a 50 us window
    assert red["busy_s"] == pytest.approx(32e-6)
    assert red["window_s"] == pytest.approx(50e-6)
    assert red["kernel_s"] == pytest.approx({"masked_dw": 10e-6,
                                             "fused_block_opt": 2e-6})
    assert red["steps"] == 2 and red["step_s"] == pytest.approx(25e-6)
    # gaps: 21000..31000 (host in sleep), 45000..51000, 41000..43000
    assert red["idle_gaps"][0] == ["$time.py sleep", pytest.approx(10e-6)]
    assert [g[1] for g in red["idle_gaps"]] == pytest.approx(
        [10e-6, 6e-6, 2e-6])
    assert red["device_ops"][:2] == [["convolution", pytest.approx(15e-6)],
                                     ["fusion", pytest.approx(10.5e-6)]]



def test_device_ops_count_self_time():
    """A `while` and the body ops nested in it are counted once: the listed
    ops' seconds come to no more than the busy time (here, with every op
    family listed, to exactly it)."""
    events = json.load(open(os.path.join(DATA, "trace_events_scoped.json")))
    span = T.host_span(events, "bench_window")
    red = T.reduce(events, span, [], "train_step")
    listed = sum(s for _name, s in red["device_ops"])
    assert listed <= red["busy_s"] * (1 + 1e-12)
    assert listed == pytest.approx(red["busy_s"])
    ops = [e for e in events if e["line"] == "XLA Ops"]
    whole = sum(T._clip(e, *span)[1] - T._clip(e, *span)[0] for e in ops
                if T._clip(e, *span)) / 1e9
    assert whole > red["busy_s"]       # what whole durations would list

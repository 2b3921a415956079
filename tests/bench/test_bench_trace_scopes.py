"""trace_scopes on a small event file made by hand: a `while` with two
body ops under different scopes, a backward op (`transpose(jvp(...))`),
an op with no scope and one clipped by the window, with known self times;
the op names taken from the program's HLO text, on those events and on a
slice of a real v5e trace; and the per-layer readers that read them."""
import importlib.util
import json
import os

import pytest

from bench import trace_reduce as TRD
from bench import trace_scopes as S

DATA = os.path.join(os.path.dirname(__file__), "data")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CELL = "rwkv6-3b.train-sparse"


def _events():
    """The device ops with the scope they were made with."""
    with open(os.path.join(DATA, "trace_events_scoped.json")) as f:
        return json.load(f)


def _hlo(events) -> str:
    """An HLO module text holding each device op's instruction as a
    compiled module prints it (operands without their shapes), with its
    scope as the op_name metadata."""
    lines = ["HloModule jit_train_step", "ENTRY %main {"]
    for e in events:
        if e["line"] == "XLA Ops":
            meta = f', metadata={{op_name="{e["scope"]}"}}' if e["scope"] \
                else ""
            lines.append(f"  {S.signature(e['name'])}(%p.0){meta}")
    return "\n".join(lines + ["}"])


def _trace_form(events):
    """The events as a TPU trace has them: no op names."""
    return [{k: v for k, v in e.items() if k != "scope"} for e in events]


def test_handmade_scopes_known_self_times():
    events = _events()
    span = TRD.host_span(events, "bench_window")
    assert span == (1000, 51000)
    got = S.tally(S.op_times(events, span), S.NAMES)
    # while 2000..22000 holds 6 us of token_mix and 8 us of channel_mix:
    # 6 us of its own; the backward conv 10 us; fusion.7 clipped to 3 us;
    # ops outside the window count nothing
    assert got["scope_s"] == pytest.approx({
        "embed": 0.0, "frozen_layers": 20e-6, "trainable_layers": 10e-6,
        "head_loss": 3e-6, "reselect": 0.0, "update": 4e-6,
        "token_mix": 16e-6, "channel_mix": 8e-6})
    assert got["unscoped_s"] == pytest.approx(4e-6)
    assert got["unscoped_ops"] == [["copy", pytest.approx(4e-6)]]
    assert got["busy_s"] == pytest.approx(41e-6)
    assert got["busy_s"] == pytest.approx(
        TRD.reduce(events, span, [], "train_step")["busy_s"])
    top = sum(got["scope_s"][k] for k in S.TOP) + got["unscoped_s"]
    assert top == pytest.approx(got["busy_s"])


def test_op_names_come_from_the_programs_instructions():
    made = _events()
    events = _trace_form(made)
    assert S.attach_scopes(events, _hlo(made)) == (1.0, [])
    assert [e.get("scope") for e in events] == [
        e["scope"] if e["line"] == "XLA Ops" else None for e in made]
    # another program: same instruction names, other shapes
    other = _hlo(made).replace("f32[4,64]", "f32[8,64]")
    share, missed = S.attach_scopes(events, other)
    assert share < S.MATCHED and missed[0] == made[0]["name"]
    assert events[1]["scope"] == ""


def test_real_v5e_ops_find_their_instructions():
    """Op names of a TPU v5e trace (no metadata, operand shapes printed)
    against the compiled step's lines for the same instructions."""
    with open(os.path.join(DATA, "trace_v5e_slice.json")) as f:
        got = json.load(f)
    events = [{"plane": "/device:TPU:0", "line": "XLA Ops", "name": n}
              for n in got["ops"]]
    assert S.attach_scopes(events, "\n".join(got["module"])) == (1.0, [])
    scope = {e["name"].split(" ", 1)[0]: S.components(e["scope"])
             for e in events}
    assert {"trainable_layers", "channel_mix"} <= scope["%masked_dw.70"]
    assert "update" in scope["%fused_block_opt.9"]
    assert "frozen_layers" in scope["%while.25"]
    assert scope["%copy-start.120"] == {""}


@pytest.mark.parametrize("text,sig", [
    ("%fusion.632 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion(bf16[6,2048]{1,0} "
     "%get-tuple-element.2135, s32[]{:T(128)} %g.1), kind=kOutput",
     "%fusion.632 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion"),
    ("%fusion.632 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion("
     "%get-tuple-element.2135, %g.1), kind=kOutput, metadata={op_name=\"a\"}",
     "%fusion.632 = bf16[2,2048]{1,0:T(8,128)(2,1)} fusion"),
    ("%while.5 = (s32[], f32[4,64]{1,0}) while((s32[], f32[4,64]{1,0}) "
     "%tuple.2), body=%b", "%while.5 = (s32[], f32[4,64]{1,0}) while"),
])
def test_trace_and_module_print_one_signature(text, sig):
    """The trace prints operand shapes, a compiled module's text does not."""
    assert S.signature(text) == sig


@pytest.mark.parametrize("scope,names", [
    ("jit(train_step)/transpose(jvp(trainable_layers))/while/body/"
     "closed_call/checkpoint/rematted_computation/token_mix/tanh",
     {"trainable_layers", "token_mix"}),
    ("jit(train_step)/jvp(frozen_layers)/while/body/closed_call/"
     "checkpoint/channel_mix/dot_general", {"frozen_layers", "channel_mix"}),
    ("jit(train_step)/update/jit(put_along_axis)/scatter", {"update"}),
    ("jit(train_step)/jvp()/dynamic_update_slice", set()),
    ("", set()),
])
def test_scope_path_components(scope, names):
    assert S.components(scope) & set(S.NAMES) == names


def _reader(name):
    path = os.path.join(ROOT, "bench", "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("reader", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def trace_on_disk(tmp_path, monkeypatch):
    """A run's trace file under a checkout in tmp_path, whose events are
    the given ones, of a program whose HLO text is the given one."""
    now = {"events": None, "hlo": None, "at": 0}

    def put(events, hlo):
        d = tmp_path / ".bench_trace" / CELL / "plugins"
        d.mkdir(parents=True, exist_ok=True)
        now["at"] += 1
        (d / "t.xplane.pb").write_bytes(b"")
        os.utime(d / "t.xplane.pb", ns=(now["at"], now["at"]))
        now.update(events=events, hlo=hlo)

    monkeypatch.setattr(S, "ROOT", str(tmp_path))
    monkeypatch.setattr(S.TRD, "load_events",
                        lambda _d: _trace_form(now["events"]))
    monkeypatch.setattr(S, "_READ", {})
    return put, lambda window_s=50e-6: {
        "trace": {"steps": 2, "window_s": window_s},
        "program_text": now["hlo"]}


@pytest.mark.parametrize("metric,us", [
    ("frozen_fwd_ms.train", 20), ("suffix_ms.train", 10),
    ("head_loss_ms.train", 3), ("token_mix_ms.train", 16),
    ("update_ms.train", 4)])
def test_readers_give_device_ms_per_step(trace_on_disk, metric, us):
    put, ctx = trace_on_disk
    put(_events(), _hlo(_events()))
    assert _reader(metric).read(ctx()) == pytest.approx(us * 1e-3 / 2)


def test_readers_read_nothing_without_scopes_or_of_another_window(
        trace_on_disk):
    put, ctx = trace_on_disk
    bare = [dict(e, scope="") for e in _events()]
    put(bare, _hlo(bare))                  # a program without the scopes
    for metric in ("frozen_fwd_ms.train", "update_ms.train"):
        assert _reader(metric).read(ctx()) is None
    put(_events(), _hlo(_events()).replace("f32[4,64]", "f32[8,64]"))
    assert _reader("suffix_ms.train").read(ctx()) is None  # another program
    put(_events(), _hlo(_events()))
    assert _reader("suffix_ms.train").read(ctx(40e-6)) is None
    assert _reader("suffix_ms.train").read(ctx()) == pytest.approx(5e-3)
    no_text = dict(ctx(), program_text=None)
    assert _reader("suffix_ms.train").read(no_text) is None


def test_step_ms_reads_any_scope_name(trace_on_disk):
    """A scope outside the train step's eight, such as an expert layer's
    `experts`, reads its ops' self time; a name no op carries reads
    nothing."""
    put, ctx = trace_on_disk
    made = [dict(e, scope=e["scope"].replace("channel_mix",
                                             "channel_mix/experts"))
            if "scope" in e else e for e in _events()]
    put(made, _hlo(made))
    assert "experts" not in S.NAMES
    assert S.step_ms(ctx(), ("experts",)) == pytest.approx(8e-3 / 2)
    assert S.step_ms(ctx(), ("experts",)) == S.step_ms(ctx(),
                                                        ("channel_mix",))
    assert S.step_ms(ctx(), ("router",)) is None

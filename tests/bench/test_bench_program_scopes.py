"""The compiled train step of each training cell, at the tiny size, names
its layers: every op carries its `jax.named_scope` path in its `op_name`
metadata. bench/trace_scopes.py reads it from the text of the step the run
compiled and ran (`compiled.as_text()`), which the job hands to the
readers, so that text has to carry it."""
import importlib
import re

import pytest

from bench import trace_scopes as S
from bench.jobs import train as J
from bench_tiny import cells, tiny

MATMUL = re.compile(r"= \S+ (?:dot|convolution)\(")
OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')


@pytest.mark.parametrize("cell", cells("train"))
def test_compiled_step_names_its_layers(cell):
    t = tiny(cell)
    job = J.Job({"load_reference": lambda name: importlib.import_module(
        f"bench.reference.{name}")}, t["entry"], t["mix"])
    assert job.tc.compact_grads
    job.build(2**31 + 7)
    text = job.step.as_text()
    ops = S.instructions(text)
    assert ops

    names = [n for _head, n in ops.values()]
    seen = set().union(*(S.components(n) for n in names))
    assert set(S.NAMES) <= seen
    assert any("/transpose(jvp(trainable_layers))/" in n for n in names)
    for stack in ("frozen_layers", "trainable_layers"):
        assert any({stack, "token_mix"} <= S.components(n) for n in names)
    matmuls = [m.group(1) for line in text.splitlines()
               if MATMUL.search(line) for m in [OP_NAME.search(line)] if m]
    assert matmuls
    scoped = [n for n in matmuls if S.components(n) & set(S.TOP)]
    assert len(scoped) >= 0.9 * len(matmuls)

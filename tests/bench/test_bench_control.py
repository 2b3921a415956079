"""The control and the planted faults of bench/calibrate.py at a tiny size
on the CPU: the reference computed with fp8 matmul operands, the
reference on half of each batch, and a served token altered, each read
against the float32 reference in the program's place, come out far from
the sound program's readings (which, in float32 here, are at rounding)."""
import pytest

from bench_tiny import cells, tiny


def _job(cell):
    import importlib

    from bench import run as R
    ov = tiny(cell)
    kind = importlib.import_module(f"bench.jobs.{ov['mix']['kind']}")
    ctx = {"t0": R.T0, "log": R.log, "load_reference": lambda n:
           importlib.import_module(f"bench.reference.{n}")}
    return kind.Job(ctx, ov["entry"], ov["mix"]), ov["limits"]


@pytest.mark.parametrize("cell", cells("train"))
def test_train_control_and_half_batch_read_far_above_the_program(cell):
    from bench import calibrate as CAL
    job, _limits = _job(cell)
    out = CAL.readings_for_seed(job, 2**31 + 5, control=True)
    prog = max(out["program"][k] for k in ("loss_gap", "grad_gap",
                                           "change_gap"))
    assert prog < 1e-4
    fp8 = max(out["fp8"][k] for k in ("loss_gap", "grad_gap", "change_gap"))
    assert fp8 > 100 * prog and fp8 > 1e-3
    assert out["half_batch"]["grad_gap"] > 0.1

"""A whole run of each training cell at a tiny size on the CPU, with the
harness's look for a chip skipped: a sound run comes out correct, and a run
with the timed path broken underneath comes out not correct, once for each
fault a training cell on one chip can have."""
import jax
import pytest

from bench_tiny import cells, run_cell


def _plain(job):
    if not hasattr(job, "_plain_step"):
        job._plain_step = jax.jit(job.raw_step)
    return job._plain_step


def state_unchanged(job, batch):
    """The step computes its loss and returns the state it was given."""
    _, metrics = _plain(job)(job.state, batch)
    return metrics["loss"]


def half_batch(job, batch):
    """Half of the batch's rows left out, the mean taken over the rest."""
    half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
    job.state, metrics = _plain(job)(job.state, half)
    return metrics["loss"]


@pytest.mark.parametrize("cell", cells("train"))
@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch],
                         ids=["sound", "state_unchanged", "half_batch"])
def test_fault_makes_the_run_incorrect(cell, fault):
    out = run_cell(cell, seed=2**31 + 77, fault=fault)
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"


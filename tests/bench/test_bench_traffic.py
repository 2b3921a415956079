import numpy as np

from bench import generate


def _mix():
    return {"batch": 3, "seq": 17,
            "data": {"generator": "markov_lm", "table_width": 64,
                     "noise": 0.05}}


def test_same_seed_same_rows_and_new_seed_new_rows():
    big = 2**31 + 12345
    a = generate.markov_lm(_mix(), 1000, big, 4)
    b = generate.markov_lm(_mix(), 1000, big, 4)
    c = generate.markov_lm(_mix(), 1000, big + 1, 4)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x["tokens"], y["tokens"])
        np.testing.assert_array_equal(x["labels"], y["labels"])
    assert any((x["tokens"] != z["tokens"]).any() for x, z in zip(a, c))


def test_rows_shift_by_one_and_all_differ():
    batches = generate.markov_lm(_mix(), 1000, 7, 5)
    for bt in batches:
        assert bt["tokens"].shape == (3, 17) and bt["tokens"].dtype == np.int32
        np.testing.assert_array_equal(bt["tokens"][:, 1:], bt["labels"][:, :-1])
        assert bt["tokens"].min() >= 0 and bt["tokens"].max() < 1000
    s = generate.summary(batches)
    assert s["distinct_rows"] == 15

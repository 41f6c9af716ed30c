"""The same seed gives the same traffic; another seed gives other traffic."""
import numpy as np

from harness import traffic

LOOP = {"rate_per_s": 20.0, "prompt_median": 32, "prompt_sigma": 0.8, "prompt_min": 4,
        "prompt_max": 128, "zipf_a": 1.0, "arrival_seed": 1}
BIG = 2**33 + 17  # wider than 32 bits


def test_open_loop_repeats_per_seed_and_keeps_the_work():
    a = traffic.open_loop(BIG, LOOP, 5.0, 500)
    b = traffic.open_loop(BIG, LOOP, 5.0, 500)
    c = traffic.open_loop(BIG + 1, LOOP, 5.0, 500)
    assert np.array_equal(a["due_s"], b["due_s"])
    assert all(np.array_equal(x, y) for x, y in zip(a["prompts"], b["prompts"]))
    # another seed: the same arrivals, the same lengths in another order, other tokens
    assert np.array_equal(a["due_s"], c["due_s"])
    assert sorted(map(len, a["prompts"])) == sorted(map(len, c["prompts"]))
    assert list(map(len, a["prompts"])) != list(map(len, c["prompts"]))
    # another arrival_seed: the same gaps in another order
    d = traffic.open_loop(BIG, {**LOOP, "arrival_seed": 2}, 5.0, 500)
    assert not np.array_equal(a["due_s"], d["due_s"])
    assert np.allclose(np.sort(np.diff(a["due_s"])), np.sort(np.diff(d["due_s"])))
    n = len(a["prompts"])
    assert n == 100 and a["due_s"][0] == 0.0 and a["due_s"][-1] < 5.0
    assert min(map(len, a["prompts"])) >= 4 and max(map(len, a["prompts"])) <= 128

"""The generators: the seed permutes, it does not resize."""

import json
import os

import pytest

from conftest import BENCH
from rtbench import gen


def traffic(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("seconds", [10, 45])
def test_open_loop_multiset_is_the_same_for_every_seed(seconds):
    t = traffic("serve-chat")
    a = gen.open_loop_plan(t, 1, seconds)
    b = gen.open_loop_plan(t, 2 ** 31 + 12345, seconds)
    for part in ("ramp", "window"):
        pairs = [sorted((r["prompt_tokens"], r["max_tokens"])
                        for r in plan[part]) for plan in (a, b)]
        assert pairs[0] == pairs[1]          # the same set of requests
        assert [r["prompt_tokens"] for r in a[part]] != \
            [r["prompt_tokens"] for r in b[part]]    # in another order
    assert len(a["window"]) == round(t["rate_per_s"] * seconds)


def test_open_loop_gaps_are_one_multiset_and_fill_the_schedule():
    t = traffic("serve-chat")
    plans = [gen.open_loop_plan(t, s, 45)["window"] for s in (3, 4)]
    gaps = []
    for reqs in plans:
        due = [r["due_s"] for r in reqs]
        assert due == sorted(due) and 0 < due[0] and due[-1] < 45
        gaps.append(sorted(round(b - a, 9)
                           for a, b in zip([0.0] + due, due)))
    assert gaps[0] == pytest.approx(gaps[1])
    # n gaps at the cell's rate fill the window up to half a mean gap.
    n = len(plans[0])
    assert plans[0][-1]["due_s"] == pytest.approx(45 * n / (n + 0.5))


def test_lengths_stay_inside_the_traffic_files_limits():
    for name in ("serve-chat", "serve-docqa"):
        t = traffic(name)
        reqs = (gen.open_loop_plan(t, 9, 45)["window"]
                if t["kind"] == "open_loop"
                else gen.closed_loop_plan(t, 9, 45)["requests"])
        for r in reqs:
            assert t["prompt_tokens"]["min"] <= r["prompt_tokens"] \
                <= t["prompt_tokens"]["max"]
            assert t["max_tokens"]["min"] <= r["max_tokens"] \
                <= t["max_tokens"]["max"]
            # prompt + answer + a chained burst fit the engine's positions
            assert r["prompt_tokens"] + r["max_tokens"] \
                <= t["engine"]["max_seq_len"]


def test_closed_loop_cycles_are_one_set_each_in_the_seeds_order():
    t = traffic("serve-docqa")
    n = t["cycle_requests"]
    reqs = gen.closed_loop_plan(t, 5, 51)["requests"]
    other = gen.closed_loop_plan(t, 6, 51)["requests"]
    assert len(reqs) % n == 0 and len(reqs) >= 2 * n

    def pairs(rs):
        return sorted((r["prompt_tokens"], r["max_tokens"]) for r in rs)

    first = pairs(reqs[:n])
    for c in range(len(reqs) // n):
        assert pairs(reqs[c * n:(c + 1) * n]) == first
    assert pairs(other[:n]) == first
    assert [r["prompt_tokens"] for r in other[:n]] != \
        [r["prompt_tokens"] for r in reqs[:n]]
    assert len({r["index"] for r in reqs}) == len(reqs)
    # enough requests for ramp, window and drain at 6 requests/s
    assert len(reqs) >= 51 * t["max_requests_per_s"]


def test_prompts_are_exact_unique_from_the_first_token_and_seeded():
    a = gen.prompt_ids(7, 1000, 300, 32768)
    assert len(a) == 300 and a == gen.prompt_ids(7, 1000, 300, 32768)
    assert a != gen.prompt_ids(8, 1000, 300, 32768)
    assert min(a) >= 259 and max(a) < 32768
    firsts = {gen.prompt_ids(7, i, 4, 32768)[0] for i in range(1000, 1400)}
    assert len(firsts) == 400


def test_quantiles_of_a_lognormal_have_its_median():
    xs = gen.quantile_multiset(
        {"kind": "lognormal", "median": 256, "sigma": 0.9}, 101)
    assert xs[50] == pytest.approx(256)
    assert xs == sorted(xs)


def test_train_batch_seed_takes_large_seeds_and_negative_steps():
    import numpy as np

    for seed in (0, 2 ** 31 + 7, 2 ** 33):
        for step in (-2, -1, 0, 5):
            np.random.default_rng(gen.train_batch_seed(seed, step))
    assert gen.train_batch_seed(2 ** 33, 1) != gen.train_batch_seed(0, 1)


# ------------------------------------------------ the serve plans, pinned
PINNED = {   # sha256 of json.dumps(plan, sort_keys=True) at 51 s: chat and
    # docqa as PR 25's generator made them, reason as PR 26 added it. A
    # change to the generator that moves a committed cell's plan shows here.
    ("serve-chat", 1):
        "02b637e4691bcdb9e0d0b6f3d3e08272101193dab84ff2ebaf0905a24d5e39c6",
    ("serve-chat", 2147483700):
        "468b443859029a1140d28ffe8cc9ab7b8078d3fc1e5b7ada250a5977e1c850c2",
    ("serve-docqa", 1):
        "e3ad0ae7e3517d51cd25bde43202480439dc632467f3bdab2dd8a77df83e1d01",
    ("serve-docqa", 2147483700):
        "7ab5425bcb01ae1c91517a914360537636633153d1feff56631e6266e66d6c34",
    ("serve-reason", 1): 
        "2a26abc4a5f5f8e7a8884607d33832ebaf4c44f54e90d6232db896ccb6bd36b1",
    ("serve-reason", 2147483700): 
        "bca6ced6c69dbb00daacf97bd9aeddc03f738a24f90a8624fa0876e9f7d45ece",
}


def _digest(name, seed):
    import hashlib

    t = traffic(name)
    plan = (gen.open_loop_plan if t["kind"] == "open_loop"
            else gen.closed_loop_plan)(t, seed, 51)
    return hashlib.sha256(
        json.dumps(plan, sort_keys=True).encode()).hexdigest()


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_a_committed_serve_plan_is_what_it_was(name, seed):
    assert _digest(name, seed) == PINNED[name, seed]

"""The traffic generator: every seed offers the same work."""

import collections
import json
import os

import pytest

from benchmarks.lib import traffic
from benchmarks.tests.helpers import BENCH

MIXES = ["gen_heavy_closed12", "prompt_heavy_closed8"]


def load(name):
    with open(os.path.join(BENCH, "traffic", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("name", MIXES)
def test_every_seed_offers_the_same_multiset_in_another_order(name):
    mix = load(name)
    base = collections.Counter(traffic.multiset(mix))
    orders = []
    for seed in (0, 7, 2**31 + 12345, 2**33 + 1):
        plan = traffic.ClosedLoopPlan(mix, seed, vocab=50257)
        assert collections.Counter(plan.order) == base
        orders.append(tuple(plan.order))
    assert len(set(orders)) == len(orders)


@pytest.mark.parametrize("name", MIXES)
def test_multiset_covers_the_ranges_evenly(name):
    mix = load(name)
    pairs = traffic.multiset(mix)
    prompts = sorted(p for p, _ in pairs)
    news = sorted(n for _, n in pairs)
    assert prompts[0] == mix["prompt_len"][0]
    assert prompts[-1] == mix["prompt_len"][1]
    assert news[0] == mix["new_tokens"][0] and news[-1] == mix["new_tokens"][1]
    steps = {b - a for a, b in zip(prompts, prompts[1:])}
    assert max(steps) - min(steps) <= 1
    assert max(p + n for p, n in pairs) <= 1024


@pytest.mark.parametrize("name", MIXES)
def test_the_stagger_leaves_no_two_clients_in_phase(name):
    mix = load(name)
    plan = traffic.ClosedLoopPlan(mix, 3, vocab=50257)
    cut = [new for _, new in plan.ramp]
    assert len(cut) == mix["clients"]
    assert len(set(cut)) == len(cut), cut
    assert max(cut) == mix["new_tokens"][0]


def test_same_seed_same_inputs_and_the_cursor_goes_round():
    mix = load("prompt_heavy_closed8")
    a = traffic.ClosedLoopPlan(mix, 11, vocab=100)
    b = traffic.ClosedLoopPlan(mix, 11, vocab=100)
    assert a.order == b.order
    assert (a.prompt(5, 40) == b.prompt(5, 40)).all()
    assert (a.prompt(-1, 40) != a.prompt(5, 40)).any()
    seen = [a.next_request() for _ in range(2 * len(a.order))]
    assert [i for i, _ in seen] == list(range(8, 8 + 2 * len(a.order)))
    assert seen[0][1] == a.order[8]
    assert seen[len(a.order)][1] == seen[0][1]


def test_train_data_is_made_from_the_seed_with_rows_that_differ():
    mix = {"batch": 4, "host_batches": 2}
    x, y = traffic.train_data(mix, 2**31 + 5, img=8, classes=10)
    x2, _ = traffic.train_data(mix, 2**31 + 5, img=8, classes=10)
    assert x.shape == (8, 3, 8, 8) and (x == x2).all()
    assert y.min() >= 1 and y.max() <= 10
    assert len({row.tobytes() for row in x}) == 8

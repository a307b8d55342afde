"""The traffic generator: the same requests for the same seed, the same
work in another order for another."""

import json
import os

import pytest

import benchtiny
from benchmark import traffic

def chat_mix():
    path = os.path.join(benchtiny.ROOT, "benchmark", "traffic",
                        "serve-chat.json")
    with open(path) as f:
        return json.load(f)


@pytest.mark.parametrize("seed", [0, 7, 2 ** 31 + 12345])
def test_same_seed_same_requests(seed):
    a = traffic.requests(chat_mix(), seed, 60.0, 50257)
    b = traffic.requests(chat_mix(), seed, 60.0, 50257)
    assert a == b and len(a) > 10


def test_another_seed_is_the_same_work_in_another_order():
    mix = chat_mix()
    a = traffic.requests(mix, 1, 60.0, 50257)
    b = traffic.requests(mix, 2, 60.0, 50257)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    shape = lambda rs: sorted(  # noqa: E731
        (len(r["prompt"]), r["max_new_tokens"]) for r in rs)
    assert shape(a) == shape(b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    assert a[-1]["due_s"] == pytest.approx(b[-1]["due_s"])
    assert [r["due_s"] for r in a] != [r["due_s"] for r in b]


def test_requests_keep_to_the_mix():
    mix = chat_mix()
    reqs = traffic.requests(mix, 3, 200.0, 50257)
    dues = [r["due_s"] for r in reqs]
    assert dues == sorted(dues) and dues[-1] < 200.0
    assert len(reqs) == pytest.approx(mix["rate_rps"] * 200.0, rel=0.35)
    for r in reqs:
        assert mix["prompt"]["min"] <= len(r["prompt"]) <= mix["prompt"]["max"]
        assert 1 <= r["max_new_tokens"] <= mix["output"]["max"]
        assert len(r["prompt"]) + r["max_new_tokens"] <= mix["max_total"]
        assert all(0 <= t < 50257 for t in r["prompt"])


def test_uniform_arrivals_are_evenly_spaced():
    mix = dict(chat_mix(), arrivals="uniform", rate_rps=5.0)
    reqs = traffic.requests(mix, 3, 4.0, 1000)
    gaps = [b["due_s"] - a["due_s"] for a, b in zip(reqs, reqs[1:])]
    assert all(g == pytest.approx(0.2) for g in gaps)

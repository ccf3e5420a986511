"""The traffic generator: deterministic per seed, true to its mix file, and
the same schedule of sizes and arrivals for every seed."""

from __future__ import annotations

import collections

import numpy as np
import pytest

from bench import loadgen, spec

OPEN = {
    "loop": "open", "rate_per_s": 5.0, "shape_seed": 3, "drain_s": 10,
    "shared_prefix": {"count": 3, "zipf_s": 1.0,
                      "length": {"median": 300, "sigma": 0.7, "min": 100, "max": 600}},
    "prompt": {"median": 50, "sigma": 0.8, "min": 16, "max": 200},
    "output": {"median": 20, "sigma": 0.6, "min": 4, "max": 64},
}
CLOSED = {
    "loop": "closed", "clients": 3, "requests_per_client": 5, "shape_seed": 4,
    "drain_s": 10,
    "prompt": {"median": 100, "sigma": 0.5, "min": 64, "max": 512},
    "output": {"median": 300, "sigma": 0.6, "min": 128, "max": 1024},
}


def _key(specs):
    return [(s.prompt.tolist(), s.max_new_tokens, s.due, s.client) for s in specs]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_same_seed_same_requests(mix):
    a = loadgen.generate(mix, 2**40 + 7, 30.0, 1000)
    b = loadgen.generate(mix, 2**40 + 7, 30.0, 1000)
    c = loadgen.generate(mix, 2**40 + 8, 30.0, 1000)
    assert _key(a) == _key(b)
    assert _key(a) != _key(c)


def test_every_seed_has_the_same_arrivals():
    due = [[s.due for s in loadgen.generate(OPEN, seed, 30.0, 1000)] for seed in (1, 2)]
    assert due[0] == due[1]


def test_a_shorter_multiset_is_the_first_part_of_a_longer():
    """What a rate sweep's largest multiset warms up covers the others."""
    short, long = loadgen.shapes(OPEN, 12.0), loadgen.shapes(OPEN, 30.0)
    assert short == long[:len(short)]


@pytest.mark.parametrize("mix", [OPEN, CLOSED], ids=["open", "closed"])
def test_every_seed_serves_the_same_sizes(mix):
    """The seed draws the tokens; each request's sizes, system prompt and
    due time are the mix's, in the mix's order."""
    def sizes(seed):
        return [(len(s.prompt), s.max_new_tokens, s.shared_len, s.group, s.due, s.client)
                for s in loadgen.generate(mix, seed, 30.0, 1000)]

    assert sizes(1) == sizes(2) == sizes(3)


def test_open_loop_keeps_to_its_mix():
    specs = loadgen.generate(OPEN, 5, 30.0, 1000)
    assert len(specs) == round(OPEN["rate_per_s"] * 30.0)
    due = [s.due for s in specs]
    assert due == sorted(due) and 0.0 <= due[0] and due[-1] < 30.0
    lengths = loadgen.system_lengths(OPEN)
    assert len(lengths) == 3 and all(100 <= n <= 600 for n in lengths)
    systems: dict[int, list] = {}
    for s in specs:
        user = len(s.prompt) - s.shared_len
        assert OPEN["prompt"]["min"] <= user <= OPEN["prompt"]["max"]
        assert OPEN["output"]["min"] <= s.max_new_tokens <= OPEN["output"]["max"]
        assert s.shared_len == lengths[s.group]
        head = s.prompt[:s.shared_len].tolist()
        assert systems.setdefault(s.group, head) == head   # one prompt per group
        assert s.prompt.min() >= loadgen.FIRST_TOKEN and s.prompt.max() < 1000
    # Zipf s=1: the first-ranked prompt is the most popular
    counts = collections.Counter(s.group for s in specs)
    assert counts[0] == max(counts.values())


def test_closed_loop_gives_each_client_its_queue():
    specs = loadgen.generate(CLOSED, 5, 30.0, 1000)
    assert len(specs) == CLOSED["clients"] * CLOSED["requests_per_client"]
    per = collections.Counter(s.client for s in specs)
    assert set(per.values()) == {CLOSED["requests_per_client"]}
    assert all(s.due is None and s.shared_len == 0 for s in specs)


def test_lognormal_median_and_clip():
    rng = np.random.default_rng(0)
    spec_ = {"median": 200, "sigma": 0.8, "min": 16, "max": 1024}
    x = loadgen.lognormal_lengths(rng, 20000, spec_)
    assert x.min() >= 16 and x.max() <= 1024
    assert abs(np.median(x) - 200) < 10


def test_committed_mix_fits_its_cell():
    mix, max_len = spec.mix("chat_shared"), spec.serving("yi6b.chat-shared")["max_len"]
    longest = max(loadgen.system_lengths(mix)) + mix["prompt"]["max"]
    assert longest + mix["output"]["max"] <= max_len


def test_lengths_are_not_rounded_to_few_values():
    """User turns and system prompts keep the lognormal's spread of
    lengths, not a few chunk-aligned values."""
    mix = spec.mix("chat_shared")
    rows = loadgen.shapes(mix, 51.0)
    assert len({u for _, _, u, _ in rows}) > 0.8 * len(rows)
    assert any(n % 512 for n in loadgen.system_lengths(mix))

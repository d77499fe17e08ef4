"""The traffic generator is deterministic by seed, keeps its clip ranges,
and gives every seed the same sizes and arrival times in the same order,
with other tokens."""
import collections
import json

import numpy as np
import pytest

from chipbench import harness, traffic

CHAT = json.loads(harness.traffic_file("chat").read_text())


def _sizes(sched, phase):
    return collections.Counter((len(r["prompt"]), r["max_new"])
                               for r in sched if r["phase"] == phase)


def test_open_schedule_is_deterministic():
    a = traffic.open_schedule(CHAT, 20, 5, 32000)
    b = traffic.open_schedule(CHAT, 20, 5, 32000)
    assert len(a) == len(b) > 0
    for x, y in zip(a, b):
        assert x["sched"] == y["sched"] and x["max_new"] == y["max_new"]
        assert np.array_equal(x["prompt"], y["prompt"])


@pytest.mark.parametrize("seed", [0, 7, 2**33 + 1])
def test_open_schedule_keeps_clip_ranges(seed):
    sched = traffic.open_schedule(CHAT, 51, seed, 32000)
    for r in sched:
        assert CHAT["prompt"]["min"] <= len(r["prompt"]) <= CHAT["prompt"]["max"]
        assert CHAT["max_new"]["min"] <= r["max_new"] <= CHAT["max_new"]["max"]
        assert r["prompt"].min() >= 0 and r["prompt"].max() < 32000


def test_seeds_share_sizes_and_arrivals():
    a = traffic.open_schedule(CHAT, 51, 1, 32000)
    b = traffic.open_schedule(CHAT, 51, 2, 32000)
    assert [r["sched"] for r in a] == [r["sched"] for r in b]
    for phase in ("warm", "window", "drain"):
        assert _sizes(a, phase) == _sizes(b, phase)
    assert [len(r["prompt"]) for r in a] == [len(r["prompt"]) for r in b]
    assert [r["max_new"] for r in a] == [r["max_new"] for r in b]
    assert not all(np.array_equal(x["prompt"][:16], y["prompt"][:16])
                   for x, y in zip(a, b))


def test_window_rate_matches_the_mix():
    sched = traffic.open_schedule(CHAT, 51, 3, 32000)
    n = sum(r["phase"] == "window" for r in sched)
    lam = CHAT["rate_rps"] * 51
    assert abs(n - lam) < 5 * lam ** 0.5


def test_lengths_clip_and_distributions():
    rng = np.random.default_rng(0)
    x = traffic.lengths({"dist": "lognormal", "median": 512, "sigma": 0.7,
                         "min": 64, "max": 2048}, 4000, rng)
    assert x.min() >= 64 and x.max() <= 2048
    assert 400 < np.median(x) < 640
    u = traffic.lengths({"dist": "uniform", "min": 10, "max": 20}, 500, rng)
    assert u.min() == 10 and u.max() == 20
    with pytest.raises(ValueError):
        traffic.lengths({"dist": "pareto", "min": 1, "max": 2}, 3, rng)


DOCQA = {"shape_seed": 3, "documents": 4, "block": 32, "zipf_s": 1.0,
         "doc_len": {"dist": "uniform", "min": 100, "max": 200},
         "question": {"dist": "uniform", "min": 8, "max": 16},
         "max_new": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                     "min": 2, "max": 12}}


def test_docqa_blocks_share_their_mix():
    a = traffic.docqa(DOCQA, 1, 1000, 3)
    b = traffic.docqa(DOCQA, 2, 1000, 3)
    assert [len(d) for d in a["documents"]] == [len(d) for d in b["documents"]]
    for blk in range(3):
        sl = slice(32 * blk, 32 * (blk + 1))
        ca = collections.Counter((r["doc"], len(r["prompt"]), r["max_new"])
                                 for r in a["requests"][sl])
        cb = collections.Counter((r["doc"], len(r["prompt"]), r["max_new"])
                                 for r in b["requests"][sl])
        assert ca == cb
    docs = collections.Counter(r["doc"] for r in a["requests"][:32])
    assert docs[0] > docs[1] > docs[3]          # Zipf: 1/k
    for r in a["requests"]:
        doc = a["documents"][r["doc"]]
        assert np.array_equal(r["prompt"][:len(doc)], doc)
        assert 8 <= len(r["prompt"]) - len(doc) <= 16


def test_zipf_counts_fill_the_block():
    c = traffic.zipf_counts(4, 1.0, 64)
    assert c.sum() == 64 and list(c) == sorted(c, reverse=True)


def test_docqa_order_is_the_same_for_every_seed():
    a = traffic.docqa(DOCQA, 1, 1000, 2)
    b = traffic.docqa(DOCQA, 2**33 + 5, 1000, 2)
    key = [(r["doc"], len(r["prompt"]), r["max_new"]) for r in a["requests"]]
    assert key == [(r["doc"], len(r["prompt"]), r["max_new"])
                   for r in b["requests"]]
    assert key[:32] == key[32:]
    assert [r["doc"] for r in a["requests"][:32]] != sorted(
        r["doc"] for r in a["requests"][:32])  # the documents interleave
    assert not np.array_equal(a["requests"][0]["prompt"],
                              b["requests"][0]["prompt"])


def test_chat_blocks_keep_the_load_profile():
    a = traffic.open_schedule(CHAT, 51, 1, 32000)
    b = traffic.open_schedule(CHAT, 51, 2, 32000)
    wa = [(r["sched"], len(r["prompt"]), r["max_new"]) for r in a
          if r["phase"] == "window"]
    wb = [(r["sched"], len(r["prompt"]), r["max_new"]) for r in b
          if r["phase"] == "window"]
    assert wa == wb

"""The traffic generator: deterministic per seed, the same work on every
seed, and the distributions its mix files state."""
from statistics import NormalDist

import numpy as np
import pytest

from harness import traffic as tr
from harness.spec import BENCH, load_json

CHAT = load_json(BENCH / "traffic" / "chat-poisson.json")
BATCH = load_json(BENCH / "traffic" / "decode-batch16.json")


def test_open_schedule_deterministic_per_seed():
    a = tr.open_schedule(CHAT, 3_000_000_011, 30)
    b = tr.open_schedule(CHAT, 3_000_000_011, 30)
    c = tr.open_schedule(CHAT, 3_000_000_012, 30)
    assert a == b
    assert [r.prompt for r in a] != [r.prompt for r in c]


def test_open_loop_order_is_the_mix_s_own():
    a = tr.open_schedule(CHAT, 1, 30)
    b = tr.open_schedule(CHAT, 2, 30)
    assert [(r.due_s, len(r.prompt), r.max_new) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new) for r in b]
    assert [r.prompt for r in a] != [r.prompt for r in b]


def test_every_seed_gets_the_same_work_in_another_order():
    free = {k: v for k, v in CHAT.items() if k != "order_seed"}
    a = tr.open_schedule(free, 1, 30)
    b = tr.open_schedule(free, 2, 30)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new for r in a) == sorted(r.max_new for r in b)
    assert [len(r.prompt) for r in a] != [len(r.prompt) for r in b]
    ga = tr.gaps(CHAT, 90, np.random.default_rng(1))
    gb = tr.gaps(CHAT, 90, np.random.default_rng(2))
    assert np.array_equal(np.sort(ga), np.sort(gb))


def test_open_schedule_covers_the_window_at_the_rate():
    s = tr.open_schedule(CHAT, 5, 30)
    assert len(s) == round(CHAT["rate_rps"] * 30)
    due = [r.due_s for r in s]
    assert due[0] == 0.0 and all(0 <= t < 30 for t in due)
    assert due == sorted(due)
    gaps = np.diff(due)
    # exponential gaps: coefficient of variation near 1
    assert 0.8 < gaps.std() / gaps.mean() < 1.2


def test_lognormal_lengths_follow_their_quantiles():
    n = 400
    x = np.sort(tr.lengths(CHAT["prompt"], n, np.random.default_rng(0)))
    d = CHAT["prompt"]
    for q in (0.25, 0.5, 0.75):
        want = d["median"] * np.exp(d["sigma"] * NormalDist().inv_cdf(q))
        want = min(max(want, d["min"]), d["max"])
        assert abs(np.quantile(x, q) - want) <= 0.03 * want + 1
    assert x.min() >= d["min"] and x.max() <= d["max"]
    ids = [t for r in tr.open_schedule(CHAT, 9, 30) for t in r.prompt]
    assert min(ids) >= CHAT["ids"]["low"] and max(ids) < CHAT["ids"]["high"]


def test_uniform_lengths_cover_their_range():
    x = tr.lengths(BATCH["output"], 65, np.random.default_rng(1))
    assert x.min() == BATCH["output"]["min"]
    assert x.max() == BATCH["output"]["max"]
    assert abs(x.mean() - 352) < 2


def test_closed_streams_cycle_one_pool_per_client():
    s1 = tr.closed_streams(BATCH, 4, 16)
    s2 = tr.closed_streams(BATCH, 5, 16)
    assert len(s1) == BATCH["clients"] and all(len(s) == 16 for s in s1)
    for a, b in zip(s1, s2):
        assert sorted(r.max_new for r in a[:8]) == \
            sorted(r.max_new for r in b[:8])
    assert s1 == tr.closed_streams(BATCH, 4, 16)


def test_bad_mix_is_refused():
    with pytest.raises(ValueError):
        tr.gaps(dict(CHAT, arrival="bursty"), 4, np.random.default_rng(0))


@pytest.mark.parametrize("extra", [{"greedy": False}, {"period": 4},
                                   {"drain_timeout_s": 60},
                                   {"loop": "half-open"}])
def test_a_knob_nothing_reads_is_refused(extra):
    tr.check_mix(CHAT)
    tr.check_mix(BATCH)
    with pytest.raises(ValueError):
        tr.check_mix(dict(CHAT, **extra))

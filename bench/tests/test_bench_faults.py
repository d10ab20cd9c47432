"""A whole run of the harness on the CPU at a tiny size, without the
look for a chip: sound, it comes out correct; with the timed path broken
underneath, ``correct`` comes out false.  The faults a serving cell can
have: a decode step that returns its KV state unchanged, and a token
altered where the decode window produces it.  (Leaving half of a batch
out is a training fault; an exchange between chips needs a ring cell.)
"""
import time

import pytest

from harness import cell as C
from harness.spec import BENCH, Cell, load_benchmark, load_family, load_json

FIX = BENCH / "tests" / "fixtures"
# the tiny model's sound runs read widest gaps of 0 to 0.06 in bf16
# over 44 tokens; a broken decode reads whole logits
LIMIT = 0.25


def tiny_cell(traffic: str) -> Cell:
    bm = load_benchmark()
    conf = load_json(FIX / "tiny-opt.json")
    return Cell(name="tiny", chips=1, config=conf,
                traffic=load_json(FIX / f"{traffic}.json"),
                limit={"served_logit_gap": LIMIT},
                end_to_end=bm["end_to_end"], per_layer=bm["per_layer"],
                family=load_family(conf["family"]))


def kv_unchanged(engine):
    """Each decode window hands back the pool it was given: the new
    tokens' K and V are never written."""
    orig = engine._window

    def window(S):
        fn = orig(S)

        def broken(params, cache, *rest):
            out = fn(params, cache, *rest)
            return out[:2] + (cache,) + out[3:]
        return broken
    engine._window = window


def token_altered(engine):
    """Slot 0's sampled token is replaced by the next id."""
    orig = engine._window
    V = engine.cfg.vocab_size

    def window(S):
        fn = orig(S)

        def broken(*args):
            out = fn(*args)
            toks = out[0].at[:, 0].set((out[0][:, 0] + 1) % V)
            return (toks,) + out[1:]
        return broken
    engine._window = window


def run(monkeypatch, fault, traffic="tiny-open", seed=3_100_000_007):
    build = C.build

    def broken_build(*a, **k):
        engine, d = build(*a, **k)
        if fault is not None:
            fault(engine)
        return engine, d

    monkeypatch.setattr(C, "build", broken_build)
    return C.run_cell(tiny_cell(traffic), seed, 2.0, False,
                      time.perf_counter(), on_chip=False)


def test_sound_run_is_correct(monkeypatch):
    r = run(monkeypatch, None)
    assert r["correct"], r["checks"]
    assert r["attempted"] == 8 and r["failed"] == 0
    assert r["checks"]["served_logit_gap"][0] <= LIMIT
    assert set(r["metrics"]) == {m["name"] for m in
                                 load_benchmark()["end_to_end"]}
    assert r["metrics"]["tpot_p90_ms.b1"] == r["metrics"]["tpot_p90_ms"]
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", [kv_unchanged, token_altered])
def test_broken_decode_is_not_correct(monkeypatch, fault):
    r = run(monkeypatch, fault)
    assert not r["correct"], r["checks"]
    assert r["checks"]["served_logit_gap"][0] > LIMIT


def test_closed_loop_run_is_correct(monkeypatch):
    r = run(monkeypatch, None, traffic="tiny-closed", seed=11)
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 2 and r["failed"] == 0

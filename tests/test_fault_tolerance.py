"""Serving fault tolerance: chaos injection, drain/rebuild, migration.

Policy units (no model): EWMA straggler flagging with mu0 seeding and
cooldown, heartbeat liveness transitions on a ManualClock, deterministic
exactly-once chaos firing, chaos-spec parsing, the analytic step-time
prior, and the scheduler's structured admission rejection.

End-to-end (tiny model): a 2-ring host fleet under injected chaos (ring
failure, stalled window, NaN logits, corrupted pool block) must finish
the trace with every surviving greedy stream bit-identical to the
chaos-off fleet and zero leaked pool blocks; exhausted-retry requests
surface ``failed=True`` + ``error`` instead of an engine crash.
"""
import jax
import pytest

from repro.compiler.mapper import plan_model
from repro.configs import get_config
from repro.core.latency_model import LPU_FPGA, step_time_prior
from repro.models.registry import build_model
from repro.serving.config import EngineConfig
from repro.serving.engine import LPUEngine, MultiRingEngine, Request
from repro.serving.ft import (ChaosEvent, FailureInjector,
                              HeartbeatTracker, ManualClock,
                              StragglerMonitor, parse_chaos)
from repro.serving.kv_cache import BlockPool
from repro.serving.scheduler import Scheduler


@pytest.fixture(scope="module")
def tiny_model():
    cfg = get_config("smollm-135m").reduced()
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    params, _ = model.init(jax.random.PRNGKey(0))
    return model, params


# -- policy units ------------------------------------------------------


def test_straggler_mu0_arms_detection_immediately():
    # without a prior the FIRST sample becomes the baseline — a 2 s
    # first step would be silently normalized; mu0 from the latency
    # model judges it against the expected step time instead
    cold = StragglerMonitor(warmup=0, cooldown=0)
    assert cold.record(1, 2.0) is None          # becomes the baseline
    warm = StragglerMonitor(warmup=0, cooldown=0, mu0=0.1)
    ev = warm.record(1, 2.0)
    assert ev is not None and ev.kind == "straggler"
    assert ev.detail["dt"] == 2.0


def test_straggler_flag_respects_cooldown_and_warmup():
    mon = StragglerMonitor(warmup=3, cooldown=10, mu0=None)
    for s in range(1, 6):
        assert mon.record(s, 0.1) is None       # warmup + steady state
    assert mon.record(6, 2.0) is not None       # outlier flagged
    assert mon.record(7, 2.0) is None           # inside cooldown
    for s in range(8, 17):
        mon.record(s, 0.1)                      # back to steady state
    assert mon.record(17, 3.0) is not None      # cooldown elapsed
    # the flagged outliers were excluded from the EWMA: mu stays near
    # the steady-state mode (the unflagged cooldown sample does count)
    assert mon.mu < 0.3


def test_heartbeat_failure_and_revive_on_manual_clock():
    clk = ManualClock()
    hb = HeartbeatTracker(2, timeout_s=5.0, clock=clk)
    clk.advance(4.0)
    hb.beat(1)
    clk.advance(2.0)                  # worker 0 is now 6 s stale
    assert hb.check() == [0]
    assert hb.check() == []           # reported exactly once
    hb.revive(0)                      # rebuilt: fresh beat, back in rotation
    assert hb.check() == []
    clk.advance(6.0)                  # both stale again
    assert sorted(hb.check()) == [0, 1]


def test_parse_chaos_specs():
    evs = parse_chaos("ring@3,stall@5:1, nan@7 ,corrupt@9:0")
    assert evs == [ChaosEvent("ring", 3, 0), ChaosEvent("stall", 5, 1),
                   ChaosEvent("nan", 7, 0), ChaosEvent("corrupt", 9, 0)]
    assert parse_chaos("") == []
    for bad in ("explode@3", "ring@0", "ring@3:-1", "ring", "@3"):
        with pytest.raises(ValueError):
            parse_chaos(bad)
    # EngineConfig validates the spec at construction, not mid-run
    with pytest.raises(ValueError):
        EngineConfig(chaos="explode@3")


def test_failure_injector_fires_exactly_once_per_ring():
    inj = FailureInjector(chaos=parse_chaos("nan@3,ring@3:1,stall@5"))
    assert inj.fire(1, ring=0) == []
    assert [e.kind for e in inj.fire(3, ring=0)] == ["nan"]
    assert inj.fire(3, ring=0) == []            # never re-fires
    assert [e.kind for e in inj.fire(3, ring=1)] == ["ring"]
    assert [e.kind for e in inj.fire(5, ring=0)] == ["stall"]
    # legacy training-driver contract unchanged
    legacy = FailureInjector(fail_at_steps=[2])
    legacy.maybe_fail(1)
    with pytest.raises(RuntimeError):
        legacy.maybe_fail(2)
    legacy.maybe_fail(2)                        # raises only once


def test_step_time_prior_scales_with_window():
    cfg = get_config("smollm-135m").reduced()
    one = step_time_prior(cfg, 1, LPU_FPGA, kv_len=256)
    assert one > 0
    assert step_time_prior(cfg, 1, LPU_FPGA, kv_len=256,
                           steps_per_sync=4) == pytest.approx(4 * one)
    with pytest.raises(ValueError):
        step_time_prior(cfg, 1, LPU_FPGA, steps_per_sync=0)


def test_scheduler_rejects_never_fitting_request():
    # a request whose RESUME state (prompt + generated) outgrew the pool
    # is popped with a reason, not raised over: the co-tenant behind it
    # in the queue must still admit in the same call
    pool = BlockPool(num_blocks=3, block_size=16)   # 2 allocatable
    sched = Scheduler(slots=2, max_seq=64, pool=pool)
    big = Request(0, list(range(1, 11)), 50)
    big.out = list(range(100, 145))                 # resume needs 4 blocks
    ok = Request(1, [1, 2, 3], 8)
    sched.submit(big)
    sched.submit(ok)
    seq = sched.admit_next()
    assert seq is not None and seq.req is ok
    rej = sched.take_rejected()
    assert len(rej) == 1 and rej[0][0] is big
    assert "blocks" in rej[0][1]
    assert sched.take_rejected() == []              # handed off once


# -- engine + fleet end-to-end -----------------------------------------


def test_engine_surfaces_rejection_as_failed_request(tiny_model):
    model, params = tiny_model
    eng = LPUEngine(model, params, EngineConfig(
        slots=2, max_seq=64, paged=True, block_size=16, num_blocks=3))
    big = Request(7, list(range(1, 11)), 50)
    big.out = list(range(100, 145))     # resume state can never fit
    eng.submit(big)
    eng.submit([1, 2, 3], max_new_tokens=4)
    results = eng.drain()               # must not raise
    assert big.failed and "blocks" in big.error
    assert results[7] == big.out        # partial stream surfaced
    assert len(results[8]) == 4         # co-tenant unaffected
    assert eng.stats.rejected_requests == 1
    assert any(e.kind == "request_rejected" for e in eng.events)


CHAOS_ALL = "ring@2,stall@3:1,nan@5,corrupt@8"


def _fleet(tiny_model, **overrides):
    model, params = tiny_model
    kw = dict(slots=2, max_seq=64, paged=True, block_size=16,
              heartbeat_timeout_s=4.0)
    kw.update(overrides)
    return MultiRingEngine(model, params, None, rings=2,
                           config=EngineConfig(**kw))


def test_fleet_chaos_parity_bit_exact(tiny_model):
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11], [12, 13, 14],
               [15, 16]]
    base = _fleet(tiny_model).generate(prompts, max_new_tokens=8)
    fleet = _fleet(tiny_model, chaos=CHAOS_ALL)
    assert isinstance(fleet._clock, ManualClock)   # chaos => virtual time
    rids = [fleet.submit(p, 8) for p in prompts]
    results = fleet.drain()                        # never raises
    fc = fleet.fleet_counters()
    # the ISSUE's three required faults all fired; corrupt@8 is a
    # best-effort extra (migration may leave ring 0 idle before step 8
    # — its kind has a dedicated test below), hence the >= 3 floor:
    # ring@2, the heartbeat-drained stall, nan@5
    fired = {e.detail["kind"] for eng in fleet.engines
             for e in eng.events if e.kind == "chaos"}
    assert {"ring", "stall", "nan"} <= fired
    assert fc["ring_failures"] >= 3
    assert fc["retries"] >= 1
    assert any(e.kind == "ring_rebuilt" for e in fleet.events)
    survivors = [i for i, r in enumerate(rids) if r not in fleet.failed]
    assert survivors                               # chaos left survivors
    for i in survivors:
        assert results[rids[i]] == base[i], \
            f"survivor {i} diverged after recovery"
    for rid, req in fleet.failed.items():
        assert req.failed and "retries exhausted" in req.error
        assert results[rid] == req.out             # partial stream kept
    assert len(survivors) + len(fleet.failed) == len(prompts)
    for eng in fleet.engines:                      # zero leaked blocks
        eng.check_pool_balanced()


def test_corrupted_pool_block_is_detected_and_recovered(tiny_model):
    # a NaN'd resident KV block must surface through the finite-logits
    # guard on the NEXT decode (never silently poison the stream), and
    # recompute-recovery must restore the bit-exact greedy tokens
    base = _fleet(tiny_model).generate([[1, 2, 3], [4, 5]],
                                       max_new_tokens=8)
    fleet = _fleet(tiny_model, chaos="corrupt@4")
    rids = [fleet.submit(p, 8) for p in [[1, 2, 3], [4, 5]]]
    results = fleet.drain()
    assert fleet.engines[0].stats.ring_failures == 1
    nan_fails = [e for e in fleet.events if e.kind == "ring_failed"]
    assert nan_fails and nan_fails[0].detail["reason"] == "nan_logits"
    assert [results[r] for r in rids] == base
    for eng in fleet.engines:
        eng.check_pool_balanced()


def test_fleet_retry_exhaustion_is_structured(tiny_model):
    # max_migrations=0: the first ring failure's orphans fail in place —
    # structured status, no exception, pool still balanced
    fleet = _fleet(tiny_model, chaos="ring@2", max_migrations=0)
    rids = [fleet.submit(p, 6) for p in [[1, 2, 3], [4, 5]]]
    results = fleet.drain()
    assert len(fleet.failed) >= 1
    for rid, req in fleet.failed.items():
        assert req.failed and "retries exhausted" in req.error
        assert rid in results
    assert fleet.fleet_counters()["migrated_requests"] == 0
    for eng in fleet.engines:
        eng.check_pool_balanced()


def test_fleet_slow_ring_step_does_not_time_out_the_others(tiny_model):
    # one host thread steps the rings in turn: a ring whose step takes
    # longer than the heartbeat timeout (a cold compile) must not get
    # the rings stepped before it drained, which would migrate their
    # requests and recompute their streams
    prompts = [[1, 2, 3], [4, 5], [6, 7, 8, 9], [10, 11]]
    base = _fleet(tiny_model).generate(prompts, max_new_tokens=6)
    model, params = tiny_model
    clk = ManualClock()
    fleet = MultiRingEngine(
        model, params, None, rings=2, clock=clk,
        config=EngineConfig(slots=2, max_seq=64, paged=True, block_size=16,
                            heartbeat_timeout_s=4.0))
    slow = fleet.engines[1]
    step = slow.step

    def compiling_step():
        clk.advance(10.0)
        return step()
    slow.step = compiling_step
    assert fleet.generate(prompts, max_new_tokens=6) == base
    assert not [e for e in fleet.events if e.kind == "ring_failed"]


def test_fleet_idle_ring_heartbeats_while_other_stalls(tiny_model):
    # ONE request: ring 0 serves it and wedges; ring 1 stays idle the
    # whole run.  Idle rings beat for free — only the stalled ring may
    # be drained, and the request still completes after recovery
    fleet = _fleet(tiny_model, chaos="stall@2")
    base = _fleet(tiny_model).generate([[1, 2, 3, 4]], max_new_tokens=6)
    rid = fleet.submit([1, 2, 3, 4], 6)
    results = fleet.drain()
    assert results[rid] == base[0]
    assert fleet.engines[0].stats.ring_failures == 1
    assert fleet.engines[1].stats.ring_failures == 0
    failed_rings = {e.detail["ring"] for e in fleet.events
                    if e.kind == "ring_failed"}
    assert failed_rings == {0}
    assert not fleet.failed

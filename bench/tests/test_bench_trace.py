"""Trace reduction on hand-made events with known answers."""
import pytest

from harness import trace as T

DEV = "/device:TPU:0"


def op(name, s, d, plane=DEV):
    return T.Ev(plane, T.OPS_LINE, name, float(s), float(d))


def mod(name, s, d, plane=DEV):
    return T.Ev(plane, T.MODULES_LINE, name, float(s), float(d))


def host(name, s, d):
    return T.Ev("/host:CPU", "python", name, float(s), float(d))


EVS = [
    host("bench.window_open", 0, 1),
    mod("jit__window_fn", 100, 400),
    op("%while.1", 100, 400),                    # encloses the ops below
    op("fusion.1", 100, 100),
    op("paged_decode_attention", 150, 100),     # overlaps fusion.1
    op("fusion.2", 300, 200),
    host("bench.engine_step", 500, 300),
    mod("jit__window_fn", 800, 100),
    op("paged_decode_attention", 800, 50),
    op("all-reduce.3", 850, 100),
    op("fusion.4", 900, 20),
    host("bench.window_close", 1000, 1),
]


def test_busy_idle_and_kernel_time():
    r = T.reduce(EVS, 0, 1000, 1)
    # busy: the loop [100, 500) + [800, 950) = 550 ns of 1000
    assert r.busy_s == pytest.approx(550e-9)
    assert r.window_s == pytest.approx(1000e-9)
    assert r.ops("paged_decode_attention") == (pytest.approx(150e-9), 2)
    assert r.programs_with("paged_decode") == (pytest.approx(500e-9), 2)
    assert r.programs_with("fusion.2") == (pytest.approx(400e-9), 1)


def test_breakdown_names_gaps_by_host_span():
    b = T.breakdown(EVS, DEV, 0, 1000)
    assert b["device_ops"][0] == ["fusion.2", pytest.approx(200e-9)]
    assert "while.1" not in [n for n, _ in b["device_ops"]]
    assert b["device_ops"][1] == ["paged_decode_attention",
                                  pytest.approx(150e-9)]
    gaps = dict((round(s * 1e9), n) for n, s in b["idle_gaps"])
    assert gaps[300] == "bench.engine_step"        # [500, 800)
    assert gaps[100] == "bench.window_open"        # [0, 100)
    assert gaps[50] == "host.outside_spans"        # [950, 1000)


def test_window_marks_and_plane_count():
    assert T.window_bounds(EVS, "bench.window_open",
                           "bench.window_close") == (0.0, 1000.0)
    with pytest.raises(ValueError):
        T.reduce(EVS, 0, 1000, 4)
    with pytest.raises(ValueError):
        T.window_bounds(EVS[1:], "bench.window_open", "bench.window_close")


def test_short_names():
    assert T.short("%copy.28 = bf16[24,65,32]{4,2,3:T(8,128)} copy(bf16[24]"
                   " %x)") == "copy.28: copy bf16[24,65,32]"
    assert T.short("%paged_decode_attention.9 = bf16[512,1,64]{2,1,0} "
                   "custom-call(s32[16]") == \
        "paged_decode_attention.9: custom-call bf16[512,1,64]"


def test_union_and_clip():
    assert T.union([(5, 7), (1, 3), (2, 4)]) == [(1, 4), (5, 7)]
    assert T.clip([(1, 4), (5, 7)], 3, 6) == [(3, 4), (5, 6)]


def recorded():
    from harness.spec import BENCH, load_json
    d = load_json(BENCH / "tests" / "fixtures" / "trace_decode_opt13.json")
    return [T.Ev(*e) for e in d["events"]]


def test_recorded_decode_windows():
    """Two decode-window programs of OPT-1.3B (24 layers) on a v5e."""
    evs = recorded()
    mods = sorted((e for e in evs if e.line == T.MODULES_LINE),
                  key=lambda e: e.start_ns)
    lo, hi = 0.0, mods[-1].end_ns + 20000
    r = T.reduce(evs, lo, hi, 1)
    # the compiler names the decode program jit__unknown; it is found by
    # the kernel it runs, once per layer per step
    t, n = r.programs_with("paged_decode_attention")
    assert n == 2 and t == pytest.approx(sum(m.dur_ns for m in mods) * 1e-9)
    kt, calls = r.ops("paged_decode_attention")
    assert calls == 2 * 24 and 0 < kt < t
    assert 0.9 < r.busy_s / r.window_s <= 1.0
    names = [n for n, _ in r.breakdown["device_ops"]]
    assert "paged_decode_attention" in names[0]
    assert not any(n.startswith("while") for n in names)
    assert {g for g, _ in r.breakdown["idle_gaps"]} <= {
        "bench.engine_step", "bench.on_token", "host.outside_spans"}


SHARES = ("kernel.paged_attn_roofline", "decode_step.mfu",
          "decode_step.hbm_bw_share")
# what the readers gave on the recorded trace when the counts were
# OPT's own module, before the harness reached them through a family
RECORDED_SHARES = {"kernel.paged_attn_roofline": 6.643746868464454,
                   "decode_step.mfu": 0.34755093856444746,
                   "decode_step.hbm_bw_share": 7.472116649455735}


def recorded_run(family):
    """A run over the recorded trace with 16 live slots for 2 steps,
    counted by ``family``."""
    from harness.spec import BENCH, load_family, load_json
    evs = recorded()
    red = T.reduce(evs, 0.0, max(e.end_ns for e in evs), 1)
    conf = load_json(BENCH / "configs" / "opt-1.3b.json")

    class Run:
        dims = load_family(conf["family"]).dims(conf["model"])
        trace = red
        block = 128
        peaks = load_json(BENCH / "peaks.json")["devices"]["TPU v5 lite"]

        def decode_ctx(self):
            return [[300] * 16, [301] * 16]
    Run.family = family
    return Run()


def test_recorded_roofline_and_step_shares_stay_under_peak():
    from harness.spec import load_family, metric_reader
    run = recorded_run(load_family("opt"))
    for name in SHARES:
        v = metric_reader(name)(run)
        assert 0 < v < 100, (name, v)
        assert v == pytest.approx(RECORDED_SHARES[name], rel=0, abs=1e-9)


@pytest.mark.parametrize("name", SHARES)
def test_share_readers_count_with_the_run_family(name):
    """A family whose operations and bytes are twice OPT's reads twice
    OPT's share on the same trace: the readers take their counts from
    the run's family and from nowhere else."""
    from harness.spec import BENCH, load_family, load_module, metric_reader
    double = load_module(BENCH / "tests" / "fixtures" / "family_double.py",
                         "bench_test_")
    read = metric_reader(name)
    opt = read(recorded_run(load_family("opt")))
    assert read(recorded_run(double)) == pytest.approx(2 * opt, rel=1e-12)


@pytest.mark.parametrize("name", SHARES)
def test_a_family_whose_kernel_never_runs_reads_nothing(name):
    from types import SimpleNamespace

    from harness.spec import load_family, metric_reader
    opt = load_family("opt")
    other = SimpleNamespace(**{k: getattr(opt, k) for k in (
        "step_flops", "step_bytes", "paged_attn_flops", "paged_attn_bytes")},
        DECODE_KERNEL="latent_decode_attention")
    assert metric_reader(name)(recorded_run(other)) is None

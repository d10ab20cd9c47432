"""BENCHMARK.json against its contract, and the harness finding every
configuration, traffic mix, limit and metric reader by name."""
import json
import re
import shutil
import subprocess
import sys

import pytest

from harness import spec

ROOT = spec.ROOT
BM = spec.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def test_benchmark_keys_and_names():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert BM["paths"] == ["bench"]
    assert BM["command"] == ["python3", "bench/run.py"]
    assert 1 <= BM["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BM[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    for m in BM["end_to_end"] + BM["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    for m in BM["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        for w in m.get("workloads", []):
            assert w in {c["name"] for c in BM["workloads"]}
    four = sum(1 for w in BM["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(BM["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_resolves_to_its_files(cell):
    c = spec.resolve_cell(BM, cell, ROOT)
    assert c.config["name"] == next(w["config"] for w in BM["workloads"]
                                    if w["name"] == cell)
    assert c.limit["served_logit_gap"] > 0
    assert {m["name"] for m in c.end_to_end} >= {"setup_s"}
    assert len(c.end_to_end) >= 2 and c.per_layer
    reported = {m["name"] for m in c.end_to_end}
    for m in c.per_layer:
        assert callable(spec.metric_reader(m["name"]))
        assert m["moves"] in reported


def test_config_files_state_their_cut():
    for c in BM["configs"]:
        conf = spec.load_json(ROOT / c["file"])
        assert conf["name"] == c["name"]
        assert (spec.BENCH / "harness" / "families"
                / f"{conf['family']}.py").is_file()
        assert conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        for k in ("assumed", "deployment", "engine", "model"):
            assert k in conf
        for k in c["reduced"]:
            assert not k.endswith(("_dim", "_rank", "_size"))


def test_per_layer_metrics_follow_what_they_move():
    """Without a ``workloads`` key a per-layer metric goes to the cells
    that report the end-to-end metric it moves, and to no other."""
    b1 = spec.resolve_cell(BM, "opt-6.7b-16l.latency-b1", ROOT)
    chat = spec.resolve_cell(BM, "opt-1.3b.chat-poisson", ROOT)
    b1_layers = {m["name"] for m in b1.per_layer}
    chat_layers = {m["name"] for m in chat.per_layer}
    assert "decode_step.hbm_bw_share.b1" in b1_layers
    assert "kernel.paged_attn_roofline" not in b1_layers
    assert "kernel.paged_attn_roofline" in chat_layers
    assert not any(n.endswith(".b1") for n in chat_layers)


def test_a_mix_with_an_unread_key_is_refused(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    path = root / "bench" / "traffic" / "latency-b1.json"
    mix = json.loads(path.read_text())
    path.write_text(json.dumps(dict(mix, greedy=False)))
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(spec.load_benchmark(root),
                          "opt-6.7b-16l.latency-b1", root)


def copy_bench(tmp_path):
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", root / "BENCHMARK.json")
    return root


def set_family(root, config: str, family):
    path = root / "bench" / "configs" / f"{config}.json"
    conf = json.loads(path.read_text())
    if family is None:
        del conf["family"]
    else:
        conf["family"] = family
    path.write_text(json.dumps(conf))


@pytest.mark.parametrize("family", ["no-such-family", "../spec", None])
def test_a_configuration_without_its_family_file_is_refused(tmp_path,
                                                             family):
    """An unknown family, a name that leads out of the families'
    directory, or none at all: refused when the cell is resolved."""
    root = copy_bench(tmp_path)
    set_family(root, "opt-6.7b-16l", family)
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(spec.load_benchmark(root),
                          "opt-6.7b-16l.latency-b1", root)


def test_a_new_family_is_a_new_file(tmp_path):
    """A configuration that names a family added as a file of its own
    gets that module as its cell's family; nothing else changes."""
    root = copy_bench(tmp_path)
    families = root / "bench" / "harness" / "families"
    (families / "scratch-lm.py").write_text(
        'DECODE_KERNEL = "scratch_decode_attention"\n')
    set_family(root, "opt-6.7b-16l", "scratch-lm")
    c = spec.resolve_cell(spec.load_benchmark(root),
                          "opt-6.7b-16l.latency-b1", root)
    assert c.family.DECODE_KERNEL == "scratch_decode_attention"
    assert c.family is spec.load_family("scratch-lm", root / "bench")
    assert spec.resolve_cell(BM, "opt-6.7b-16l.latency-b1", ROOT).family \
        is spec.load_family("opt")


def test_unknown_names_are_errors():
    with pytest.raises(spec.SpecError):
        spec.resolve_cell(BM, "no-such-cell", ROOT)
    with pytest.raises(spec.SpecError):
        spec.metric_reader("no.such.metric")
    with pytest.raises(spec.SpecError):
        spec.load_peaks("TPU v99 imaginary")
    assert spec.load_peaks("TPU v5 lite")["hbm_bytes_per_s"] == 819e9


def test_a_new_metric_is_a_new_file_and_an_entry(tmp_path):
    """Adding a per-layer metric touches no existing file: a reader file
    and a BENCHMARK.json entry are enough."""
    root = tmp_path / "repo"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    bm = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = bm["workloads"][0]["name"]
    bm["per_layer"].append({
        "name": "scratch.count", "unit": "requests", "better": "higher",
        "source": "host_clock", "layer": "front end",
        "moves": "tokens_per_s", "workloads": [cell]})
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    (root / "bench" / "metrics" / "scratch.count.py").write_text(
        "def read(run):\n    return float(len(run.reqs))\n")
    c = spec.resolve_cell(spec.load_benchmark(root), cell, root)
    assert "scratch.count" in [m["name"] for m in c.per_layer]
    reader = spec.metric_reader("scratch.count", root / "bench")

    class R:
        reqs = [1, 2, 3]
    assert reader(R()) == 3.0


def test_run_exits_nonzero_without_a_tpu():
    env = {"JAX_PLATFORMS": "cpu", "PATH": "/usr/bin:/bin"}
    import os
    env.update({k: v for k, v in os.environ.items()
                if k in ("HOME", "PYTHONPATH", "TMPDIR")})
    cell = BM["workloads"][0]["name"]
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", cell, "--seed", "1", "--seconds", "1",
                        "--trace", "0"], capture_output=True, text=True,
                       env=env, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr

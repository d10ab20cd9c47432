"""The plain float32 OPT reference against the serving program, at a
size the CPU runs: both in float32, the program's prefill logits and its
greedy decode through the paged pool must agree with the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness.families import seed_key
from harness.spec import BENCH, load_family, load_json


TINY = load_json(BENCH / "tests" / "fixtures" / "tiny-opt.json")
opt = load_family(TINY["family"])
D = opt.dims(TINY["model"])


@pytest.fixture(scope="module")
def program():
    from repro.compiler.mapper import plan_model
    from repro.models.registry import build_model
    from repro.serving.config import EngineConfig
    from repro.serving.engine import LPUEngine
    cfg = opt.arch_config(TINY, D)
    plan = plan_model(cfg, None, (1,), "serve", esl_overlap=False,
                      remat="none", compute_dtype="float32",
                      param_dtype="float32")
    model = build_model(cfg, plan)
    template = jax.eval_shape(lambda k: model.init(k)[0],
                              jax.random.PRNGKey(0))
    c = opt.canonical(seed_key(2_500_000_001), D, jnp.float32)
    params = opt.program_params(c, D, template)
    e = TINY["engine"]
    eng = LPUEngine(model, params, EngineConfig(
        slots=e["slots"], max_seq=e["max_seq"], block_size=e["block_size"]))
    return eng, c


def test_prefill_logits_match_the_reference(program):
    eng, c = program
    rng = np.random.default_rng(0)
    prompt = rng.integers(2, D["V"], size=21).tolist()
    buf = np.zeros((1, 32), np.int32)
    buf[0, :21] = prompt
    row, _ = eng._prefill(eng.params, jnp.asarray(buf), jnp.int32(21))
    row = np.asarray(row, np.float32)[:D["V"]]
    h = opt.hidden(c, D, buf, np.array([21]))
    ref = np.asarray(opt._head(c, h, control=None))[0, 20, :D["V"]]
    assert np.abs(row - ref).max() <= 1e-3 * np.abs(ref).max()


def test_served_tokens_sit_at_the_reference_argmax(program):
    eng, c = program
    rng = np.random.default_rng(1)
    prompts = [rng.integers(2, D["V"], size=n).tolist() for n in (5, 17, 30)]
    outs = eng.generate(prompts, max_new_tokens=8)
    g, _ = opt.served_gaps(c, D, list(zip(prompts, outs)))
    assert g.size == 24
    assert g.max() <= 1e-3


def test_a_wrong_token_shows_as_a_gap(program):
    _, c = program
    rng = np.random.default_rng(2)
    prompt = rng.integers(2, D["V"], size=9).tolist()
    g, _ = opt.served_gaps(c, D, [(prompt, [5, 6, 7])])
    assert g.max() > 0.1


def test_int8_control_departs_from_the_reference(program):
    _, c = program
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(2, D["V"], size=20).tolist(),
             rng.integers(2, D["V"], size=20).tolist()) for _ in range(4)]
    g, gc = opt.served_gaps(c, D, seqs, control=True)
    assert gc.shape == g.shape
    assert gc.max() > 0


def test_weights_depend_on_every_bit_of_the_seed():
    a = opt.canonical(seed_key(5), D)
    b = opt.canonical(seed_key(5 + 2 ** 31), D)
    assert not np.array_equal(np.asarray(a["wq"]), np.asarray(b["wq"]))
    assert np.array_equal(np.asarray(a["bo"]), np.zeros(D["L"] * D["D"])
                          .reshape(D["L"], D["D"]))


@pytest.mark.parametrize("conf", ["configs/opt-1.3b.json",
                                  "configs/opt-6.7b-16l.json",
                                  "tests/fixtures/tiny-opt.json"])
def test_arch_config_is_the_one_the_harness_built_inline(conf):
    """Field for field, the program configuration the harness built
    in ``cell.build`` before the family module took it over."""
    import dataclasses
    from repro.configs import get_config
    c = load_json(BENCH / conf)
    d = opt.dims(c["model"])
    inline = dataclasses.replace(
        get_config(c["arch"]), name=c["name"], n_layers=d["L"],
        d_model=d["D"], n_heads=d["H"], n_kv_heads=d["H"], d_head=d["dh"],
        d_ff=d["F"], vocab_size=d["V"], max_seq=d["P"])
    assert dataclasses.asdict(opt.arch_config(c, d)) == \
        dataclasses.asdict(inline)

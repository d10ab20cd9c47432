"""The control at a size the CPU holds: the served tokens of the bf16
serving program sit far closer to the float32 reference's best logits
than the tokens an int8 computation of the same reference puts first,
and a whole run with the control in the program's place comes out not
correct.  On the chip the same comparison sets each cell's limit
(``bench/control.py``)."""
import copy
import time

import numpy as np
import pytest

from harness import cell as C
from harness.families import seed_key
from harness.spec import BENCH, Cell, load_benchmark, load_family, load_json

FIX = BENCH / "tests" / "fixtures"
SMALL_OPEN = dict(load_json(FIX / "tiny-open.json"),
                  prompt={"dist": "uniform", "min": 8, "max": 40},
                  output={"dist": "fixed", "value": 48},
                  ids={"low": 2, "high": 4096})
# widest gaps over 256 served tokens on this model: bf16 program
# 0.04-0.06, int8 control 0.40-0.67 (seeds 1-3)
LIMIT = 0.2


@pytest.fixture(scope="module")
def small():
    bm = load_benchmark()
    conf = copy.deepcopy(load_json(FIX / "tiny-opt.json"))
    conf["model"].update(num_hidden_layers=4, hidden_size=256,
                         num_attention_heads=4, ffn_dim=1024,
                         vocab_size=4096)
    conf["engine"].update(max_seq=128)
    return Cell(name="small", chips=1, config=conf, traffic=SMALL_OPEN,
                limit={"served_logit_gap": LIMIT},
                end_to_end=bm["end_to_end"], per_layer=bm["per_layer"],
                family=load_family(conf["family"]))


@pytest.mark.parametrize("seed", [1, 3])
def test_control_fails_where_the_program_passes(small, seed):
    engine, d = C.build(small, seed, on_chip=False)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(2, d["V"], size=n).tolist()
               for n in (8, 20, 33, 47)]
    outs = engine.generate(prompts, max_new_tokens=64)
    fam = small.family
    c = fam.canonical(seed_key(seed), d)
    g, gc = fam.served_gaps(c, d, list(zip(prompts, outs)),
                            control=True)
    assert g.size == 256
    assert g.max() < LIMIT < gc.max()
    assert gc.max() > 3 * g.max()


def test_a_run_with_the_control_in_place_is_not_correct(small):
    r = C.run_cell(small, 5, 1.0, False, time.perf_counter(),
                   on_chip=False, control=True)
    checks = r["checks"]
    assert r["correct"] is False, checks
    assert checks["program_logit_gap"][0] <= LIMIT
    assert checks["served_logit_gap"][0] > LIMIT
    assert checks["served_logit_gap"][1] == LIMIT

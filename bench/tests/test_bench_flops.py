"""Operation and byte counts of the OPT family against hand counts for
OPT-1.3B."""
from harness.families import over_steps
from harness.spec import BENCH, load_family, load_json

fb = load_family("opt")
D13 = fb.dims(load_json(BENCH / "configs" / "opt-1.3b.json")["model"])


def test_opt13_weight_params_by_hand():
    # per layer: q,k,v,o 4*2048^2; fc1+fc2 2*2048*8192; biases
    # 4*2048 (q,k,v,o) + 8192 + 2048; LayerNorms 4*2048
    per_layer = 4 * 2048 ** 2 + 2 * 2048 * 8192 + 4 * 2048 + 8192 + 2048 \
        + 4 * 2048
    assert per_layer == 50_358_272
    head = 50272 * 2048
    assert fb.weight_params(D13) == 24 * per_layer + head + 2 * 2048
    assert fb.weight_params(D13) == 1_311_559_680


def test_opt13_decode_step_by_hand():
    ctx = [100, 300]                      # two live slots
    mm = 24 * (4 * 2048 ** 2 + 2 * 2048 * 8192) + 50272 * 2048
    attn = 24 * 4 * 2048 * (101 + 301)
    assert fb.step_flops(D13, ctx) == 2 * mm * 2 + attn
    # KV: blocks of 128 -> 1 and 3 blocks; K and V, 2 bytes, 24 layers
    kv = 24 * (128 + 384) * 2 * 2048 * 2
    write = 24 * 2 * 2 * 2048 * 2
    assert fb.kv_read_bytes(D13, ctx, 128) == kv
    assert fb.step_bytes(D13, ctx, 128) == \
        fb.weight_params(D13) * 2 + kv + write


def test_paged_kernel_by_hand():
    ctx = [128, 129]
    # 1 + 2 blocks of 128 tokens, K and V of 2048 bf16 each; q and out
    assert fb.paged_attn_bytes(D13, ctx, 128) == \
        384 * 2 * 2048 * 2 + 2 * 2 * 2048 * 2
    assert fb.paged_attn_flops(D13, ctx) == 4 * 2048 * (129 + 130)
    assert over_steps(fb.paged_attn_flops, D13, [ctx, ctx]) == \
        2 * fb.paged_attn_flops(D13, ctx)

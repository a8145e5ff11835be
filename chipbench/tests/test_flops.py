"""The FLOP and byte functions against counts made by hand."""

import json
import os

import pytest

import flops
import weights

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def dims_of(name):
    with open(os.path.join(BENCH, "configs", f"{name}.json")) as f:
        return weights.Dims.from_config(json.load(f))


# the chip's share of starcoder2-7b under two-way tensor parallelism
# (ISSUE 25, cell 4): 18 of 36 heads, 2 of 4 kv heads, d_ff 9216 of 18432
SEVEN_B_TP2 = weights.Dims(
    vocab=49152, d_model=4608, n_heads=18, n_kv_heads=2, head_dim=128,
    d_ff=9216, n_layers=1, rope_theta=1e6, window=4096, norm_eps=1e-5,
    context=16384)


def test_parameter_counts():
    d = dims_of("starcoder2-3b")
    per_layer = (3072 * 3072 + 3072 * 2 * 256 + 3072 * 3072
                 + 2 * 3072 * 12288)
    assert flops.layer_matmul_params(d) == per_layer == 95_944_704
    # + biases and norms: 4*3072 (two norms) + 12288 + 3072
    assert d.n_params() == 30 * (per_layer + 4 * 3072 + 12288 + 3072) \
        + 2 * 49152 * 3072 + 2 * 3072 == 3_181_166_592
    t = dims_of("starcoder2-3b-train")
    assert t.n_params() == 6 * 95_972_352 + 2 * 49152 * 3072 + 6144 \
        == 877_830_144
    # 7B, one chip's half of a layer: wq 4608*18*128, wkv 4608*2*256,
    # wo 18*128*4608, mlp 2*4608*9216
    assert flops.layer_matmul_params(SEVEN_B_TP2) == (
        10_616_832 + 2_359_296 + 10_616_832 + 84_934_656)


def test_banded_pairs_by_enumeration():
    for t, w in ((8, None), (8, 3), (5, 8), (16, 4)):
        want = sum(1 for q in range(t) for k in range(t)
                   if k <= q and (w is None or q - k < w))
        assert flops.banded_pairs(t, w) == want
    # seq 8192, window 4096: 4096*4097/2 + 4096*4096
    assert flops.banded_pairs(8192, 4096) == 8_390_656 + 16_777_216


def test_train_step_flops_by_hand():
    d = dims_of("starcoder2-3b-train")
    n_mm = 6 * 95_944_704 + 3072 * 49152          # 726,663,168
    dense = 6 * n_mm * 8192
    attn = 3 * (4 * 24 * 128 * 25_167_872) * 6    # fwd + 2x bwd, 6 layers
    assert flops.train_flops_per_step(d, 1, 8192) == dense + attn
    assert round((dense + attn) / 1e12, 1) == 41.3
    # two rows a step double it
    assert flops.train_flops_per_step(d, 2, 8192) == 2 * (dense + attn)


def test_serve_flops_by_hand():
    d = dims_of("starcoder2-3b")
    # one decode token at context 1000: matmuls, attention, head
    want = (2 * 30 * 95_944_704 + 30 * 4 * 24 * 128 * 1000
            + 2 * 3072 * 49152)
    assert flops.serve_token_flops(d, 1000, True) == want
    # a prefill chunk [512, 1024): positions attend 513..1024 keys
    pairs = sum(range(513, 1025))
    want = 2 * 30 * 95_944_704 * 512 + 30 * 4 * 24 * 128 * pairs
    assert flops.prefill_flops(d, 512, 512, False) == want
    assert flops.prefill_flops(d, 512, 512, True) == want + 2 * 3072 * 49152
    # chunks add up to the whole prompt
    whole = flops.prefill_flops(d, 0, 1300, True)
    parts = (flops.prefill_flops(d, 0, 512, False)
             + flops.prefill_flops(d, 512, 512, False)
             + flops.prefill_flops(d, 1024, 276, True))
    assert whole == parts
    # past the window a token attends 4096 keys, not its whole context
    w = weights.Dims(**{**d.__dict__, "window": 4096})
    assert (flops.serve_token_flops(w, 9000, False)
            == flops.serve_token_flops(w, 4096, False))


def test_kernel_costs_and_bounds():
    d = dims_of("starcoder2-3b-train")
    peaks = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}
    f, b = flops.flash_fwd_cost(d, 1, 8192, 24)
    assert f == 4 * 24 * 128 * 25_167_872
    assert b == 4 * 8192 * 24 * 128 * 2
    s, bound = flops.roofline_seconds(f, b, peaks)
    assert bound == "compute" and s == pytest.approx(1.57e-3, rel=0.01)
    f2, b2 = flops.flash_bwd_cost(d, 1, 8192, 24)
    assert f2 == 10 * 24 * 128 * 25_167_872 and b2 == 7 * 8192 * 24 * 128 * 2
    # the 7B share: 18 heads a chip
    f7, _ = flops.flash_fwd_cost(SEVEN_B_TP2, 1, 8192, 18)
    assert f7 == 4 * 18 * 128 * 25_167_872
    # paged decode: two rows at contexts 100 and 3000, 30 layers, 2 kv heads
    s = dims_of("starcoder2-3b")
    f, b = flops.paged_decode_cost(s, [100, 3000])
    assert b == 2 * 3100 * 2 * 128 * 2 * 30
    assert f == 4 * 24 * 128 * 3100 * 30
    assert flops.roofline_seconds(f, b, peaks)[1] == "bandwidth"

"""Operations and bytes of the kernels and of the model step, against
arithmetic done by hand."""
import pytest

from bench import registry

BERT = {"n_layers": 12, "d_model": 768, "n_heads": 12, "n_kv_heads": 12,
        "d_ff": 3072, "vocab": 30522, "causal": False, "n_classes": 2}


def test_memo_attention_hit_and_miss_rows():
    w = registry.module("work", "memo_attention")
    # one hit row, int8, S=512: APM 12*512*512 B + scales 12*512*2 B
    # + V 12*512*64*4 B + out 12*512*64*4 B; APM.V 2*12*512*512*64
    ops, nbytes = w.work(BERT, 512, 1, 0, "int8")
    assert ops == 2 * 12 * 512 * 512 * 64 == 402653184
    assert nbytes == 3145728 + 12288 + 1572864 + 1572864
    # one miss row: QK^T + PV = 4*12*512*512*64; Q, K, V, out in f32
    ops, nbytes = w.work(BERT, 512, 0, 1, "int8")
    assert ops == 805306368
    assert nbytes == 4 * 1572864
    # causal halves the miss row's operations, not its bytes
    ops_c, _ = w.work(dict(BERT, causal=True), 512, 0, 1, "f16")
    assert ops_c == 805306368 / 2
    # f16 hit: 2-byte codes, no scales
    _, nb16 = w.work(BERT, 512, 1, 0, "f16")
    assert nb16 == 2 * 3145728 + 2 * 1572864


@pytest.mark.parametrize("S", [128, 512])
def test_model_step(S):
    w = registry.module("work", "model_step")
    per_layer = 2 * S * (4 * 768 * 768 + 2 * 768 * 3072) \
        + 4 * S * S * 768
    assert w.flops(BERT, S, "classify") == 12 * per_layer + 2 * 768 * 2
    with pytest.raises(ValueError):
        w.flops(BERT, S, "lm_last")
    if S == 512:
        # ~0.75 TFLOP per 8-row batch of 512 tokens (issue reckoning)
        assert 0.7e12 < 8 * w.flops(BERT, S, "classify") < 0.8e12


def test_peaks_table_has_the_v5e():
    p = registry.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        registry.peaks("TPU v99")

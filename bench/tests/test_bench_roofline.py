"""Operation and byte counts of the roofline yardstick, worked by hand."""
import pytest

from bench import roofline, weights

PEAKS = {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def deepseek():
    return weights.Arch(weights.load_config("deepseek-7b-l14"))


@pytest.fixture
def starcoder():
    return weights.Arch(weights.load_config("starcoder2-15b-l10"))


def test_paged_attention_bytes(deepseek, starcoder):
    # 8 rows holding 1000 positions in all: keys and values of 32 heads of
    # 128, bf16, read once = 1000*32*128*2*2; queries and outputs
    # 8*4096*2 each
    f, b = roofline.paged_attention(deepseek, 1000, 8)
    assert f == 4 * 1000 * 4096
    assert b == 1000 * 32 * 128 * 4 + 2 * 8 * 4096 * 2
    # GQA: 4 KV heads of 128 -> 2 KiB per position, 48 query heads
    f, b = roofline.paged_attention(starcoder, 1, 16)
    assert b == 2048 + 2 * 16 * 6144 * 2 and f == 4 * 6144


def test_lm_head_pass(deepseek):
    f, b = roofline.lm_head_pass(deepseek, 8)
    assert f == 2 * 8 * 4096 * 102400
    assert b == (4096 * 102400 + 8 * 4096) * 2
    # memory-bound: one pass takes 0.84 GB / 819 GB/s = 1.025 ms
    assert roofline.share(f, b, 1.025e-3, PEAKS) == pytest.approx(
        100 * (4096 * 102400 + 8 * 4096) * 2 / 819e9 / 1.025e-3)


def test_decode_flops(deepseek, starcoder):
    # DeepSeek layer: 4*4096^2 attention + 3*4096*11008 MLP = 202,375,168
    assert roofline.layer_matmul_params(deepseek) == 202375168
    # StarCoder2 layer: 2*6144^2 + 2*6144*512 + 2*6144*24576 = 383,778,816
    assert roofline.layer_matmul_params(starcoder) == 383778816
    assert roofline.decode_token_flops(deepseek, 0) == \
        14 * 2 * 202375168 + 2 * 4096 * 102400
    assert roofline.decode_token_flops(deepseek, 100) - \
        roofline.decode_token_flops(deepseek, 0) == 14 * 4 * 100 * 4096


def test_share_takes_the_binding_bound():
    assert roofline.share(197e12, 0, 1.0, PEAKS) == pytest.approx(100)
    assert roofline.share(0, 819e9, 2.0, PEAKS) == pytest.approx(50)

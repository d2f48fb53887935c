"""``flops.py`` against counts made by hand."""

import pytest

import flops

GPT2_SMALL = dict(vocab_size=50257, dim=768, num_heads=12, num_blocks=12,
                  seq_len=1024)


def test_gpt2_small_is_798_mflop_a_token():
    # forward matmuls: 12 blocks x 24 d^2 + 2 d V
    matmul = 12 * 24 * 768 ** 2 + 2 * 768 * 50257
    assert flops.gpt_lm_matmul_flops_per_token(GPT2_SMALL) == matmul
    assert 3 * matmul == pytest.approx(741.19e6, rel=1e-4)
    # causal attention: 12 blocks x 4 d flops a key, (T + 1) / 2 keys
    attn = 12 * 4 * 768 * 1025 / 2
    assert flops.causal_attention_flops_per_token(GPT2_SMALL) == attn
    per_token = flops.gpt_lm_train(GPT2_SMALL) / 1024
    assert per_token == 3 * (matmul + attn)
    assert per_token == pytest.approx(798e6, rel=1e-3)


def test_resnet20_is_244_mflop_a_sample():
    # stem, three stages of six 3x3 convolutions, two 1x1 shortcuts, head
    stem = 32 * 32 * 27 * 16
    s1 = 6 * 32 * 32 * 9 * 16 * 16
    s2 = 16 * 16 * 9 * 16 * 32 + 5 * 16 * 16 * 9 * 32 * 32 + 16 * 16 * 16 * 32
    s3 = 8 * 8 * 9 * 32 * 64 + 5 * 8 * 8 * 9 * 64 * 64 + 8 * 8 * 32 * 64
    macs = stem + s1 + s2 + s3 + 64 * 10
    assert macs == 40_813_184
    assert flops.resnet_cifar_macs({"width": 16}) == macs
    assert flops.resnet_cifar_train({"width": 16}) == 6 * macs
    assert 6 * macs == pytest.approx(244.9e6, rel=1e-3)


def test_flash_work_and_executed_blocks_at_1024():
    work, moved = flops.flash_train(GPT2_SMALL, batch=1)
    # 7 matmuls of 2 * d flops a (query, key) pair, 12 blocks, causal half
    assert work == 12 * 7 * 2 * 768 * (1024 * 1025 / 2)
    assert moved == 12 * 12 * 1024 * 768 * 2
    # needed attention is what the model count has: (2 + 4) of those 7
    assert work * 6 / 7 == pytest.approx(
        3 * flops.causal_attention_flops_per_token(GPT2_SMALL) * 1024)
    # one 1024-wide block (pallas_attention._auto_block(1024, 64)) runs the
    # whole square: twice the causal half; 128-wide blocks run 36 of 64
    assert flops.flash_executed_block_pairs(1024, 1024, 1024) == 1
    assert flops.flash_executed_block_pairs(1024, 128, 128) == 36
    from distkeras_tpu.ops.pallas_attention import _auto_block
    assert _auto_block(1024, 64) == 1024

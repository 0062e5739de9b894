"""Operation and byte counts against hand counts at tiny shapes."""
import pytest

from lib import flops, peaks


def test_transformer_matmul_parameters_by_hand():
    # one layer pair, d=4, d_inner=8, vocab=10:
    # encoder: qkv 4x12=48, o 16, ffn 32+32=64          -> 128
    # decoder: self qkv 48 + o 16, cross q 16 + kv 32 + o 16, ffn 64 -> 192
    # projection 4x10 = 40
    assert flops.transformer_matmul_params(1, 4, 8, 10) == 128 + 192 + 40


def test_transformer_train_flops_per_token_by_hand():
    # 6 per matmul parameter, plus 12*T*d for each of 3 attention blocks
    want = 6 * 360 + 12 * 16 * 4 * 3
    assert flops.transformer_train_flops_per_token(1, 4, 8, 10, 16) == want


def test_transformer_base_numbers():
    p = flops.transformer_matmul_params(6, 512, 2048, 32000)
    assert p == 6 * (4 * 512 * 512 + 2 * 512 * 2048
                     + 8 * 512 * 512 + 2 * 512 * 2048) + 512 * 32000
    per_token = flops.transformer_train_flops_per_token(6, 512, 2048, 32000,
                                                        256)
    assert per_token == pytest.approx(390.9e6, rel=1e-3)


def test_resnet50_forward_matches_the_papers_count():
    # He et al., Table 1: 3.8e9 multiply-adds for the 50-layer net
    f = flops.resnet_forward_flops_per_image(50, 224, 1000)
    assert f / 2 == pytest.approx(3.86e9, rel=0.01)
    assert flops.resnet_train_flops_per_image(50, 224, 1000) == 3 * f


def test_resnet_count_by_hand_at_a_tiny_shape():
    # depth 18 at 32x32, 10 classes: conv1 16x16 out, pooled to 8x8
    f = 2.0 * 16 * 16 * 3 * 64 * 49                        # conv1
    f += 2 * 2 * (2.0 * 8 * 8 * 64 * 64 * 9)               # stage 1, 2 blocks
    # stage 2: first block strides to 4x4 and projects 64 -> 128
    f += 2.0 * 4 * 4 * 64 * 128 * 9 + 2.0 * 4 * 4 * 128 * 128 * 9 \
        + 2.0 * 4 * 4 * 64 * 128
    f += 2 * (2.0 * 4 * 4 * 128 * 128 * 9)
    f += 2.0 * 2 * 2 * 128 * 256 * 9 + 2.0 * 2 * 2 * 256 * 256 * 9 \
        + 2.0 * 2 * 2 * 128 * 256
    f += 2 * (2.0 * 2 * 2 * 256 * 256 * 9)
    # stage 4 has ONE block in models/resnet.py's depth-18 table
    f += 2.0 * 1 * 1 * 256 * 512 * 9 + 2.0 * 1 * 1 * 512 * 512 * 9 \
        + 2.0 * 1 * 1 * 256 * 512
    f += 2.0 * 512 * 10
    assert flops.resnet_forward_flops_per_image(18, 32, 10) == f


def test_decode_step_bytes_by_hand():
    # 1 layer, d=8, 2 heads of 4, 1 kv head, ffn 16, vocab 10, bf16:
    # q 64 + k 32 + v 32 + o 64 + 3*128 = 576 params; head 80
    assert flops.llama_block_params(8, 2, 1, 4, 16) == 576
    got = flops.decode_step_bytes(1, 8, 2, 1, 4, 16, 10, 2, 2,
                                  live_kv_tokens=100, active_rows=3)
    assert got == (576 + 80) * 2 + 3 * 8 * 2 + 2 * 1 * 1 * 4 * 2 * 100
    ops = flops.decode_step_flops(1, 8, 2, 1, 4, 16, 10, 100, 3)
    assert ops == 2 * 656 * 3 + 4 * 1 * 2 * 4 * 100


def test_mistral_cut_weights_are_seven_and_a_half_gigabytes():
    b = flops.decode_step_bytes(16, 4096, 32, 8, 128, 14336, 32000, 2, 2,
                                0, 0)
    # plus the embedding table: what the chip holds
    assert (b + 32000 * 4096 * 2) / 1e9 == pytest.approx(7.50, abs=0.02)


def test_peaks_table_is_exact_and_has_no_default():
    assert peaks.peaks('TPU v5 lite') == {'bf16_flops': 197e12,
                                          'hbm_bytes_per_s': 819e9,
                                          'hbm_bytes': 16e9}
    with pytest.raises(peaks.NoChip):
        peaks.peaks('TPU v5')
    with pytest.raises(peaks.NoChip):
        peaks.require_device(1)          # the tests run on the CPU
    with pytest.raises(peaks.NoChip):
        peaks.require_device(64, allow_cpu=True)

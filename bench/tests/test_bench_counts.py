"""``bench/counts`` against counts made by hand for qwen2-1.5b and
internvl2-1b."""
import os

import pytest

from lib import common

counts = common.load_module(os.path.join(common.BENCH, "counts",
                                         "transformer.py"))
QWEN = common.load_json(common.BENCH, "configs", "qwen2-1.5b.json")
VLM = common.load_json(common.BENCH, "configs", "internvl2-1b.json")


def test_matmul_weights_by_hand():
    # qwen2-1.5b: q 1536*12*128 + k, v 2*1536*2*128 + o 12*128*1536
    # + SwiGLU 3*1536*8960 = 2,359,296 + 786,432 + 2,359,296 + 41,287,680
    assert counts.layer_matmul_params(QWEN) == 46_792_704
    assert counts.unembed_params(QWEN) == 151_936 * 1536 == 233_373_696
    # 28 layers and the tied table: the model's 1.54 B
    assert 28 * 46_792_704 + 233_373_696 == 1_543_569_408
    # internvl2-1b: 802,816 + 229,376 + 802,816 + 13,074,432
    assert counts.layer_matmul_params(VLM) == 14_909_440
    assert counts.projector_params(VLM) == 4096 * 896 + 896 * 896 == 4_472_832
    assert counts.projector_params(QWEN) == 0


def test_causal_attention_is_the_lower_triangle():
    # 2048 positions: 2048 * 2049 / 2 = 2,098,176 (query, key) pairs;
    # QK and PV are 2 * 128 operations a pair and head: 512 per pair-head
    ops, nbytes = counts.causal_attention(QWEN, 2048)
    assert ops == 28 * 12 * 512 * 2_098_176 == 360_953_413_632
    # Q and O are 2048 x 12 x 128, K and V 2048 x 2 x 128, 2 bytes each
    assert nbytes == 28 * 2 * (2 * 2048 * 1536 + 2 * 2048 * 256)
    bwd_ops, bwd_bytes = counts.causal_attention(QWEN, 2048, backward=True)
    assert bwd_ops == 2 * ops
    assert bwd_bytes == 28 * 2 * (3 * 2048 * 1536 + 4 * 2048 * 256)
    # one position attends itself only
    assert counts.causal_attention(VLM, 1)[0] == 24 * 14 * 4 * 64


def test_training_operations_by_hand():
    # internvl2-1b, 2048 positions of which 256 are the image prefix:
    # 6 * (357,826,560 * 2048 + 135,882,880 * 1792 + 4,472,832 * 256)
    # + forward and backward attention, 3 * 24 * 14 * 256 * 2,098,176
    want = (6 * (357_826_560 * 2048 + 135_882_880 * 1792
                 + 4_472_832 * 256) + 3 * 24 * 14 * 256 * 2_098_176)
    assert counts.train_ops_per_sequence(VLM, 2048) == want
    assert 3.0e9 < want / 2048 < 3.3e9          # about 3.1 GFLOP a position


def test_prefill_and_decode_by_hand():
    # 512 prompt tokens through 28 layers, the last one unembedded
    ops = counts.prefill_ops(QWEN, 512)
    assert ops == (2 * (28 * 46_792_704 * 512 + 233_373_696)
                   + 28 * 12 * 512 * (512 * 513 // 2))
    # decode: every weight once in bf16, and 28 layers x K, V x 2 heads x
    # 128 x 2 bytes = 28,672 bytes per cache position in use
    assert counts.decode_bytes(QWEN, []) == 2 * 1_543_569_408
    assert counts.decode_bytes(QWEN, [10, 5]) - counts.decode_bytes(
        QWEN, []) == 15 * 28_672


def test_decode_operations_by_hand():
    # two lanes: each token through 28 layers and the 151,936 x 1536 table
    # (2 x 1,543,569,408 operations), and attention over 10 and 5 positions
    # at 28 layers x 12 heads x 4 x 128
    want = 2 * 2 * 1_543_569_408 + 28 * 12 * 512 * 15
    assert counts.decode_ops(QWEN, [10, 5]) == want


@pytest.mark.parametrize("seq", [128, 2048])
def test_counts_grow_as_they_should(seq):
    a, _ = counts.causal_attention(QWEN, seq)
    b, _ = counts.causal_attention(QWEN, 2 * seq)
    assert 3.9 < b / a < 4.1

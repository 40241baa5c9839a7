"""``flops/*.py`` against hand counts at the flagship shapes."""
import bench_helpers  # noqa: F401  (puts the repo root on sys.path)
import pytest

from benchmark.flops import logbert, mlp

LOGBERT = {"model": "logbert", "vocab_size": 32768, "dim": 256, "depth": 4,
           "heads": 4, "seq_len": 32}
MLP = {"model": "mlp", "dim": 128, "seq_len": 32}


def test_logbert_ops_per_row_by_hand():
    # per token and block: qkv 2*256*768, proj 2*256*256, mlp 2*2*256*1024,
    # attention 2*2*32*256  = 393216 + 131072 + 1048576 + 32768 = 1605632
    # head per token 2*256*32768 = 16777216
    per_token = 4 * 1605632 + 16777216
    assert per_token == 23199744
    assert logbert.ops_per_row(LOGBERT) == 32 * per_token == 742391808


def test_logbert_params_by_hand():
    block = 4 * 256 + (256 * 768 + 768) + (256 * 256 + 256) \
        + (256 * 1024 + 1024) + (1024 * 256 + 256)
    assert block == 789760
    assert logbert.params_count(LOGBERT) == (32768 * 256 + 32 * 256
                                             + 4 * block + 512)


def test_logbert_full_batch_is_compute_bound_on_v5e():
    ops, nbytes = logbert.ops_and_bytes(LOGBERT, 16384)
    assert ops == 16384 * 742391808
    assert nbytes == 4 * logbert.params_count(LOGBERT) + 16384 * 68
    assert ops / 197e12 > nbytes / 819e9
    assert ops / 197e12 == pytest.approx(0.06174, rel=1e-3)


def test_mlp_ops_per_row_by_hand():
    # pooling 2*32*128, fc1 2*128*256, fc2 2*256*128, head 2*128*32768
    assert mlp.ops_per_row(MLP) == 8192 + 65536 + 65536 + 8388608 == 8527872
    assert mlp.params_count(MLP) == 32768 * 128 + 128 * 256 + 256 \
        + 256 * 128 + 128


def test_mlp_full_batch():
    ops, nbytes = mlp.ops_and_bytes(MLP, 16384)
    assert ops == 16384 * 8527872
    assert nbytes == 4 * mlp.params_count(MLP) + 16384 * 68


def test_logbert_head_kernel_by_hand():
    # the logits' matmul alone: 2 * rows * 32 positions * 32768 ids * 256
    ops, nbytes = logbert.head_ops_and_bytes(LOGBERT, 32768)
    assert ops == 2 * 32768 * 32 * 32768 * 256 == 17592186044416
    # bfloat16 hidden states and embedding in, one float32 per position out
    assert nbytes == (2 * 32768 * 32 * 256 + 2 * 32768 * 256
                      + 4 * 32768 * 32) == 557842432
    assert ops / 197e12 == pytest.approx(0.08930, rel=1e-3)
    assert ops / 197e12 > nbytes / 819e9        # compute-bound on the v5e
    # the head is 537 of the 742 MFLOP a line that ops_and_bytes counts
    assert ops == 32768 * 32 * 2 * 256 * 32768
    assert ops < logbert.ops_and_bytes(LOGBERT, 32768)[0]

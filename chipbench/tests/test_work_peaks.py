"""Operation and byte counts at olmo_1b's shapes, checked against numbers
worked by hand, and the table of peaks."""
import json
import os

import pytest

from chipbench import peaks, work
from chipbench.refs import olmo

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def arch(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return olmo.arch(json.load(f))


def test_phi_gemm_at_olmo_shapes():
    # w1 at prefill of a 1024-token bucket, T=2: M=2048, K=2048, N=8192
    w = work.phi_gemm(2048, 2048, 8192, k=16, q=16, timesteps=2)
    assert w["ops"] == 2048 * 128 * 8192                     # 2.147e9 adds
    # W is 2048*8192*4 = 67,108,864 B; the bank 128*17*8192*4 = 71,303,168 B
    assert w["bytes"] == 67_108_864 + 2048 * 2048 // 8 + 2 * 1024 * 8192
    # one addition per k-wide tile where the dense product does k
    # multiply-adds (2k operations): 32x fewer operations at k=16
    assert work.dense_gemm(2048, 2048, 8192)["ops"] == 32 * w["ops"]


def test_phi_gemm_w2_bank_vs_weight():
    # w2: K=8192, N=2048. Bank 512*17*2048*4 = 71,303,168 B > W 67,108,864 B
    w = work.phi_gemm(16, 8192, 2048, k=16, q=16, timesteps=2)
    assert w["bytes"] == 67_108_864 + 16 * 8192 // 8 + 2 * 8 * 2048
    assert w["ops"] == 16 * 512 * 2048


def test_no_exact_lowering_beats_the_count():
    """The counts are lower bounds: the dense spiking product of the same
    spikes (an exact lowering) does at least as many operations and reads
    W whole, so its roofline share of the Phi count stays under 100%."""
    for M, K, N in [(16, 2048, 2048), (4096, 2048, 8192), (16, 8192, 2048)]:
        phi = work.phi_gemm(M, K, N, k=16, q=16, timesteps=2)
        dense = work.dense_gemm(M, K, N)
        assert phi["ops"] < dense["ops"]
        assert phi["bytes"] <= K * N * 4 + M * K * 4 + M * N * 4


def test_model_ops_plain_olmo():
    a = arch("olmo_1b")
    gemm = 16 * 2 * (4 * 2048 * 2048 + 3 * 2048 * 8192)      # per token
    head = 2 * 2048 * 50304
    assert olmo.model_ops(a, decode_contexts=[100]) == \
        gemm + 4 * 16 * 16 * 128 * 100 + head
    assert olmo.model_ops(a, prompt_lens=[3]) == \
        3 * gemm + 4 * 16 * 16 * 128 * 6 + head


def test_model_ops_phi_olmo():
    a = arch("olmo_1b_phi")
    gemm = 16 * 2 * (4 * 128 * 2048 + 2 * 128 * 8192 + 512 * 2048)  # T=2 rows
    head = 2 * 2048 * 50304
    assert olmo.model_ops(a, decode_contexts=[1]) == gemm + 4 * 16 * 16 * 128 + head


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peaks("cpu")

"""benchmark/flops.py and the Mistral family's counts against counts made by
hand for one Mistral-7B layer."""
import pytest

from benchmark import flops
from benchmark.harness.spec import Spec

F = Spec().family("mistral")

CFG = dict(hidden_size=4096, intermediate_size=14336, num_attention_heads=32,
           num_key_value_heads=8, head_dim=128, num_hidden_layers=1, vocab_size=32768)


def test_layer_parameters_by_hand():
    # q 4096x4096, k and v 4096x1024 each, o 4096x4096, gate/up/down 4096x14336 each
    by_hand = 4096 * 4096 + 2 * 4096 * 1024 + 4096 * 4096 + 3 * 4096 * 14336
    assert F.layer_matmul_params(CFG) == by_hand == 218_103_808
    assert F.matmul_params(CFG) == by_hand + 4096 * 32768


def test_forward_of_one_sequence_by_hand():
    S = 4096
    matmuls = 2 * 218_103_808 * S + 2 * 4096 * 32768 * S
    # token i sees i+1 keys; scores and weighted sum: 2 x 2 x keys x 32 heads x 128
    attention = 4 * 32 * 128 * (S * (S + 1) // 2)
    assert F.forward_flops(CFG, S, 0) == pytest.approx(matmuls + attention, rel=1e-12)
    assert F.train_step_flops(CFG, 2, S) == pytest.approx(6 * (matmuls + attention), rel=1e-12)
    one = F.forward_flops(CFG, 1, 1000)      # a decode step at 1000 cached tokens
    assert one == pytest.approx(2 * 218_103_808 + 2 * 4096 * 32768 + 4 * 32 * 128 * 1001)


def test_kernel_work_by_hand():
    # causal flash forward: QK^T and PV over half of S x S, per head
    fwd = 2 * (2 * 4096 * 4096 * 128) / 2 * 32 * 2
    assert flops.flash_flops(2, 4096, 32, 128, backward=False) == pytest.approx(fwd)
    assert flops.flash_flops(2, 4096, 32, 128, backward=True) == pytest.approx(2.5 * fwd)
    # a decode step over 10k cached tokens reads K and V: 8 kv heads x 128 x 2 bytes
    assert flops.paged_decode_bytes(CFG, 10_000) == 2 * 10_000 * 8 * 128 * 2
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    assert flops.roofline_seconds(197e12, 1.0, peak) == pytest.approx(1.0)
    assert flops.roofline_seconds(1.0, 819e9, peak) == pytest.approx(1.0)

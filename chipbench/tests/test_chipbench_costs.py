"""The FLOP and byte counts against hand counts at the cells' widths."""
import json
from pathlib import Path

import pytest

from chipbench import costs, peaks

CONFIGS = Path(__file__).resolve().parents[1] / "configs"


def dims(name):
    return costs.Dims.from_config(json.loads((CONFIGS / f"{name}.json").read_text()))


def test_yi_layer_matrices():
    d = dims("yi-6b")
    # fused QKV 4096 -> 32*128 + 2*4*128, wo, fused gate/up, down
    assert d.layer_matrices() == [(4096, 5120), (4096, 4096),
                                  (4096, 22016), (11008, 4096)]
    per_layer = 4096 * 5120 + 4096 * 4096 + 4096 * 22016 + 11008 * 4096
    assert per_layer == 173_015_040
    assert d.params_per_token() == 32 * per_layer + 4096 * 64000


def test_phi3_layer_matrices():
    d = dims("phi3-medium-4k")
    assert d.layer_matrices() == [(5120, 7680), (5120, 5120),
                                  (5120, 35840), (17920, 5120)]
    assert d.vocab_rows == 32128 and d.n_layers == 40
    assert d.layer_bits == (8,) + ((4, 6, 4, 4) * 10)[:39]


def test_packed_bytes_by_bits():
    assert costs.packed_bytes(4096, 5120, 4) == 5120 * 2048 + 4 * 5120
    assert costs.packed_bytes(4096, 5120, 6) == 5120 * 4096 + 4 * 5120
    assert costs.packed_bytes(4096, 5120, 2) == 5120 * 1024 + 4 * 5120


def test_yi_decode_step_weight_bytes():
    """Every packed weight once: layer 0 at 8 bits, then 4/6/4/4 by layer
    index, and the 8-bit LM head: 3.81e9 bytes at 32 layers."""
    d = dims("yi-6b")
    flops, nbytes = costs.decode_step_cost(d, [])
    per_layer_n = 5120 + 4096 + 22016 + 4096
    lanes = {8: 1, 6: 1, 4: 2}
    weights = sum(sum(n * -(-k // lanes[b]) for k, n in d.layer_matrices())
                  for b in d.layer_bits) + 32 * 4 * per_layer_n
    head = 64000 * 4096 + 4 * 64000
    assert flops == 0 and nbytes == weights + head
    assert 3.80e9 < nbytes < 3.82e9


def test_decode_step_kv_and_flops():
    d = dims("yi-6b")
    live = [600, 1000]
    f0, b0 = costs.decode_step_cost(d, [])
    f, b = costs.decode_step_cost(d, live)
    kv_flops = 32 * sum(4 * n * 32 * 128 for n in live)
    assert f == 2 * 2 * d.params_per_token() + kv_flops
    # 4+4 bits of K and V per position, 4 KV heads of 128, 32 layers
    kv_read = 32 * sum(n * 4 * 128 for n in live)
    assert kv_read < b - b0 < kv_read * 1.2


def test_prefill_flops_by_hand():
    d = dims("phi3-medium-4k")
    t = 1400
    qkv = 2 * t * 5120 * 7680
    rest = 2 * t * (5120 * 5120 + 5120 * 35840 + 17920 * 5120)
    attn = 4 * 40 * 128 * t * (t + 1) // 2
    # the last layer's output projection, MLP and attention are not needed
    assert costs.prefill_flops(d, t) == 40 * qkv + 39 * (rest + attn)


def test_roofline_bound_names():
    v5e = peaks.peaks_for("TPU v5 lite")
    assert peaks.roofline_s(197e12, 1.0, v5e) == pytest.approx((1.0, "compute"))
    assert peaks.roofline_s(1.0, 819e9, v5e) == pytest.approx((1.0, "memory"))

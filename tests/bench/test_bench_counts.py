"""The benchmark's operation and byte counts against hand-worked shapes,
and the peaks table's refusal of an unknown device."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench import counts  # noqa: E402

OLMO = {"hidden_size": 2048, "intermediate_size": 8192, "num_hidden_layers": 16,
        "num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128,
        "vocab_size": 50304, "hidden_act": "swiglu"}


def test_lr_hvp_counts_mimic():
    # three [N, d+1] x [d+1, C] products; Xa and the row weights read once
    f, b = counts.lr_hvp(78487, 2049, 2)
    assert f == 6 * 78487 * 2049 * 2 == 1929838356
    assert b == 4 * (78487 * 2049 + 78487) == 643593400


def test_minibatch_grad_counts():
    f, b = counts.minibatch_grad(2000, 2049, 2)
    assert f == 32784000
    assert b == 4 * (2000 * 2049 + 2000 * 2 + 2000) == 16416000


def test_least_time_takes_the_larger_bound():
    pk = counts.load_peaks("TPU v5 lite")
    f, b = counts.lr_hvp(78487, 2049, 2)
    # 644 MB at 819 GB/s is 0.79 ms; 1.9 GFLOP at 197 TFLOP/s is 0.01 ms
    assert counts.least_time(f, b, pk) == pytest.approx(b / 819e9)
    assert counts.least_time(1e15, 1.0, pk) == pytest.approx(1e15 / 197e12)


def test_decoder_parameter_count_olmo_1b():
    p = counts.decoder_params(OLMO)
    # attention 4 x 2048^2, SwiGLU 3 x 2048 x 8192 per layer
    assert p["layer"] == 4 * 2048 * 2048 + 3 * 2048 * 8192 == 67108864
    assert p["layer"] * p["layers"] + p["vocab"] == 1073741824 + 103022592


def test_decode_and_paged_attention_counts():
    f, b = counts.paged_attention(OLMO, 1000)
    assert f == 4 * 16 * 128 * 1000 * 16
    # K and V of 1,000 positions, 16 heads x 128, bf16, 16 layers
    assert b == 2 * 2 * 16 * 128 * 1000 * 16 == 131072000
    fd, bd = counts.decode_token(OLMO, 1000)
    assert fd == 2 * (1073741824 + 103022592) + f and bd == b


def test_prefill_counts_causal():
    L = 776
    dense = 2 * 67108864 * 16 * L
    attn = 2 * 16 * 128 * L * (L + 1) * 16
    assert counts.prefill(OLMO, L) == dense + attn + 2 * 2048 * 50304


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        counts.load_peaks("TPU v9 imaginary")

"""The operation and byte counts against hand counts at tiny shapes."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import flops  # noqa: E402

D = dict(L=2, D=8, Hq=4, Hkv=2, hd=4, F=16, V=32)
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}


def test_layer_params():
    # q 8*4*4 + o 4*4*8 + k,v 2*8*2*4 + gate/up/down 3*8*16
    assert flops.layer_matmul_params(D) == 128 + 128 + 128 + 384


def test_decode_attention():
    # two rows attending 5 and 7 tokens: 12 tokens
    f, b = flops.decode_attention(D, 2, 12)
    assert f == 4 * 4 * 4 * 12                  # qk and pv, 2 flops each
    # K and V of 12 tokens (2 heads x 4 dims x 2 B each), q and o of 2 rows
    assert b == 12 * 2 * 2 * 4 * 2 + 2 * 2 * 4 * 4 * 2


def test_prefill_attention():
    # queries at positions 3, 4, 5 attend 4, 5, 6 keys
    f, b = flops.prefill_attention(D, 3, 3)
    assert f == 4 * 4 * 4 * (4 + 5 + 6)
    assert b == 6 * 2 * 2 * 4 * 2 + 2 * 3 * 4 * 4 * 2


def test_step_flops():
    per_tok = 2 * (2 * flops.layer_matmul_params(D) + 8 * 32)
    assert flops.decode_step_flops(D, 2, 12) == (
        2 * per_tok + 2 * flops.decode_attention(D, 2, 12)[0])
    assert flops.prefill_chunk_flops(D, 3, 3) == (
        3 * 2 * 2 * flops.layer_matmul_params(D)
        + 2 * flops.prefill_attention(D, 3, 3)[0])


def test_roofline_takes_the_larger_bound():
    assert flops.roofline_s(1000, 10, PEAKS) == pytest.approx(10.0)
    assert flops.roofline_s(10, 1000, PEAKS) == pytest.approx(100.0)

"""The GQA module's operation and byte counts against hand counts at
tiny shapes, and the shares that ``flops.py`` reads from a trace of
one device and of several."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import archlib  # noqa: E402
import flops  # noqa: E402
import harness  # noqa: E402
import tracing  # noqa: E402
from window import Ctx  # noqa: E402

gqa = harness.load_arch("gqa")

D = dict(L=2, D=8, Hq=4, Hkv=2, hd=4, F=16, V=32)
PEAKS = {"bf16_flops": 100.0, "hbm_bytes_s": 10.0}


def test_layer_params():
    # q 8*4*4 + o 4*4*8 + k,v 2*8*2*4 + gate/up/down 3*8*16
    assert gqa.layer_matmul_params(D) == 128 + 128 + 128 + 384


def test_decode_attention():
    # two rows attending 5 and 7 tokens: 12 tokens
    f, b = gqa.decode_attention(D, 2, 12)
    assert f == 4 * 4 * 4 * 12                  # qk and pv, 2 flops each
    # K and V of 12 tokens (2 heads x 4 dims x 2 B each), q and o of 2 rows
    assert b == 12 * 2 * 2 * 4 * 2 + 2 * 2 * 4 * 4 * 2


def test_prefill_attention():
    # queries at positions 3, 4, 5 attend 4, 5, 6 keys
    f, b = gqa.prefill_attention(D, 3, 3)
    assert f == 4 * 4 * 4 * (4 + 5 + 6)
    assert b == 6 * 2 * 2 * 4 * 2 + 2 * 3 * 4 * 4 * 2


def test_step_flops():
    per_tok = 2 * (2 * gqa.layer_matmul_params(D) + 8 * 32)
    assert gqa.decode_step_flops(D, 2, 12) == (
        2 * per_tok + 2 * gqa.decode_attention(D, 2, 12)[0])
    assert gqa.prefill_chunk_flops(D, 3, 3) == (
        3 * 2 * 2 * gqa.layer_matmul_params(D)
        + 2 * gqa.prefill_attention(D, 3, 3)[0])


def test_roofline_takes_the_larger_bound():
    assert archlib.roofline_s(1000, 10, PEAKS) == pytest.approx(10.0)
    assert archlib.roofline_s(10, 1000, PEAKS) == pytest.approx(100.0)


# ---------------------------------------------------------------------------
def _ctx(n_devices, decode_calls, kernel_s, kernel_n):
    """A traced slice of 2 s on ``n_devices`` devices whose decode kernel
    ran ``kernel_n`` times for ``kernel_s`` seconds in all."""
    tr = tracing.Reduced(window_s=2.0, busy_s=1.0, n_devices=n_devices,
                         op_s={gqa.DECODE_KERNEL: kernel_s},
                         op_n={gqa.DECODE_KERNEL: kernel_n})
    return Ctx(cell="tiny.chat", d=D, peaks=PEAKS, setup_s=0.0, w0=0.0,
               w1=2.0, arch=gqa, decode_calls=decode_calls, trace=tr)


def test_step_mfu_divides_by_the_traced_devices():
    """Four replicas each doing one chip's work read one chip's share,
    not four times it."""
    one = flops.step_mfu(_ctx(1, [(2, 12)], 1.0, 1))
    four = flops.step_mfu(_ctx(4, [(2, 12)] * 4, 4.0, 4))
    want = 100.0 * gqa.decode_step_flops(D, 2, 12) / (2.0 * 100.0)
    assert one == pytest.approx(want)
    assert four == pytest.approx(want)


def test_roofline_sums_time_and_needs_across_devices():
    """The trace sums a kernel's events over every device, and the
    needs sum over every replica's calls: four replicas at one chip's
    pace read one chip's share."""
    need = D["L"] * archlib.roofline_s(*gqa.decode_attention(D, 2, 12),
                                       PEAKS)
    one = flops.decode_roofline(_ctx(1, [(2, 12)], 0.5, 2))
    four = flops.decode_roofline(_ctx(4, [(2, 12)] * 4, 2.0, 8))
    assert one == pytest.approx(100.0 * need / 0.5)
    assert four == pytest.approx(one)
    dev = {f"/device:TPU:{i}": {"XLA Ops": [(gqa.DECODE_KERNEL, 0, 10**8)]}
           for i in range(4)}
    r = tracing.reduce_events(dev, [(tracing.WINDOW, 0, 10**9)])
    assert r.sum_ops(gqa.DECODE_KERNEL) == (pytest.approx(0.4), 4)

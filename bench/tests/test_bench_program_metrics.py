"""Readers of what the program names: the prefill chunk program's
device time per run, found by its module name."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import harness  # noqa: E402
import tracing  # noqa: E402
from window import Ctx  # noqa: E402


def _ctx(module_s, module_n):
    tr = tracing.Reduced(window_s=1.0, busy_s=0.9, n_devices=1,
                         module_s=module_s, module_n=module_n)
    return Ctx(cell="tiny.chat", d={}, peaks={}, setup_s=0.0, w0=0.0,
               w1=1.0, trace=tr)


def test_prefill_chunk_ms_reads_its_program_alone():
    read = harness.load_reader("prefill_chunk_ms")
    ctx = _ctx({"jit_prefill_chunk(7)": 0.048, "jit_prefill_chunk(9)": 0.016,
                "jit_prefill_chunk_seg(8)": 0.5, "jit_fused_decode(3)": 0.9},
               {"jit_prefill_chunk(7)": 3, "jit_prefill_chunk(9)": 1,
                "jit_prefill_chunk_seg(8)": 2, "jit_fused_decode(3)": 10})
    assert read(ctx) == pytest.approx(16.0)


@pytest.mark.parametrize("modules", [{}, {"jit__unknown(4)": 0.2}])
def test_prefill_chunk_ms_is_silent_without_the_name(modules):
    read = harness.load_reader("prefill_chunk_ms")
    assert read(_ctx(modules, {m: 1 for m in modules})) is None

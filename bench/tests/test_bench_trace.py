"""The trace reduction on a small recorded trace: busy union, idle
share, per-operation sums and the attribution of idle gaps to host
spans; and the reading of a real profiler trace's host spans."""
import glob
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import tracing  # noqa: E402

MS = 1_000_000     # nanoseconds


def recorded():
    """Window [0, 100) ms.  Device ops: a 0-20, b 10-30 (overlapping),
    a 50-60, c 90-120 (clipped to 90-100).  Host spans: step 0-100,
    _admit 30-45 inside it, _decode_fused 60-90."""
    dev = {"/device:TPU:0": {
        "XLA Ops": [("while", 0, 30 * MS), ("a", 0, 20 * MS),
                    ("b", 10 * MS, 30 * MS),
                    ("a", 50 * MS, 60 * MS), ("c", 90 * MS, 120 * MS)],
        "XLA Modules": [("jit__unknown(1)", 0, 30 * MS),
                        ("jit__unknown(2)", 50 * MS, 60 * MS)]}}
    host = [(tracing.WINDOW, 0, 100 * MS),
            ("engine.step", 0, 100 * MS),
            ("engine._admit", 30 * MS, 45 * MS),
            ("engine._decode_fused", 60 * MS, 90 * MS),
            ("unrelated", 0, 100 * MS)]
    return dev, host


def test_busy_union_and_idle_share():
    r = tracing.reduce_events(*recorded())
    assert r.window_s == pytest.approx(0.100)
    # busy: 0-30, 50-60, 90-100 -> 50 ms
    assert r.busy_s == pytest.approx(0.050)
    assert 1 - r.busy_s / r.window_s == pytest.approx(0.5)


def test_per_op_sums_clip_to_the_window():
    r = tracing.reduce_events(*recorded())
    assert r.op_s["a"] == pytest.approx(0.030)
    assert r.op_n["a"] == 2
    assert r.op_s["b"] == pytest.approx(0.020)
    assert r.op_s["c"] == pytest.approx(0.010)
    assert r.sum_ops("a", "b") == (pytest.approx(0.050), 3)
    assert "while" not in r.op_s          # a loop holds the ops above
    # programs are found by the ops they ran
    assert r.sum_programs("b") == (pytest.approx(0.030), 1)
    assert r.sum_programs("a") == (pytest.approx(0.040), 2)
    assert r.sum_programs("c") == (0, 0)


@pytest.mark.parametrize("text, kind", [
    ("%_paged_decode_pallas.5 = bf16[64,32,128] custom-call(s32[64,64])",
     "_paged_decode_pallas"),
    ("%fusion.96 = bf16[64,13696] fusion(bf16[10,4096,13696])", "fusion"),
    ("%copy = bf16[10,4225,64,2,128] copy(bf16[10,4225,64,2,128])", "copy"),
    ("dot_general.1", "dot_general"),
    ("a", "a")])
def test_op_kind(text, kind):
    assert tracing.op_name(text) == kind


def test_idle_gaps_go_to_the_innermost_span():
    r = tracing.reduce_events(*recorded())
    # gaps: 30-50 (middle 40: inside _admit) and 60-90 (_decode_fused)
    assert r.idle_gaps == {"engine._admit": pytest.approx(0.020),
                           "engine._decode_fused": pytest.approx(0.030)}
    assert tracing.top(r.idle_gaps, 1) == [["engine._decode_fused",
                                             pytest.approx(0.030)]]


def test_gap_outside_any_span():
    dev = {"/device:TPU:0": {"XLA Ops": [("a", 0, 10 * MS)]}}
    host = [(tracing.WINDOW, 0, 20 * MS)]
    r = tracing.reduce_events(dev, host)
    assert r.idle_gaps == {tracing.NO_SPAN: pytest.approx(0.010)}


def test_two_devices_average():
    dev = {"/device:TPU:0": {"XLA Ops": [("a", 0, 10 * MS)]},
           "/device:TPU:1": {"XLA Ops": [("a", 0, 30 * MS)]}}
    r = tracing.reduce_events(dev, [(tracing.WINDOW, 0, 40 * MS)])
    assert r.n_devices == 2
    assert r.busy_s == pytest.approx(0.020)


def test_no_window_is_an_error():
    with pytest.raises(ValueError):
        tracing.reduce_events({}, [("engine.step", 0, 1)])


def test_reads_a_real_trace(tmp_path):
    """A profiler trace taken here (CPU: no device plane) yields the
    WINDOW span and the engine spans inside it."""
    import jax
    import jax.numpy as jnp
    d = str(tmp_path)
    f = jax.jit(lambda x: x * 2)
    f(jnp.ones(4)).block_until_ready()
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation(tracing.WINDOW):
        with jax.profiler.TraceAnnotation("engine.step"):
            f(jnp.ones(4)).block_until_ready()
    jax.profiler.stop_trace()
    assert glob.glob(f"{d}/**/*.xplane.pb", recursive=True)
    lines, spans = tracing.read_xplane(d)
    names = [n for n, _, _ in spans]
    assert tracing.WINDOW in names and "engine.step" in names
    r = tracing.reduce_events(lines, spans)
    assert r.window_s > 0

"""The harness end to end on the CPU at a tiny size: the result line's
shape, the check of outputs, and that a broken timed path reads as not
correct."""
import functools
import io
import json
from contextlib import redirect_stderr, redirect_stdout

import jax
import jax.numpy as jnp
import pytest

import bench_tiny
import run


@pytest.mark.parametrize("mix", ["chat", "unique"])
def test_end_to_end_result(mix):
    out = bench_tiny.run_tiny(mix)
    assert out["correct"] is True
    assert list(out)[-1] == "compared"
    assert set(out) >= {"correct", "attempted", "failed", "metrics",
                        "device"}
    assert out["attempted"] > 0 and out["failed"] == 0
    names = set(out["metrics"])
    assert {"itl_p95_ms", "setup_s"} <= names
    assert ("ttft_p90_ms" in names) == (mix == "chat")
    for m in out["metrics"].values():
        assert m["value"] > 0
    gap = out["compared"]["logit_gap"]
    assert gap["value"] <= gap["limit"]


def test_traced_run_reports_per_layer_metrics():
    out = bench_tiny.run_tiny("chat", trace=1)
    assert out["correct"] is True
    names = set(out["metrics"])
    assert {"decode_batch_mean", "prompt_cached_share",
            "admit_lag_ms_p90"} <= names
    assert "ttft_p90_ms" not in names
    # no device in a CPU trace: device readings are left out, never 0
    assert "device_idle_share" not in names
    assert {"busy_s", "window_s"} <= set(out["device"])
    assert set(out["breakdown"]) == {"device_ops", "idle_gaps"}


def _alter_tokens(eng):
    """A token altered where it is produced: every sampled token + 1."""
    fused = eng._fused_decode

    def broken(*a):
        toks, state = fused(*a)
        return (toks + 1) % eng.cfg.vocab_size, state
    broken._cache_size = fused._cache_size
    eng._fused_decode = broken


def _state_unchanged(eng):
    """A step that returns its state unchanged: the fused decode step
    hands back the KV pools and lengths it was given."""
    from repro.serving import engine as eng_mod

    def step(model, backend, params, state, *a):
        toks, _ = eng_mod._fused_paged_step(model, backend, params,
                                            dict(state), *a)
        return toks, state
    eng._fused_decode = jax.jit(functools.partial(
        step, eng.model, eng.kernel_backend))


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged])
def test_broken_timed_path_is_not_correct(fault):
    out = bench_tiny.run_tiny("chat", fault=fault)
    assert out["correct"] is False
    gap = out["compared"]["logit_gap"]
    assert gap["value"] > gap["limit"]


def test_control_fails_the_limit():
    """The reference one precision below bfloat16 (fp8 weights), put in
    the program's place, reads above the limit that the program meets."""
    out = bench_tiny.run_tiny("chat", control=True)
    assert out["compared"]["logit_gap"]["value"] <= bench_tiny.LIMIT
    assert out["control_gap"] > bench_tiny.LIMIT


def test_no_chip_no_result():
    """On the CPU the command exits non-zero and prints no result."""
    so, se = io.StringIO(), io.StringIO()
    with redirect_stdout(so), redirect_stderr(se):
        rc = run.main(["--workload", "glm4-9b.chat", "--seed", "1",
                       "--seconds", "1", "--trace", "0"])
    assert rc != 0
    for line in so.getvalue().splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
    assert jnp is not None

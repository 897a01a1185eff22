"""The configuration names its architecture, and the harness finds the
module by that name: weights, reference, costs and kernel names all
come from it, so a new architecture is new files only."""
import json

import pytest

import bench_tiny
import harness
import tracing
from window import Ctx

ARCH_TESTS = bench_tiny.HERE / "arch"
COSTS = ("decode_attention", "prefill_attention", "decode_step_flops",
         "prefill_chunk_flops")


@pytest.fixture
def probe(monkeypatch):
    monkeypatch.setattr(harness, "ARCH_DIRS",
                        harness.ARCH_DIRS + [ARCH_TESTS])
    mod = harness.load_arch("probe")
    mod.calls.clear()
    return mod


def _traced_ctx(arch, kernel_names):
    """A traced slice of one device in which the two kernels named ran,
    inside one program run each, and the engine's calls were counted."""
    dec, pre = kernel_names
    ms = 1_000_000
    dev = {"/device:TPU:0": {
        "XLA Ops": [(dec, 0, 10 * ms), (pre, 20 * ms, 30 * ms)],
        "XLA Modules": [("jit_fused_decode(1)", 0, 12 * ms),
                        ("jit_prefill_chunk(2)", 20 * ms, 32 * ms)]}}
    tr = tracing.reduce_events(dev, [(tracing.WINDOW, 0, 100 * ms)])
    return Ctx(cell="tiny.chat", d=arch.dims(bench_tiny.make_spec("chat")[
        "config"]["model"]), peaks=bench_tiny.PEAKS, setup_s=0.0, w0=0.0,
        w1=0.1, arch=arch, decode_calls=[(2, 300)],
        prefill_calls=[(0, 64)], trace=tr)


def test_run_goes_through_the_named_architecture(probe):
    spec = bench_tiny.make_spec("chat")
    spec["config"] = dict(spec["config"], arch="probe")
    out = bench_tiny.run_tiny("chat", spec=spec)
    assert out["correct"] is True
    assert probe.calls["dims"] >= 1 and probe.calls["init_weights"] == 1
    assert probe.calls["logits"] >= 1          # the check's reference
    readers = ("decode_step_ms", "decode_attn_roofline",
               "prefill_attn_roofline.chat", "step_mfu.itl")
    ctx = _traced_ctx(probe, (probe.DECODE_KERNEL, probe.PREFILL_KERNEL))
    got = {m: harness.load_reader(m)(ctx) for m in readers}
    assert all(v is not None and v > 0 for v in got.values()), got
    assert all(probe.calls[c] >= 1 for c in COSTS), probe.calls
    # the kernels are the module's: the GQA names read nothing here
    gqa = harness.load_arch("gqa")
    other = _traced_ctx(probe, (gqa.DECODE_KERNEL, gqa.PREFILL_KERNEL))
    assert all(harness.load_reader(m)(other) is None for m in readers[:3])


def test_an_architecture_without_a_kernel_reads_nothing(probe,
                                                        monkeypatch):
    monkeypatch.setattr(probe, "DECODE_KERNEL", None)
    ctx = _traced_ctx(probe, ("probe_decode_kernel",
                              probe.PREFILL_KERNEL))
    assert harness.load_reader("decode_step_ms")(ctx) is None
    assert harness.load_reader("decode_attn_roofline")(ctx) is None
    assert harness.load_reader("prefill_attn_roofline.chat")(ctx) > 0


def test_a_configuration_without_arch_is_refused():
    spec = bench_tiny.make_spec("chat")
    spec["config"] = {k: v for k, v in spec["config"].items()
                      if k != "arch"}
    with pytest.raises(KeyError, match="names no architecture"):
        bench_tiny.run_tiny("chat", spec=spec)
    with pytest.raises(KeyError, match="no architecture module"):
        harness.load_arch("no_such_arch")


def test_every_benchmark_configuration_names_a_module():
    bench = harness.load_benchmark()
    for c in bench["configs"]:
        conf = json.loads((harness.ROOT / c["file"]).read_text())
        arch = harness.config_arch(conf)
        d = arch.dims(conf["model"])
        assert {"L", "V"} <= set(d)


def test_family_reaches_the_model_config():
    conf = {"name": "h", "model": dict(
        bench_tiny.make_spec("chat")["config"]["model"], family="hybrid",
        ssm_state=16, attn_every=2)}
    assert harness.model_config(conf).family == "hybrid"
    conf["model"].pop("family")
    assert harness.model_config(conf).family == "decoder"


def test_run_refuses_chips_without_replicas():
    spec = bench_tiny.make_spec("chat")
    spec["workload"] = dict(spec["workload"], chips=2)
    with pytest.raises(ValueError, match="names its replicas"):
        bench_tiny.run_tiny("chat", spec=spec)

"""Mosaic compiles of the serving kernels at published widths, for a
described (not attached) TPU v5e: what the chip's compiler refuses —
a vector-layout cast it cannot lower, more scoped VMEM than a kernel may
use — fails here, without a chip.  Nothing runs; these tests say
nothing about results or speed.

The topology is described inside a fixture, never while a module is
imported: only one process may load the TPU library at a time, and the
worker that runs this file keeps it until it exits."""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.configs.paper_models import DEEPSEEK_V3
from repro.kernels.mla_paged_decode import mla_paged_decode
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_int8)
from repro.kernels.paged_prefill import (mla_paged_prefill_segments,
                                         paged_prefill_attention,
                                         paged_prefill_segments)

BATCH, PAGE, PAGES, POOL, CHUNK = 8, 64, 32, 256, 64   # engine defaults
LAYERS = 4             # the GQA kernels read one layer of a stacked pool
GQA_ARCHS = ["llama3.2-1b", "glm4-9b"]


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2.  A described-chip compile cannot
    be read back from JAX's persistent cache without a chip, so the
    cache is off while these tests run."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _compile(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text        # the Mosaic kernel is there


def _gqa(arch):
    cfg = get_config(arch)
    return cfg.n_heads, cfg.n_kv_heads, cfg.hd


@pytest.mark.parametrize("arch", GQA_ARCHS)
def test_paged_decode_compiles(arch, one_chip):
    hq, hkv, hd = _gqa(arch)
    _compile(lambda q, k, v, bt, ln, layer: paged_decode_attention(
        q, k, v, bt, ln, layer, interpret=False), one_chip,
        ((BATCH, hq, hd), jnp.bfloat16),
        ((LAYERS, POOL, PAGE, hkv, hd), jnp.bfloat16),
        ((LAYERS, POOL, PAGE, hkv, hd), jnp.bfloat16),
        ((BATCH, PAGES), jnp.int32), ((BATCH,), jnp.int32),
        ((), jnp.int32))


def _pool_program(which, sharding, monkeypatch):
    """The compiled fused decode step or prefill chunk of a 2-layer model
    at glm4-9b's widths over a pool of 2,049 pages (16 slots of 8,192
    tokens), with the Pallas kernels; and the pool's shape."""
    import dataclasses
    import functools

    from repro.kernels import backend as kb
    from repro.models.model import build_model
    from repro.serving import engine as eng_mod

    monkeypatch.setattr(kb, "on_tpu", lambda: True)
    cfg = dataclasses.replace(get_config("glm4-9b"), n_layers=2)
    model = build_model(cfg)
    slots, pages = 16, 128
    pool = (cfg.n_layers, 1 + slots * pages, PAGE, cfg.n_kv_heads, cfg.hd)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    params = jax.tree.map(lambda x: sds(x.shape, x.dtype),
                          model.abstract_params())
    pools = {k: sds(pool, jnp.bfloat16) for k in ("k_pages", "v_pages")}
    if which == "fused_decode":
        fn = jax.jit(functools.partial(eng_mod._fused_paged_step, model,
                                       "pallas"), donate_argnums=(1,))
        rows = sds((slots,), jnp.int32)
        low = fn.lower(params, dict(pools, block_tables=sds(
            (slots, pages), jnp.int32), lengths=rows), rows, rows,
            sds((2,), jnp.uint32), sds((slots,), jnp.float32), rows,
            sds((slots,), jnp.float32))
    else:
        fn = jax.jit(functools.partial(model.prefill_chunk,
                                       backend="pallas"))
        low = fn.lower(params, dict(pools, block_table=sds(
            (1, pages), jnp.int32)), sds((1, CHUNK), jnp.int32),
            sds((1,), jnp.int32))
    return low.compile(), pool


@pytest.mark.parametrize("which", ["fused_decode", "prefill_chunk"])
def test_gqa_programs_read_the_pool_in_place(which, one_chip, monkeypatch):
    """The GQA decode step and prefill chunk hand the kernels the whole
    [L, N, page, Hkv, hd] pool and a layer index: no layer is sliced out
    of the stack (a 67 MB copy per layer here) or stacked back.  So the
    program's temporaries stay below one layer's K slice, and nothing
    but the in-place scatter of the new tokens (a fusion around a
    scatter) writes an array of the pool's shape or a layer's."""
    import math
    import re
    compiled, pool = _pool_program(which, one_chip, monkeypatch)
    layer_bytes = math.prod(pool[1:]) * jnp.dtype(jnp.bfloat16).itemsize
    assert compiled.memory_analysis().temp_size_in_bytes < layer_bytes
    text = compiled.as_text()
    bodies = {m.group(1): m.group(2) for m in re.finditer(
        r"^%(\S+) [^\n]*\{\n(.*?)^\}", text, re.M | re.S)}
    shapes = {",".join(map(str, pool)), ",".join(map(str, pool[1:]))}
    moved = []
    for line in text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(",
                     line)
        if not m or m.group(1) not in shapes or m.group(2) in (
                "parameter", "get-tuple-element", "bitcast", "scatter",
                "custom-call"):
            continue
        called = re.search(r"calls=%([\w.-]+)", line)
        if m.group(2) == "fusion" and called and \
                " scatter(" in bodies.get(called.group(1), ""):
            continue
        moved.append(line[:160])
    assert not moved, moved


@pytest.mark.parametrize("arch", GQA_ARCHS)
def test_paged_decode_int8_compiles(arch, one_chip):
    hq, hkv, hd = _gqa(arch)
    _compile(lambda q, k, v, ks, vs, bt, ln: paged_decode_attention_int8(
        q, k, v, ks, vs, bt, ln, interpret=False), one_chip,
        ((BATCH, hq, hd), jnp.bfloat16),
        ((POOL, PAGE, hkv, hd), jnp.int8),
        ((POOL, PAGE, hkv, hd), jnp.int8),
        ((POOL, PAGE, hkv, 1), jnp.bfloat16),
        ((POOL, PAGE, hkv, 1), jnp.bfloat16),
        ((BATCH, PAGES), jnp.int32), ((BATCH,), jnp.int32))


@pytest.mark.parametrize("arch", GQA_ARCHS)
@pytest.mark.parametrize("kernel,positions", [
    (paged_prefill_attention, (BATCH,)),          # scalar offsets
    (paged_prefill_segments, (BATCH, CHUNK)),     # per-query positions
], ids=["offsets", "segments"])
def test_paged_prefill_compiles(arch, kernel, positions, one_chip):
    hq, hkv, hd = _gqa(arch)
    _compile(lambda q, kc, vc, kp, vp, bt, pos, layer: kernel(
        q, kc, vc, kp, vp, bt, pos, layer, interpret=False), one_chip,
        ((BATCH, CHUNK, hq, hd), jnp.bfloat16),
        ((BATCH, CHUNK, hkv, hd), jnp.bfloat16),
        ((BATCH, CHUNK, hkv, hd), jnp.bfloat16),
        ((LAYERS, POOL, PAGE, hkv, hd), jnp.bfloat16),
        ((LAYERS, POOL, PAGE, hkv, hd), jnp.bfloat16),
        ((BATCH, PAGES), jnp.int32), (positions, jnp.int32),
        ((), jnp.int32))


def test_mla_decode_compiles(one_chip):
    hq, dl, dr = DEEPSEEK_V3.n_heads, DEEPSEEK_V3.d_latent, DEEPSEEK_V3.d_rope
    _compile(lambda ql, qr, lp, bt, ln: mla_paged_decode(
        ql, qr, lp, bt, ln, d_latent=dl, scale=(128 + dr) ** -0.5,
        interpret=False), one_chip,
        ((BATCH, hq, dl), jnp.bfloat16), ((BATCH, hq, dr), jnp.bfloat16),
        ((POOL, PAGE, dl + dr), jnp.bfloat16),
        ((BATCH, PAGES), jnp.int32), ((BATCH,), jnp.int32))


def test_mla_prefill_segments_compiles(one_chip):
    """At chunk 64 one step's q/out blocks and f32 state for all 128
    heads exceed the 16 MiB scoped VMEM; the kernel tiles heads."""
    hq, dl, dr = DEEPSEEK_V3.n_heads, DEEPSEEK_V3.d_latent, DEEPSEEK_V3.d_rope
    _compile(lambda ql, qr, lc, lp, bt, cp: mla_paged_prefill_segments(
        ql, qr, lc, lp, bt, cp, d_latent=dl, scale=(128 + dr) ** -0.5,
        interpret=False), one_chip,
        ((BATCH, CHUNK, hq, dl), jnp.bfloat16),
        ((BATCH, CHUNK, hq, dr), jnp.bfloat16),
        ((BATCH, CHUNK, dl + dr), jnp.bfloat16),
        ((POOL, PAGE, dl + dr), jnp.bfloat16),
        ((BATCH, PAGES), jnp.int32), ((BATCH, CHUNK), jnp.int32))


@pytest.mark.parametrize("k,n", [(2048, 1408), (1408, 2048)],
                         ids=["gate_up", "down"])
def test_expert_gmm_compiles(k, n, one_chip):
    """DeepSeek-V2-Lite's experts at published widths: 64 a layer, six
    MoE layers stacked, 64 rows of top-6 assignments; the kernel reads
    one layer's experts out of the stack by group offset."""
    from repro.kernels.ops import _expert_gmm_pallas
    _compile(lambda x, w, gs, layer: _expert_gmm_pallas(
        x, w, gs, layer, interpret=False), one_chip,
        ((64 * 6, k), jnp.bfloat16), ((6, 64, k, n), jnp.bfloat16),
        ((64,), jnp.int32), ((), jnp.int32))


@pytest.fixture(scope="module")
def four_chips(one_chip):
    """The four chips of the described v5e:2x2 as a (data 1, model 4)
    mesh."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    return Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"))


@pytest.mark.parametrize("rules", ["EP_RULES", "BASE_RULES"])
def test_moe_layer_on_a_mesh_gathers_no_expert_weights(rules, four_chips,
                                                       monkeypatch):
    """granite-moe-1b-a400m's MoE layer on four chips, experts split
    over the model axis (EP) or each expert's hidden slice split (TP):
    the grouped-matmul kernel runs on each chip's own weights, and no
    all-gather brings a chip's share of a layer's experts or more."""
    import re
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.distributed import sharding as shlib
    from repro.kernels import ops
    from repro.models import moe

    # the Mosaic kernel, which this CPU process would not pick itself
    monkeypatch.setattr(ops, "expert_matmul", lambda a, w, gs, layer, **_:
                        ops._expert_gmm_pallas(a, w, gs, layer,
                                               interpret=False))

    cfg = get_config("granite-moe-1b-a400m")
    shd = shlib.MeshSharding(four_chips, getattr(shlib, rules))
    lm, e, d, f = cfg.n_layers, cfg.n_experts, cfg.d_model, cfg.moe_ff
    specs = moe.moe_pspecs(cfg)
    t = 16 * CHUNK

    def arg(shape, dtype, axes):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=NamedSharding(
            four_chips, shd.pspec(axes)))
    p = {"router": arg((lm, d, e), jnp.bfloat16,
                       ("layers",) + specs["router"].axes),
         **{w: arg((lm,) + specs[w].shape, jnp.bfloat16,
                   ("layers",) + specs[w].axes)
            for w in ("w_gate", "w_up", "w_down")}}
    x = arg((t, d), jnp.bfloat16, ("batch", None))
    fn = jax.jit(lambda p, x, layer: moe.moe_ffn(p, x, cfg, layer,
                                                 shd=shd)[0])
    text = fn.lower(p, x, jax.ShapeDtypeStruct(
        (), jnp.int32, sharding=NamedSharding(four_chips, P()))
    ).compile().as_text()
    assert "tpu_custom_call" in text
    share = e * d * f // 4
    for line in text.splitlines():
        if "all-gather" in line and "=" in line:
            dims = re.search(r"=\s*\(?\w+\[([\d,]*)\]", line)
            n = 1
            for v in (dims.group(1).split(",") if dims and dims.group(1)
                      else []):
                n *= int(v)
            assert n < share, line

"""Compile a latent-attention (MLA) configuration's serving programs for
a described TPU v5e and print what the compiler says about their
memory.  Nothing runs: the chip is described, not attached, so this
gives bytes and never times.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/compile_v5e_mla.py deepseek-v2-lite

The MLA counterpart of ``compile_v5e.py`` (which builds a GQA pool):
it compiles, at the configuration's widths, depth and engine sizes, the
fused decode step over the whole latent pool and one 64-token prefill
chunk (the contiguous and the segment program), each with the compiled
Pallas kernels, and prints ``memory_analysis()`` for each beside the
bytes of the parameters and the pool, and the names of the kernels each
program calls (the op names a device trace shows).  Used to size
``kv_budget_bytes``.
"""
from __future__ import annotations

import argparse
import functools
import json
import math
import os
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

import harness  # noqa: E402


def kernels(hlo: str) -> list:
    """Names of the Mosaic custom calls in a compiled program's text."""
    names = re.findall(r"%([\w.\-]+) = [^\n]*custom-call\([^\n]*"
                       r"custom_call_target=\"tpu_custom_call\"", hlo)
    return sorted({n.rsplit(".", 1)[0] if n.rsplit(".", 1)[-1].isdigit()
                   else n for n in names})


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("config")
    args = ap.parse_args(argv)

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.kernels import backend as kb
    from repro.serving import engine as eng_mod

    jax.config.update("jax_enable_compilation_cache", False)
    # the described chip is a TPU: let the program pick its Pallas path
    kb.on_tpu = lambda: True
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])

    conf = harness.load_config(args.config)
    cfg = harness.model_config(conf)
    ecfg = harness.engine_config(conf)
    if cfg.attention_variant != "mla":
        raise SystemExit(f"{args.config} has no latent attention: "
                         "use compile_v5e.py")
    from repro.models.model import build_model
    from repro.serving.scheduler import Scheduler, SchedulerConfig
    model = build_model(cfg)
    n_slots = Scheduler(cfg, SchedulerConfig(
        kv_budget_bytes=ecfg.kv_budget_bytes, max_len=ecfg.max_len)).n_slots

    def sds(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one)

    params = jax.tree.map(sds, model.abstract_params())
    # the pool's shape as PagedKVCache builds it, without allocating it
    pages_per_slot = -(-ecfg.max_len // ecfg.page_tokens)
    n_pages = 1 + n_slots * pages_per_slot + max(8, 2 * pages_per_slot)
    pool = (cfg.n_layers, n_pages, ecfg.page_tokens,
            cfg.d_latent + cfg.d_rope)
    pools = {"latent_pages": jax.ShapeDtypeStruct(pool, jnp.bfloat16,
                                                  sharding=one)}
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one)
    f32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.float32,
                            sharding=one)
    if cfg.n_experts:
        pools["expert_tokens"] = i32((cfg.n_moe_layers,
                                      cfg.layout_n_experts))
    param_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(params))
    pool_bytes = jnp.dtype(jnp.bfloat16).itemsize * math.prod(pool)
    out = {"config": conf["name"], "n_slots": n_slots, "n_pages": n_pages,
           "param_bytes": int(param_bytes), "pool_bytes": int(pool_bytes)}

    dstate = dict(pools, block_tables=i32((n_slots, pages_per_slot)),
                  lengths=i32((n_slots,)))
    fused = jax.jit(functools.partial(eng_mod._fused_paged_step, model,
                                      "pallas"), donate_argnums=(1,))
    key = jax.ShapeDtypeStruct((2,), jnp.uint32, sharding=one)
    progs = {"fused_decode": fused.lower(
        params, dstate, i32((n_slots,)), i32((n_slots,)), key,
        f32((n_slots,)), i32((n_slots,)), f32((n_slots,)))}
    C = ecfg.prefill_chunk_tokens
    cstate = dict(pools, block_table=i32((1, pages_per_slot)))
    if cfg.n_experts:
        cstate["n_valid"] = i32(())
    progs["prefill_chunk"] = jax.jit(functools.partial(
        model.prefill_chunk, backend="pallas")).lower(
        params, cstate, i32((1, C)), i32((1,)))
    progs["prefill_chunk_seg"] = jax.jit(functools.partial(
        model.prefill_chunk_seg, backend="pallas")).lower(
        params, cstate, i32((1, C)), i32((1, C)))
    for name, low in progs.items():
        comp = low.compile()
        m = comp.memory_analysis()
        out[name] = {k: int(getattr(m, k)) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "alias_size_in_bytes", "temp_size_in_bytes")}
        out[name]["kernels"] = kernels(comp.as_text())
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

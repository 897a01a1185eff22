"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

Proves the distribution config is coherent without hardware: every cell
must lower, SPMD-partition and compile for the 16x16 single-pod mesh and
the 2x16x16 multi-pod mesh; ``memory_analysis()`` proves per-chip fit and
``cost_analysis()`` + HLO collective parsing feed the roofline table
(EXPERIMENTS.md §Roofline).

Usage:
    PYTHONPATH=src python -m repro.launch.dryrun --arch llama3.2-1b \
        --shape decode_32k [--multi-pod] [--all] [--out results.json]
"""
import argparse
import json
import os
import re
import sys
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.config import (KIND_DECODE, KIND_PREFILL, KIND_TRAIN, SHAPES,
                          ModelConfig, ShapeConfig, shape_applicable)
from repro.configs import REGISTRY, get_config
from repro.distributed import sharding as shlib
from repro.launch import hlocost
from repro.launch.mesh import (HBM_BW, ICI_BW, PEAK_FLOPS_BF16,
                               make_production_mesh)
from repro.models.model import build_model
from repro.training import train as train_mod
from repro.training.optimizer import AdamWConfig

# ---------------------------------------------------------------------------
# HLO collective parsing
# ---------------------------------------------------------------------------
_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "u32": 4, "s32": 4,
                "u64": 8, "s64": 8, "u16": 2, "s16": 2, "u8": 1, "s8": 1,
                "pred": 1, "c64": 8, "c128": 16}

_COLL_RE = re.compile(
    r"=\s+(?:\([^)]*\)|(\w+)\[([\d,]*)\][^ ]*)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(", )
_TUPLE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=\{?\{([\d,]+)\}")


def _shape_bytes(dtype: str, dims: str) -> float:
    b = _DTYPE_BYTES.get(dtype, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return float(n * b)


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Per-device wire-byte estimate per collective kind.

    Result shapes in SPMD HLO are per-device shards.  Wire factors (ring
    algorithms over a group of g): all-gather moves (g-1)/g of the result;
    all-reduce 2(g-1)/g of the tensor; reduce-scatter (g-1)/g of the
    input (~= result*(g-1)); all-to-all / collective-permute ~= result.
    """
    out: Dict[str, Dict[str, float]] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(3)
        if "-done" in line.split("=")[1][:40]:
            continue
        # result bytes (sum over tuple elements if tuple-shaped)
        lhs = line.split(" = ", 1)[1]
        head = lhs.split("(", 1)[0]
        rbytes = sum(_shape_bytes(d, s) for d, s in _TUPLE_RE.findall(head))
        g = 2
        gm = _GROUPS_RE.search(line)
        if gm:
            g = max(2, len(gm.group(1).split(",")))
        if kind == "all-gather":
            wire = rbytes * (g - 1) / g
        elif kind == "all-reduce":
            wire = 2.0 * rbytes * (g - 1) / g
        elif kind == "reduce-scatter":
            wire = rbytes * (g - 1)
        else:
            wire = rbytes
        slot = out.setdefault(kind, {"count": 0, "bytes": 0.0, "wire": 0.0})
        slot["count"] += 1
        slot["bytes"] += rbytes
        slot["wire"] += wire
    return out


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------
OPTS = {"sp": False, "defer_grad": False, "bf16_scores": False,
        "bf16_grads": False, "unroll": 1, "ep": False,
        "kv_dtype": "bfloat16"}


def _rules_for(shape: ShapeConfig, cfg: Optional[ModelConfig] = None):
    if OPTS["ep"] and cfg is not None and cfg.n_experts > 0:
        return shlib.EP_RULES          # MoE archs only
    if shape.name.startswith("long"):
        return shlib.LONG_CONTEXT_RULES
    if OPTS["sp"] and shape.kind == KIND_TRAIN:
        return shlib.SP_RULES
    return shlib.BASE_RULES


def lower_cell(cfg: ModelConfig, shape: ShapeConfig, mesh,
               *, donate: bool = True, pad_heads: bool = False):
    """Returns (lowered, aux) for one (arch x shape) on `mesh`."""
    if pad_heads:
        from dataclasses import replace as _replace
        from repro.config import padded_head_layout
        padded = padded_head_layout(cfg, mesh.shape.get("model", 1))
        if padded:
            cfg = _replace(cfg, internal_pad_q_heads=padded)
    if OPTS["ep"] and cfg.n_experts:
        from dataclasses import replace as _replace
        tp = mesh.shape.get("model", 1)
        pe = ((cfg.n_experts + tp - 1) // tp) * tp
        cfg = _replace(cfg, internal_pad_experts=pe)
    rules = _rules_for(shape, cfg)
    shd = shlib.MeshSharding(mesh, rules)
    # steady-state decode benchmark: position-aligned batch (the ragged
    # path is exercised by the live engine + tests; TPU ragged fast path
    # is the paged-attention Pallas kernel)
    model = build_model(cfg, shd, aligned_decode=True,
                        scan_unroll=OPTS["unroll"],
                        kv_dtype=OPTS["kv_dtype"])
    p_sh = shlib.tree_shardings(model.specs, mesh, rules)
    aparams = model.abstract_params()
    ins = model.input_specs(shape)
    in_batch_sh = shlib.batch_shardings(ins, mesh, rules)

    if shape.kind == KIND_TRAIN:
        n_micro = train_mod.pick_n_microbatches(
            cfg, shape, mesh.shape.get("data", 1)
            * mesh.shape.get("pod", 1),
            sp_degree=mesh.shape.get("model", 1) if OPTS["sp"] else 1)
        step = train_mod.make_train_step(
            model, n_micro=n_micro, defer_grad_sync=OPTS["defer_grad"],
            bf16_grad_sync=OPTS["bf16_grads"])
        opt_sh = train_mod.train_shardings(model, mesh, ins,
                                           rules=rules).opt
        aopt = train_mod.abstract_opt_state(model)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step,
                in_shardings=(p_sh, opt_sh, in_batch_sh),
                out_shardings=(p_sh, opt_sh, None),
                donate_argnums=(0, 1) if donate else (),
            ).lower(aparams, aopt, ins)
        return lowered, {"n_micro": n_micro, "entry": "train_step"}

    if shape.kind == KIND_PREFILL:
        state_sh = shlib.tree_shardings(
            model.decode_state_specs(shape.global_batch, shape.seq_len),
            mesh, rules)
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                model.prefill,
                in_shardings=(p_sh, in_batch_sh),
                out_shardings=(None, state_sh),
            ).lower(aparams, ins)
        return lowered, {"entry": "prefill"}

    # decode
    astate = model.abstract_decode_state(shape.global_batch, shape.seq_len)
    state_sh = shlib.tree_shardings(
        model.decode_state_specs(shape.global_batch, shape.seq_len),
        mesh, rules)
    tok_sh = shlib.batch_shardings(
        jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32), mesh, rules)
    with jax.set_mesh(mesh):
        lowered = jax.jit(
            model.decode_step,
            in_shardings=(p_sh, state_sh, tok_sh),
            out_shardings=(None, state_sh),
            donate_argnums=(1,) if donate else (),
        ).lower(aparams, astate,
                jax.ShapeDtypeStruct((shape.global_batch,), jnp.int32))
    return lowered, {"entry": "serve_step(decode)"}


# ---------------------------------------------------------------------------
# roofline terms
# ---------------------------------------------------------------------------
def roofline(cfg: ModelConfig, shape: ShapeConfig, compiled,
             summary, n_devices: int) -> Dict[str, Any]:
    ca = compiled.cost_analysis() or {}
    flops = summary.flops                        # per-device, trip-adjusted
    byts = summary.bytes_native                  # TPU-native bf16 widths
    wire = summary.wire_bytes
    colls = summary.collectives
    t_comp = flops / PEAK_FLOPS_BF16
    t_mem = byts / HBM_BW
    t_coll = wire / ICI_BW
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    n = cfg.active_param_count()
    if shape.kind == KIND_TRAIN:
        model_flops = 6.0 * n * shape.tokens
    elif shape.kind == KIND_PREFILL:
        model_flops = 2.0 * n * shape.tokens
    else:
        model_flops = 2.0 * n * shape.global_batch
    model_flops_dev = model_flops / n_devices
    return {
        "flops_per_dev": flops,
        "bytes_per_dev": byts,
        "bytes_per_dev_raw": summary.bytes_accessed,
        "wire_bytes_per_dev": wire,
        "t_compute": t_comp, "t_memory": t_mem, "t_collective": t_coll,
        "bottleneck": bottleneck,
        "model_flops_per_dev": model_flops_dev,
        "useful_flops_frac": (model_flops_dev / flops) if flops else 0.0,
        "step_time_bound": max(t_comp, t_mem, t_coll),
        "roofline_frac": (min(1.0, model_flops_dev / PEAK_FLOPS_BF16
                              / max(t_comp, t_mem, t_coll))
                          if max(t_comp, t_mem, t_coll) > 0 else 0.0),
        "collectives": colls,
        "trip_counts": summary.trip_counts,
        "xla_cost_analysis": {"flops": float(ca.get("flops", 0.0)),
                              "bytes": float(ca.get("bytes accessed", 0.0))},
    }


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             verbose: bool = True,
             pad_heads: bool = False) -> Optional[Dict[str, Any]]:
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    if not ok:
        if verbose:
            print(f"SKIP {arch} x {shape_name}: {why}")
        return None
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.time()
    lowered, aux = lower_cell(cfg, shape, mesh, pad_heads=pad_heads)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0
    mem = compiled.memory_analysis()
    hlo = compiled.as_text()
    summary = hlocost.analyze(hlo)
    rf = roofline(cfg, shape, compiled, summary, n_dev)
    result = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "entry": aux.get("entry"),
        "n_micro": aux.get("n_micro"),
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "mem": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_hint": mem.argument_size_in_bytes
                         + mem.temp_size_in_bytes
                         + mem.output_size_in_bytes
                         - mem.alias_size_in_bytes,
        },
        **rf,
    }
    if verbose:
        print(f"OK {arch} x {shape_name} [{result['mesh']}] "
              f"compile={t_compile:.1f}s "
              f"flops/dev={rf['flops_per_dev']:.3e} "
              f"bytes/dev={rf['bytes_per_dev']:.3e} "
              f"wire/dev={rf['wire_bytes_per_dev']:.3e} "
              f"bottleneck={rf['bottleneck']} "
              f"roofline={rf['roofline_frac']:.2%}")
        print(f"   mem/dev: args={mem.argument_size_in_bytes/1e9:.2f}GB "
              f"temp={mem.temp_size_in_bytes/1e9:.2f}GB "
              f"aliased={mem.alias_size_in_bytes/1e9:.2f}GB")
    return result


def main(argv=None) -> int:
    # 512 virtual CPU devices for the production meshes; set before the
    # first device query initializes the backend
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count"
                               "=512")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--pad-heads", action="store_true",
                    help="pad q heads per GQA group to divide TP "
                         "(perf optimization, §Perf)")
    ap.add_argument("--sp", action="store_true",
                    help="sequence-parallel residual stream (train)")
    ap.add_argument("--defer-grad", action="store_true",
                    help="single deferred grad all-reduce per step")
    ap.add_argument("--bf16-scores", action="store_true",
                    help="bf16 attention score buffers")
    ap.add_argument("--bf16-grads", action="store_true",
                    help="bf16 gradient all-reduce (half grad wire)")
    ap.add_argument("--int8-kv", action="store_true",
                    help="int8 KV cache with per-token-head scales "
                         "(paper §VI quantization compatibility)")
    ap.add_argument("--static-causal", action="store_true",
                    help="unrolled causal q-chunks (halves attention "
                         "flops vs masked rectangle)")
    ap.add_argument("--ep", action="store_true",
                    help="expert parallelism: shard (padded) experts "
                         "over the model axis")
    ap.add_argument("--unroll", type=int, default=1,
                    help="layer-scan unroll factor (reduces in-loop "
                         "collective count)")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    OPTS["sp"] = args.sp
    OPTS["defer_grad"] = args.defer_grad
    OPTS["bf16_grads"] = args.bf16_grads
    OPTS["unroll"] = args.unroll
    OPTS["ep"] = args.ep
    if args.int8_kv:
        OPTS["kv_dtype"] = "int8"
    if args.bf16_scores:
        from repro.models import attention as _attn
        _attn.SCORES_BF16 = True
    if args.static_causal:
        from repro.models import attention as _attn
        _attn.STATIC_CAUSAL = True
    archs = list(REGISTRY) if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    results, failures = [], []
    for mp in meshes:
        for arch in archs:
            for shape in shapes:
                try:
                    r = run_cell(arch, shape, multi_pod=mp,
                                 pad_heads=args.pad_heads)
                    if r:
                        results.append(r)
                except Exception as e:   # noqa: BLE001
                    failures.append((arch, shape, mp, repr(e)[:500]))
                    print(f"FAIL {arch} x {shape} multi_pod={mp}: "
                          f"{repr(e)[:300]}", file=sys.stderr)
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"results": results,
                       "failures": failures}, f, indent=1)
        print(f"wrote {args.out} ({len(results)} cells, "
              f"{len(failures)} failures)")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload glm4-9b.chat --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json``) names a configuration
(``configs/<config>.json``: model sizes, engine settings and the
architecture module in ``arch/`` that makes its weights and reference)
and a traffic mix (``traffic/<mix>.json``, read by ``loadgen.py``); its
load is in ``cells/<cell>.json``.  One process holds the chips.  It
makes the weights from ``--seed`` on the device, builds
``ServingEngine`` with the configuration's settings behind
``ServingFrontend``, and drives the mix's schedule on the host clock:
the mix's warm phase first (set-up), then a window of ``--seconds``.
Requests are timed from their due time.  A cell whose file names
``replicas`` runs one engine per chip it asks for, each with the same
weights, behind ``ReplicaCluster`` with the file's routing and shared
tier; its counts are summed over the replicas.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
records a profiler trace over the start of the window (at most
``TRACE_S`` seconds), with host spans around the calls into the
engine's layers, and prints the per-layer metrics instead.  Each metric
is read by ``metrics/<name>.py``.

After the window the outputs are checked: a sample of finished
requests, drawn from the seed and holding the longest and one of each
replica's, is run through
the float32 reference (``arch/<arch>.py``) over prompt and served tokens,
and the widest gap by which a served token's logit lies below the
reference's best must stay within the cell's limit.

The last line of standard output is the JSON result.  Without a TPU, or
with fewer chips than the cell asks for, the run exits non-zero and
prints no result.
"""
from __future__ import annotations

import time

T0 = time.perf_counter()          # set-up is timed from process start

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from collections import deque  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent / "src"))
sys.path.insert(0, str(HERE))

import numpy as np  # noqa: E402

import harness  # noqa: E402
import loadgen  # noqa: E402
from window import Ctx, Rec  # noqa: E402

TRACE_S = 10.0          # longest traced slice of the window
GRACE_S = 60.0          # how long past the window a due request is
#                         waited for before it counts as failed
CHECK_REQUESTS = 4      # finished requests compared with the reference
SPANNED = ("step", "_admit", "_run_prefill_chunks", "_register_prompt_blocks",
           "_decode_fused", "_poll_transfers")


class NoChip(RuntimeError):
    pass


# ---------------------------------------------------------------------------
# instrumentation of the engine instance (benchmark side; the program is
# not changed)
# ---------------------------------------------------------------------------
class Instrument:
    """Counts taken around calls into the engines (one, or each
    replica's), and host spans around their layers when tracing.  ``on``
    gates the per-call records."""

    def __init__(self, engines, spans: bool):
        self.on = False
        self.decode = []           # (rows, tokens attended) per decode step
        self.prefill = []          # (start, tokens) per prefill chunk
        self.hits = {}             # request id -> [cow, inject] blocks
        self.replica = {}          # request id -> index of its engine
        for k, eng in enumerate(engines):
            self._wrap(k, eng, spans)

    def _wrap(self, k: int, eng, spans: bool):
        import jax
        kv = eng.kv
        orig_wc, orig_wcp = kv.write_chunk, kv.write_chunk_positions

        def write_chunk(slot, state1, offset, n):
            if self.on:
                self.prefill.append((int(offset), int(n)))
            return orig_wc(slot, state1, offset, n)

        def write_chunk_positions(slot, state1, positions):
            if self.on and len(positions):
                self.prefill.append((int(positions[0]), len(positions)))
            return orig_wcp(slot, state1, positions)

        kv.write_chunk, kv.write_chunk_positions = (write_chunk,
                                                    write_chunk_positions)

        orig_decode = eng._decode_fused

        def decode_fused(decode_reqs):
            if self.on:
                ctx = sum(kv.slots[r.slot].length + 1 for r in decode_reqs)
                self.decode.append((len(decode_reqs), ctx))
            return orig_decode(decode_reqs)

        eng._decode_fused = decode_fused

        for name in ("_admit", "_run_prefill_chunks"):
            setattr(eng, name,
                    self._hit_counter(k, eng, getattr(eng, name)))

        if spans:
            for name in SPANNED:
                fn = getattr(eng, name)
                setattr(eng, name, self._span(f"engine.{name}", fn, jax))

    def _hit_counter(self, k: int, eng, fn):
        def wrapped(req, *a):
            self.replica[req.request_id] = k
            c0 = eng.cow_share_hits + eng.segment_share_hits
            i0 = eng.inject_hits + eng.segment_inject_hits
            out = fn(req, *a)
            h = self.hits.setdefault(req.request_id, [0, 0])
            h[0] += eng.cow_share_hits + eng.segment_share_hits - c0
            h[1] += eng.inject_hits + eng.segment_inject_hits - i0
            return out
        return wrapped

    @staticmethod
    def _span(name, fn, jax):
        def wrapped(*a, **k):
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **k)
        return wrapped


COUNTERS = ("cow_share_hits", "inject_hits", "segment_share_hits",
            "segment_inject_hits", "shared_fetch_hits", "prefill_chunks",
            "steps")


def engine_counts(engines) -> dict:
    return {k: sum(int(getattr(e, k)) for e in engines) for k in COUNTERS}


# ---------------------------------------------------------------------------
# the client side
# ---------------------------------------------------------------------------
class Driver:
    """Submits the schedule to the front end on the host clock and pumps
    it on this thread.  Open loop: each turn at its due time.  Closed
    loop: a client's next request when its last one completes."""

    def __init__(self, fe, sched: loadgen.Schedule, t_zero: float):
        from repro.serving import SamplingParams
        self.fe, self.s, self.t0 = fe, sched, t_zero
        self.SP = SamplingParams
        self.i = 0
        self.sent = []                         # (handle, due)
        self.ready = deque()
        self.next = [0] * len(sched.clients)
        for c in range(len(sched.clients)):
            self.ready.append((c, t_zero))

    def _submit(self, turn, due, on_done=None):
        h = self.fe.submit(turn.prompt,
                           params=self.SP(max_new_tokens=turn.max_new),
                           session_id=turn.session, arrival_t=due,
                           on_done=on_done, retain_blocks=not turn.last)
        self.sent.append((h, due))

    def _arrivals(self, now: float) -> float:
        """Submit what is due; return when the next arrival is due."""
        turns = self.s.turns
        while self.i < len(turns) and self.t0 + turns[self.i].t <= now:
            self._submit(turns[self.i], self.t0 + turns[self.i].t)
            self.i += 1
        while self.ready:
            c, due = self.ready.popleft()
            q = self.s.clients[c]
            if self.next[c] < len(q):
                self._submit(q[self.next[c]], due,
                             on_done=lambda h, c=c: self.ready.append(
                                 (c, h.done_t)))
                self.next[c] += 1
        if self.i < len(turns):
            return self.t0 + turns[self.i].t
        return float("inf")

    def run_until(self, t_end: float, stop=None) -> None:
        fe = self.fe
        while True:
            now = time.monotonic()
            if now >= t_end or (stop is not None and stop()):
                return
            nxt = self._arrivals(now)
            if fe.in_flight() == 0:
                time.sleep(max(0.0, min(nxt, t_end) - now, 1e-4)
                           if nxt > now else 0)
                continue
            fe.pump_once()


# ---------------------------------------------------------------------------
def _warm_programs(eng) -> None:
    """Compile every program the window calls before any traffic: the
    fused decode step with every row inactive, both prefill chunk
    programs, and the pool scatter at the widths of a chunk and of an
    injected block, on the engine's device.  None writes anything but
    the scratch page."""
    import contextlib

    import jax
    with (contextlib.nullcontext() if eng.device is None
          else jax.default_device(eng.device)):
        _warm_on_device(eng)


def _warm_on_device(eng) -> None:
    import jax
    import jax.numpy as jnp
    from repro.serving import kvcache

    kv = eng.kv
    sa = eng.scheduler.step_arrays([], kv.n_slots)
    # twice: on a fresh state, then on the state the step handed back,
    # as a step that reuses it passes it (on a replica pinned to its
    # device that state is committed there, and compiles apart)
    state = kv.decode_state([])
    for _ in range(2):
        toks, state = eng._fused_decode(
            eng.params, state, jnp.asarray(sa["tokens"]),
            jnp.asarray(sa["active"]), jax.random.PRNGKey(0),
            jnp.asarray(sa["temperature"]), jnp.asarray(sa["top_k"]),
            jnp.asarray(sa["top_p"]))
        kv.absorb(state)
        np.asarray(toks)
    C = eng.ecfg.prefill_chunk_tokens
    z = jnp.zeros((1, C), jnp.int32)
    jax.block_until_ready(eng._prefill_chunk(
        eng.params, kv.chunk_state(0), z, jnp.zeros((1,), jnp.int32)))
    if eng._prefill_chunk_seg is not None:
        jax.block_until_ready(eng._prefill_chunk_seg(
            eng.params, kv.chunk_state(0), z, jnp.full((1, C), -1,
                                                       jnp.int32)))
    for width in (C, eng.manager.block_tokens):
        z = jnp.zeros((width,), jnp.int32)
        for key, arr in kv.pools.items():
            data = jnp.zeros((arr.shape[0], width) + arr.shape[3:],
                             arr.dtype)
            kv.pools[key] = kvcache._scatter_pool(arr, z, z, data)
    kv.state_version += 1
    jax.block_until_ready(kv.pools)


def choose_sample(done, hits: dict, seed: int, n: int,
                  replica: dict | None = None) -> list:
    """The longest finished request, the ones with the most blocks
    reused by CoW and by injection, one drawn from the seed for each
    replica (``replica``: request id -> engine) that has none yet, and
    the rest drawn from the seed: at least ``n``, and more only where
    the replicas ask for it."""
    if not done:
        return []
    done = sorted(done, key=lambda h: h.request.request_id)
    pick = [max(done, key=lambda h: len(h.prompt) + len(h.tokens))]
    for k in (0, 1):
        best = max(done, key=lambda h: hits.get(h.request.request_id,
                                                [0, 0])[k])
        if hits.get(best.request.request_id, [0, 0])[k] > 0 and all(
                best is not p for p in pick):
            pick.append(best)
    rng = np.random.default_rng([seed, 17])
    where = (replica or {}).get
    for r in sorted({where(h.request.request_id) for h in done} - {None}):
        if any(where(p.request.request_id) == r for p in pick):
            continue
        mine = [h for h in done if where(h.request.request_id) == r]
        pick.append(mine[int(rng.integers(len(mine)))])
    rest = [h for h in done if all(h is not p for p in pick)]
    while len(pick) < n and rest:
        pick.append(rest.pop(int(rng.integers(len(rest)))))
    return pick


def check_outputs(arch, params, d, sample, control: bool = False) -> dict:
    """Widest gap (in logit spreads) between the reference's best logit
    and the served token's, over every served token of the sample; with
    ``control``, also the gap of the token the fp8 reference puts
    first.  The reference is the architecture module's."""
    from archlib import served_gaps
    gaps, cgaps, n_tok = [], [], 0
    for h in sample:
        p, g = list(h.prompt), list(h.tokens)
        seq = p + g[:-1]
        rows = list(range(len(p) - 1, len(seq)))
        ref = arch.logits(params, d, seq, rows)
        gaps.append(float(served_gaps(ref, g).max()))
        n_tok += len(g)
        if control:
            low = arch.logits(params, d, seq, rows, quant="fp8")
            cgaps.append(float(served_gaps(
                ref, low.argmax(axis=1)).max()))
    out = {"logit_gap": max(gaps) if gaps else None,
           "tokens": n_tok, "requests": len(sample)}
    if control:
        out["control_gap"] = max(cgaps) if cgaps else float("nan")
    return out


# ---------------------------------------------------------------------------
def resolve(workload: str) -> dict:
    bench = harness.load_benchmark()
    wl = harness.find_workload(bench, workload)
    return {"workload": wl, "config": harness.load_config(wl["config"]),
            "traffic": harness.load_traffic(wl["traffic"]),
            "cell": harness.load_cell(wl["name"]),
            "end_to_end": harness.cell_metrics(bench, wl["name"],
                                               "end_to_end"),
            "per_layer": harness.cell_metrics(bench, wl["name"],
                                              "per_layer")}


def build_engines(cfg, ecfg, params, weights, n_chips: int, replicas):
    """The engine the front end drives, and the engines under it: one
    ``ServingEngine`` on the default device with ``params``, or, where
    the cell names ``replicas``, a ``ReplicaCluster`` of ``n_chips`` of
    them, replica i on device i.  Each replica gets the same weights,
    ``params`` where they already lie on its device, else made there by
    ``weights()``: a copy from the first device would pass through it."""
    import jax
    from repro.serving import ServingEngine
    if replicas is None:
        if n_chips != 1:
            raise ValueError(f"a cell on {n_chips} chips names its "
                             "replicas in its cell file")
        eng = ServingEngine(cfg, ecfg, params=params)
        return eng, [eng]
    from repro.serving.cluster import ReplicaCluster

    def replica(dev):
        if {dev} == jax.tree.leaves(params)[0].devices():
            return ServingEngine(cfg, ecfg, params=params, device=dev)
        with jax.default_device(dev):
            own = weights()
        return ServingEngine(cfg, ecfg, params=own, device=dev)
    cluster = ReplicaCluster(
        n_replicas=n_chips, routing=replicas["routing"],
        shared_tier=replicas["shared_tier"], engine_factory=replica)
    return cluster, list(cluster.engines.values())


def memory_line(devices, when: str) -> str:
    """Bytes in use and the peak so far on each device, in GB."""
    out = []
    for dv in devices:
        m = dv.memory_stats() or {}
        out.append(f"{m.get('bytes_in_use', 0) / 1e9:.2f}/"
                   f"{m.get('peak_bytes_in_use', 0) / 1e9:.2f}")
    return f"memory in use/peak (GB) per device, {when}: {' '.join(out)}"


def run(args, spec: dict, *, require_tpu: bool = True, peaks=None,
        fault=None, control: bool = False, log=print) -> dict:
    """One run of one cell; returns the result line's object.  ``fault``
    (tests) breaks each engine before traffic; ``control`` also reads
    the fp8 reference's gap."""
    import jax

    import tracing

    wl, conf, mix, cell = (spec[k] for k in
                           ("workload", "config", "traffic", "cell"))
    devices = jax.devices()
    dev = devices[0]
    if require_tpu:
        if dev.platform != "tpu":
            raise NoChip(f"JAX finds no TPU (platform {dev.platform!r})")
        if len(devices) < wl["chips"]:
            raise NoChip(f"{len(devices)} chips, the cell asks for "
                         f"{wl['chips']}")
        peaks = harness.load_peaks(dev.device_kind)
        from repro.launch.compile_cache import ENV_VAR, enable_compile_cache
        os.environ[ENV_VAR] = str(harness.ROOT / ".jax_cache")
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda ev, secs, **kw: compiles.append(time.monotonic())
        if ev == "/jax/core/compile/backend_compile_duration" else None)

    from repro.serving.frontend import ServingFrontend

    arch = harness.config_arch(conf)
    d = arch.dims(conf["model"])
    cfg, ecfg = harness.model_config(conf), harness.engine_config(conf)
    params = arch.init_weights(d, args.seed)
    jax.block_until_ready(params)
    top, engines = build_engines(
        cfg, ecfg, params, lambda: arch.init_weights(d, args.seed),
        wl["chips"], cell.get("replicas"))
    if fault is not None:
        for eng in engines:
            fault(eng)
    inst = Instrument(engines, spans=bool(args.trace))
    fe = ServingFrontend(top)
    load = dict(cell.get("load", {}))
    if args.rate is not None:
        load["session_rate_per_s"] = args.rate
    n_slots = sum(eng.kv.n_slots for eng in engines)
    sched = loadgen.build(mix, load, args.seed, args.seconds, d["V"],
                          ecfg.max_len, n_slots)
    # the devices whose memory the run fills, in replica order
    used = list(dict.fromkeys(eng.device or dev for eng in engines))
    log(memory_line(used, "engines built"))
    for eng in engines:
        _warm_programs(eng)
    log(memory_line(used, "programs warm"))

    t_zero = time.monotonic()
    drv = Driver(fe, sched, t_zero)
    drv.run_until(t_zero + sched.warm_s)

    log(memory_line(used, "warm phase over"))
    w0 = time.monotonic()
    w1 = w0 + args.seconds
    setup_s = time.perf_counter() - T0
    rc0 = [eng.recompiles() for eng in engines]
    c0 = engine_counts(engines)
    inst.on = True
    trace_dir = None
    if args.trace:
        trace_dir = tempfile.mkdtemp(prefix="bench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
        win = jax.profiler.TraceAnnotation(tracing.WINDOW)
        win.__enter__()
        drv.run_until(min(w1, w0 + TRACE_S))
        inst.on = False
        jax.block_until_ready([eng.kv.pools for eng in engines])
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    drv.run_until(w1)
    inst.on = False
    rc1 = [eng.recompiles() for eng in engines]
    c1 = engine_counts(engines)
    n_compiles = sum(1 for t in compiles if w0 <= t < w1)

    due = [(h, t) for h, t in drv.sent if w0 <= t < w1]

    def waited():
        # enough finished requests, one that can be sampled from each
        # replica that admitted any, and a first token for every due one
        fin = [h for h, _ in drv.sent if h.status == "done"]
        full = {inst.replica.get(h.request.request_id) for h in fin
                if len(h.tokens) == h.params.max_new_tokens}
        return (len(fin) >= CHECK_REQUESTS
                and full >= set(inst.replica.values())
                and all(h.first_token_t is not None or h.status == "shed"
                        for h, _ in due))
    drv.run_until(w1 + GRACE_S, stop=waited)
    wait_end = time.monotonic()
    mem = [d_.memory_stats() or {} for d_ in used]
    peak_each = [int(m.get("peak_bytes_in_use", 0)) for m in mem]
    handles = [h for h, _ in drv.sent]
    done = [h for h in handles if h.status == "done"
            and len(h.tokens) == h.params.max_new_tokens]
    eng_stats = {k: c1[k] - c0[k] for k in COUNTERS}
    log(f"window: {len(due)} due, {sum(h.first_token_t is not None for h, _ in due)} "
        f"first tokens, {len(done)} of {len(handles)} requests finished, "
        f"engine deltas {eng_stats}, backend compiles in window {n_compiles}")
    log(f"recompiles before/after window: {rc0} {rc1}; "
        f"{len(engines)} engine(s), slots {n_slots}; memory peak "
        f"{[m.get('peak_bytes_in_use') for m in mem]} of "
        f"{[m.get('bytes_limit') for m in mem]}")
    if rc0 != rc1:
        raise RuntimeError(f"recompiled inside the window: {rc0} -> {rc1}")

    bt = engines[0].manager.block_tokens
    records = [Rec(due=t, admit=h.admit_t, first=h.first_token_t,
                   token_times=list(h.token_times), prompt_len=len(h.prompt),
                   hit_tokens=bt * (h.request.prefix_hit_blocks
                                    + h.request.segment_hit_blocks)
                   if h.request is not None else 0,
                   shed=h.status == "shed", wait_end=wait_end)
               for h, t in due]
    quarters = [sorted(r.ttft for r in records
                       if w0 + q * args.seconds / 4 <= r.due
                       < w0 + (q + 1) * args.seconds / 4)
                for q in range(4)]
    log("ttft median per quarter of the window (s): "
        + " ".join(f"{q[len(q) // 2]:.3f}" if q else "-" for q in quarters))
    ctx = Ctx(cell=wl["name"], d=d, peaks=peaks, setup_s=setup_s, w0=w0,
              w1=w1, arch=arch, records=records,
              token_times=[list(h.token_times) for h in handles],
              counts=eng_stats, decode_calls=list(inst.decode),
              prefill_calls=list(inst.prefill))

    # the program's state, every replica's, goes before the reference
    # comes; the reference runs on the first device
    fe.stop(drain=False)
    hits, replica = dict(inst.hits), dict(inst.replica)
    n_replicas = len(engines)
    del fe, top, engines, eng, inst, drv
    gc.collect()

    if trace_dir is not None:
        import shutil
        lines, spans = tracing.read_xplane(trace_dir)
        ctx.trace = tracing.reduce_events(lines, spans)
        shutil.rmtree(trace_dir, ignore_errors=True)
        if args.dump:
            Path(args.dump).mkdir(parents=True, exist_ok=True)
            with open(Path(args.dump) / f"{wl['name']}.{args.seed}.json",
                      "w") as f:
                json.dump({"op_s": ctx.trace.op_s, "op_n": ctx.trace.op_n,
                           "module_s": ctx.trace.module_s,
                           "module_n": ctx.trace.module_n,
                           "programs": [[t, sorted(k)] for t, k in
                                        ctx.trace.programs],
                           "idle_gaps": ctx.trace.idle_gaps,
                           "decode_calls": ctx.decode_calls,
                           "prefill_calls": ctx.prefill_calls}, f)

    sample = choose_sample(done, hits, args.seed, CHECK_REQUESTS, replica)
    t_chk = time.perf_counter()
    chk = check_outputs(arch, params, d, sample, control=control)
    log(f"check: {chk['requests']} requests, {chk['tokens']} served tokens, "
        f"hits (cow, inject) "
        f"{[hits.get(h.request.request_id, [0, 0]) for h in sample]}, "
        f"{time.perf_counter() - t_chk:.2f} s")
    limits = cell["limits"]
    compared = {"logit_gap": {"value": chk["logit_gap"],
                              "limit": limits["logit_gap"]}}
    if n_replicas > 1:
        # a replica that served requests and has none in the sample is
        # not vouched for
        missed = set(replica.values()) - {
            replica.get(h.request.request_id) for h in sample}
        compared["replicas_unchecked"] = {"value": len(missed), "limit": 0}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec[section]:
        v = harness.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": max(peak_each),
              "memory_peak_bytes_each": peak_each}
    out = {"correct": correct, "attempted": len(records),
           "failed": sum(r.first is None for r in records),
           "metrics": metrics, "device": device}
    if ctx.trace is not None:
        device["busy_s"] = ctx.trace.busy_s
        device["window_s"] = ctx.trace.window_s
        out["breakdown"] = {"device_ops": tracing.top(ctx.trace.op_s),
                            "idle_gaps": tracing.top(ctx.trace.idle_gaps)}
    if control:
        out["control_gap"] = chk["control_gap"]
    out["compared"] = compared
    return out


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="override the cell's session start rate "
                         "(for finding the knee; never used by a check)")
    ap.add_argument("--dump", default=None,
                    help="directory for the reduced trace of a traced run "
                         "(op and program names with their seconds)")
    return ap.parse_args(argv)


def emit(out: dict) -> None:
    for name, c in out["compared"].items():
        print(f"{name} {c['value']!r} limit {c['limit']!r}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)


def main(argv=None) -> int:
    args = parse(argv)
    try:
        out = run(args, resolve(args.workload))
    except NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 2
    emit(out)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Run one benchmark cell once on the chip and print its result line.

    python3 bench/run.py --workload glm4-9b.chat --seed 7 --seconds 51 --trace 0

The cell (``BENCHMARK.json``) names a configuration
(``configs/<config>.json``: model sizes and engine settings) and a
traffic mix (``traffic/<mix>.json``, read by ``loadgen.py``); its load
is in ``cells/<cell>.json``.  One process holds the chip.  It makes the
weights from ``--seed`` on the device, builds ``ServingEngine`` with the
configuration's settings behind ``ServingFrontend``, and drives the
mix's schedule on the host clock: the mix's warm phase first (set-up),
then a window of ``--seconds``.  Requests are timed from their due time.

``--trace 0`` prints the cell's end-to-end metrics; ``--trace 1``
records a profiler trace over the start of the window (at most
``TRACE_S`` seconds), with host spans around the calls into the
engine's layers, and prints the per-layer metrics instead.  Each metric
is read by ``metrics/<name>.py``.

After the window the outputs are checked: a sample of finished
requests, drawn from the seed and holding the longest, is run through
the float32 reference (``reference.py``) over prompt and served tokens,
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
    """Counts taken around calls into the engine, and host spans around
    its layers when tracing.  ``on`` gates the per-call records."""

    def __init__(self, eng, spans: bool):
        import jax
        self.eng = eng
        self.on = False
        self.decode = []           # (rows, tokens attended) per decode step
        self.prefill = []          # (start, tokens) per prefill chunk
        self.hits = {}             # request id -> [cow, inject] blocks
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
            setattr(eng, name, self._hit_counter(getattr(eng, name)))

        if spans:
            for name in SPANNED:
                fn = getattr(eng, name)
                setattr(eng, name, self._span(f"engine.{name}", fn, jax))

    def _hit_counter(self, fn):
        eng = self.eng

        def wrapped(req, *a):
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


def engine_counts(eng) -> dict:
    return {k: int(getattr(eng, k)) for k in COUNTERS}


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
    injected block.  None writes anything but the scratch page."""
    import jax
    import jax.numpy as jnp
    from repro.serving import kvcache

    kv = eng.kv
    sa = eng.scheduler.step_arrays([], kv.n_slots)
    toks, new = eng._fused_decode(
        eng.params, kv.decode_state([]), jnp.asarray(sa["tokens"]),
        jnp.asarray(sa["active"]), jax.random.PRNGKey(0),
        jnp.asarray(sa["temperature"]), jnp.asarray(sa["top_k"]),
        jnp.asarray(sa["top_p"]))
    kv.absorb(new)
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


def choose_sample(done, hits: dict, seed: int, n: int) -> list:
    """The longest finished request, the ones with the most blocks
    reused by CoW and by injection, and the rest drawn from the seed."""
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
    rest = [h for h in done if all(h is not p for p in pick)]
    rng = np.random.default_rng([seed, 17])
    while len(pick) < n and rest:
        pick.append(rest.pop(int(rng.integers(len(rest)))))
    return pick[:n]


def check_outputs(params, d, sample, control: bool = False) -> dict:
    """Widest gap (in logit spreads) between the reference's best logit
    and the served token's, over every served token of the sample; with
    ``control``, also the gap of the token the fp8 reference puts
    first."""
    import reference
    gaps, cgaps, n_tok = [], [], 0
    for h in sample:
        p, g = list(h.prompt), list(h.tokens)
        seq = p + g[:-1]
        rows = list(range(len(p) - 1, len(seq)))
        ref = reference.logits(params, d, seq, rows)
        gaps.append(float(reference.served_gaps(ref, g).max()))
        n_tok += len(g)
        if control:
            low = reference.logits(params, d, seq, rows, quant="fp8")
            cgaps.append(float(reference.served_gaps(
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


def run(args, spec: dict, *, require_tpu: bool = True, peaks=None,
        fault=None, control: bool = False, log=print) -> dict:
    """One run of one cell; returns the result line's object.  ``fault``
    (tests) breaks the engine before traffic; ``control`` also reads
    the fp8 reference's gap."""
    import jax

    import reference
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

    from repro.serving import ServingEngine
    from repro.serving.frontend import ServingFrontend

    d = reference.dims(conf["model"])
    cfg, ecfg = harness.model_config(conf), harness.engine_config(conf)
    params = reference.init_weights(d, args.seed)
    jax.block_until_ready(params)
    eng = ServingEngine(cfg, ecfg, params=params)
    if fault is not None:
        fault(eng)
    inst = Instrument(eng, spans=bool(args.trace))
    fe = ServingFrontend(eng)
    load = dict(cell.get("load", {}))
    if args.rate is not None:
        load["session_rate_per_s"] = args.rate
    sched = loadgen.build(mix, load, args.seed, args.seconds, d["V"],
                          ecfg.max_len, eng.kv.n_slots)
    _warm_programs(eng)

    t_zero = time.monotonic()
    drv = Driver(fe, sched, t_zero)
    drv.run_until(t_zero + sched.warm_s)

    w0 = time.monotonic()
    w1 = w0 + args.seconds
    setup_s = time.perf_counter() - T0
    rc0 = eng.recompiles()
    c0 = engine_counts(eng)
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
        jax.block_until_ready(eng.kv.pools)
        win.__exit__(None, None, None)
        jax.profiler.stop_trace()
    drv.run_until(w1)
    inst.on = False
    rc1 = eng.recompiles()
    c1 = engine_counts(eng)
    n_compiles = sum(1 for t in compiles if w0 <= t < w1)

    due = [(h, t) for h, t in drv.sent if w0 <= t < w1]

    def waited():
        n_done = sum(h.status == "done" for h, _ in drv.sent)
        return n_done >= CHECK_REQUESTS and all(
            h.first_token_t is not None or h.status == "shed"
            for h, _ in due)
    drv.run_until(w1 + GRACE_S, stop=waited)
    wait_end = time.monotonic()
    stats = dev.memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    handles = [h for h, _ in drv.sent]
    done = [h for h in handles if h.status == "done"
            and len(h.tokens) == h.params.max_new_tokens]
    eng_stats = {k: c1[k] - c0[k] for k in COUNTERS}
    log(f"window: {len(due)} due, {sum(h.first_token_t is not None for h, _ in due)} "
        f"first tokens, {len(done)} of {len(handles)} requests finished, "
        f"engine deltas {eng_stats}, backend compiles in window {n_compiles}")
    log(f"recompiles before/after window: {rc0} {rc1}; "
        f"slots {eng.kv.n_slots}; memory {stats.get('peak_bytes_in_use')} "
        f"peak of {stats.get('bytes_limit')}")
    if rc0 != rc1:
        raise RuntimeError(f"recompiled inside the window: {rc0} -> {rc1}")

    bt = eng.manager.block_tokens
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
              w1=w1, records=records,
              token_times=[list(h.token_times) for h in handles],
              counts=eng_stats, decode_calls=list(inst.decode),
              prefill_calls=list(inst.prefill))

    # the program's state goes before the reference comes
    fe.stop(drain=False)
    hits = dict(inst.hits)
    del fe, eng, inst, drv
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

    sample = choose_sample(done, hits, args.seed, CHECK_REQUESTS)
    t_chk = time.perf_counter()
    chk = check_outputs(params, d, sample, control=control)
    log(f"check: {chk['requests']} requests, {chk['tokens']} served tokens, "
        f"hits (cow, inject) "
        f"{[hits.get(h.request.request_id, [0, 0]) for h in sample]}, "
        f"{time.perf_counter() - t_chk:.2f} s")
    limits = cell["limits"]
    compared = {"logit_gap": {"value": chk["logit_gap"],
                              "limit": limits["logit_gap"]}}
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in compared.values())

    section = "per_layer" if args.trace else "end_to_end"
    metrics = {}
    for m in spec[section]:
        v = harness.load_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
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

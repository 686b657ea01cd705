"""The open loop on the wall clock, in-process, through the program's own
entry (``launch/serve.build_cluster`` -> ``ClusterFrontend`` ->
``ReplicaDriver`` -> ``ServingEngine`` -> ``PagedKVManager`` -> the
Pallas paged kernels).

Each request is submitted when it is due, as the gateway submits one
(``arrival = cluster.clock``), with an ``on_token`` callback that stamps
the host's clock; ``cluster.step()`` runs whenever work is queued, and
the loop sleeps until the next due time when nothing is.  The window
closes at the end of the first step that ends after ``seconds``: a rate
is then the work of the whole window over its whole length.
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.judge import Outcome
from bench.lib.spec import ROOT

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"
WINDOW_OPEN, WINDOW_CLOSE = "bench.window.open", "bench.window.close"
PREFILL_LENS = (8, 16, 32, 64, 128, 256, 512, 1024, 2048)
DECODE_STEPS = (1, 2, 4, 8, 16, 32, 64, 128, 256)
LANES = (1, 2, 4, 8)
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/core/compile/jaxpr_trace_duration")


class CompileCounter:
    """Counts, while armed, backend compiles (a load from the persistent
    cache counts too) by function name, and traces that took time (a jit
    cache hit still reports a trace of about 0 s)."""

    def __init__(self):
        self.armed = False
        self.reset()
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def reset(self) -> None:
        self.counts = {k: 0 for k in COMPILE_EVENTS}
        self.seconds = 0.0
        self.names: dict = {}

    def backend_compiles(self) -> int:
        return self.counts[COMPILE_EVENTS[0]]

    def _on(self, name, secs, fun_name="?", **_kw):
        if not (self.armed and name in self.counts):
            return
        if "backend" in name:
            self.counts[name] += 1
            self.seconds += secs
            self.names[fun_name] = self.names.get(fun_name, 0) + 1
        elif secs > 1e-3:
            self.counts[name] += 1


def enable_cache() -> None:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile, and with no
    size cap that would evict a cell's programs between runs."""
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def start_trace(out_dir: str = str(TRACE_DIR)) -> None:
    """Device ops and the host's TraceAnnotation spans; no Python
    function tracing, which would swamp a window of tens of seconds."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(out_dir, profiler_options=opts)


def span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def build(pcfg, params, seed: int, telemetry: bool, full: bool = True):
    """One replica as ``launch/serve.py`` builds it: chip sizes (1024
    pages of 16 tokens, 8 slots, 2048-token tables), or with ``full``
    false the program's CPU sizes, for tests."""
    from repro.launch.serve import build_cluster
    from repro.telemetry import ClusterTelemetry
    return build_cluster(pcfg, params, full=full, replicas=1, seed=seed,
                         telemetry=ClusterTelemetry(enabled=telemetry))


def _bucket(n: int, buckets) -> int:
    return next(b for b in buckets if n <= b)


def warm_shapes(max_context: int, max_output: int) -> tuple[list, list]:
    """(prefill (chunk, lanes), decode (steps, lanes)) shapes the engine
    can reach: its chunk buckets up to ``max_context``, its lane buckets,
    and its decode step buckets up to ``max_output`` (past 256 steps the
    engine's next bucket is 2048)."""
    top = _bucket(max_context, PREFILL_LENS)
    pre = [(n, b) for n in PREFILL_LENS if n <= top for b in LANES]
    m = max(max_output - 1, 1)          # the first token comes of prefill
    top = _bucket(m, DECODE_STEPS) if m <= DECODE_STEPS[-1] else 2048
    steps = [n for n in DECODE_STEPS + (2048,) if n <= top]
    return pre, [(n, b) for n in steps for b in LANES]


def warm(cluster, max_context: int, max_output: int) -> int:
    from repro.serving.kvcache import _copy_pages_prog
    """Compile, or load from the persistent cache, each of the engine's
    prefill and decode programs at every shape the traffic can reach, and
    run the host-side eager operations of each lane bucket once, so that
    nothing compiles in the window.  Nothing executes on the pools.
    Returns the number of programs."""
    n = 0
    pre, dec = warm_shapes(max_context, max_output)
    for d in cluster.drivers:
        eng, kv = d.engine, d.engine.kv
        for L, B in pre:
            slots = [0] * B
            zeros = eng._put(np.zeros((B,), np.int32))
            keys = eng._put(jnp.stack([jax.random.PRNGKey(0)] * B))
            eng._prefill.lower(
                eng.params, eng._put(np.zeros((B, L), np.int32)),
                kv.lane_cache(slots), zeros, zeros, kv.table_rows(slots),
                None, keys).compile()
            n += 1
        for S, B in dec:
            slots = [0] * B
            zeros = eng._put(np.zeros((B,), np.int32))
            _, sk = jax.random.split(eng.key)
            eng._decode.lower(
                eng.params, kv.lane_cache(slots), zeros, zeros, zeros,
                eng._put(np.full((B,), -1, np.int32)), kv.table_rows(slots),
                None, eng._put(sk), n_steps=S).compile()
            n += 1
        # the KV manager maps pages into a block-table row with an eager
        # update whose program depends on the number of pages mapped
        for k in range(1, kv.max_pages_per_seq + 1):
            kv.block_tables.at[0, 0:k].set(jnp.asarray([0] * k, jnp.int32))
        kv.block_tables.at[0].set(0)
        # two random prompts that begin with the same token share a
        # partial first page, and the first write into it copies that
        # one page (copy-on-write) and remaps one block-table entry
        one = jnp.zeros((1,), jnp.int32)
        _copy_pages_prog.lower(kv.pools, kv._paged_axes(), one,
                               one).compile()
        kv.block_tables.at[0, one].set(one)
        n += 1
    return n


def warm_request(cluster) -> None:
    """One short request end to end, so the host-side eager operations of
    the serving path are compiled before the window too."""
    from repro.core.request import simple_request
    r = simple_request(-1, cluster.clock, 24, 3, 5.0, 0.1)
    cluster.submit(r, prompt=list(range(1, 25)))
    cluster.run_until_idle()


def instrument(cluster) -> list:
    """Host spans around the calls into each layer, and the shapes each
    prefill and decode call ran at (traced runs only).  Returns the list
    the calls are recorded in: (kind, perf_counter at the call, pos0,
    chunk lengths or step budgets per lane, steps of the scan)."""
    calls: list = []

    def wrap(obj, attr, name, record=None):
        fn = getattr(obj, attr)

        def wrapped(*a, **kw):
            if record is not None:
                calls.append(record(time.perf_counter(), *a, **kw))
            with jax.profiler.TraceAnnotation(name):
                return fn(*a, **kw)
        setattr(obj, attr, wrapped)

    def prefill(t, params, toks, cache, pos0, true_len, *_a, **_kw):
        return ("prefill", t, np.asarray(pos0), np.asarray(true_len), 1)

    def decode(t, params, cache, toks0, pos0, steps, *_a, n_steps, **_kw):
        return ("decode", t, np.asarray(pos0), np.asarray(steps), n_steps)

    for d in cluster.drivers:
        wrap(d.sched, "plan", "bench.plan")
        wrap(d.engine, "execute", "bench.execute")
        wrap(d.engine, "_prefill", "bench.prefill_call", prefill)
        wrap(d.engine, "_decode", "bench.decode_call", decode)
    return calls


def counters(cluster) -> dict:
    keys = ("prefill_calls", "decode_calls", "decode_tokens", "preemptions")
    return {k: sum(d.engine.counters[k] for d in cluster.drivers)
            for k in keys}


def plan_latency(cluster) -> Optional[tuple[float, int]]:
    """(seconds, calls) of the planner's wall time so far, from the
    program's telemetry (None when telemetry is off)."""
    tot, n = 0.0, 0
    for d in cluster.drivers:
        if d.tel is None:
            return None
        tot += d.tel.plan_latency.sum
        n += d.tel.plan_latency.count
    return tot, n


def run_window(cluster, reqs, seconds: float, drain_s: float,
               ttft_limit, trace_from: Optional[float] = None,
               on_close=None) -> dict:
    """Serve ``reqs`` (bench.lib.traffic.Req, due-ordered) open-loop, call
    ``on_close`` when the window closes, then drain.  With ``trace_from``
    a timer starts the profiler that many seconds into the window, so
    the trace covers the window's tail (what it costs to write grows with
    its length).  Returns the outcomes and the window's clock readings
    and counters."""
    from repro.core.request import Request
    from repro.core.slo import StageSpec, decode_slo, prefill_slo
    outs: dict[int, Outcome] = {}

    def on_token(rid, toks):
        o = outs[rid]
        o.times.append((time.perf_counter(), len(toks)))
        o.tokens.extend(toks)

    def on_finish(req, _attained, dropped):
        if dropped and req.rid in outs:
            outs[req.rid].dropped = True

    for d in cluster.drivers:
        d.on_finish = on_finish
    c0, p0 = counters(cluster), plan_latency(cluster)
    late = []
    traced = trace_from is not None
    t0 = time.perf_counter()
    due = [t0 + r.due for r in reqs]
    end = t0 + seconds
    i, n = 0, len(reqs)
    started = []
    t_trace = None
    timer = None
    if traced:
        def trace_on():
            start_trace()
            with jax.profiler.TraceAnnotation(WINDOW_OPEN):
                started.append(time.perf_counter())
        timer = threading.Timer(max(0.0, t0 + trace_from
                                    - time.perf_counter()), trace_on)
        timer.start()
    while True:
        now = time.perf_counter()
        while i < n and due[i] <= now:
            r = reqs[i]
            P = len(r.prompt)
            outs[r.rid] = Outcome(
                rid=r.rid, cls=r.cls, due=due[i], prompt_len=P,
                out_len=r.out_len, ttft_limit=r.ttft_slowdown * ttft_limit(P),
                tpot_limit=r.tpot_s, submitted=now)
            with span(traced, "bench.submit"):
                cluster.submit(Request(r.rid, cluster.clock, stages=[
                    StageSpec(prefill_slo(r.ttft_slowdown), P),
                    StageSpec(decode_slo(r.tpot_s), r.out_len)]),
                    prompt=list(r.prompt), on_token=on_token)
            late.append(now - due[i])
            i += 1
        if now >= end and i >= n:
            break
        if cluster.idle:
            nxt = min(due[i] if i < n else end, end)
            with span(traced, "bench.wait"):
                time.sleep(max(0.0, nxt - time.perf_counter()))
        else:
            if started and t_trace is None:
                t_trace = now           # the first step the trace holds
            with span(traced, "bench.step"):
                cluster.step()
    t_end = time.perf_counter()
    if timer is not None:
        timer.join()
        with jax.profiler.TraceAnnotation(WINDOW_CLOSE):
            pass
    c1, p1 = counters(cluster), plan_latency(cluster)
    if on_close is not None:
        on_close()
    deadline = t_end + drain_s
    while drain_s > 0 and not cluster.idle \
            and time.perf_counter() < deadline:
        cluster.step()
    return {
        "outs": [outs[r.rid] for r in reqs if r.rid in outs],
        "t0": t0, "t_end": t_end, "t_drain": time.perf_counter(),
        "t_trace": t_trace if t_trace is not None else (
            started[0] if started else None),
        "late_s": late,
        "counters": {k: c1[k] - c0[k] for k in c0},
        "plan": None if p0 is None else (p1[0] - p0[0], p1[1] - p0[1]),
    }


def clear(cluster, outs) -> None:
    """Cancel every request of ``outs`` still in the system and run the
    cluster until it is empty, so the next window starts from nothing."""
    for o in outs:
        if not o.finished:
            cluster.cancel(o.rid)
    while not cluster.idle:
        cluster.step()


def swap_weights(cluster, make) -> None:
    """Delete the served weights, then serve ``make()``'s in their place:
    the same shapes and device, so every program is reused."""
    for d in cluster.drivers:
        for a in jax.tree.leaves(d.engine.params):
            if not a.is_deleted():
                a.delete()
    new = make()
    for d in cluster.drivers:
        eng = d.engine
        eng.params = new if eng.device is None else jax.device_put(
            new, eng.device)


def free_device() -> None:
    """Drop every array the process holds on the device."""
    for a in jax.live_arrays():
        a.delete()

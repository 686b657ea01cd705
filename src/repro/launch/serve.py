"""Serving launcher: SLOs-Serve scheduler driving the JAX engine end-to-end
through the SLO-routed cluster (serving/cluster.py: ``ClusterFrontend`` →
``ReplicaDriver`` → ``ServingEngine`` → ``PagedKVManager``).

The planner runs against a performance model in VIRTUAL time (the model
stands in for the chip the plan would execute on); the engine executes
every planned token for real.  By default the model is the reduced
(CPU-smoke) variant of ``--arch`` and request lengths are scaled down by
``--time-scale``, so a CPU run finishes in minutes.  ``--full`` serves the
architecture's published config at its real request lengths, planned with
the v5e roofline perf model — the size a TPU v5e chip holds:

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --rate 2.0 --duration 8
  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --full

``--http`` switches from batch replay to the live serving path: an
asyncio HTTP/SSE gateway (serving/gateway.py) over the same cluster, with
wall-clock telemetry on ``GET /metrics`` and graceful SIGINT/SIGTERM
shutdown — intake stops, every in-flight stream drains to its done event,
then the process exits:

  PYTHONPATH=src python -m repro.launch.serve --http --replicas 2
  curl -N -d '{"slo":"tight","prompt_len":16,"output_len":32}' \
      http://127.0.0.1:8080/v1/generate
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import get_config, get_reduced
from repro.core.perf_model import TPU_V5E, PerfModel, cpu_scale_perf_model
from repro.core.router import RoutingPolicy, make_real_cluster
from repro.core.scheduler import SchedulerConfig
from repro.core.workload import generate_workload
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_encdec_params, init_params

VIRTUAL_PERF = cpu_scale_perf_model()

# Per-replica engine sizes.  FULL: a published config on one 16 GB chip —
# Qwen3-1.7B in float32 holds ~6.9 GB of weights, and 1024 pages of 16
# tokens (229 KB of KV per token) take ~3.8 GB beside them; 2048-token
# contexts.  CPU: the reduced configs at smoke-test lengths.
FULL_SIZE = dict(replica_pages=1024, page_size=16, max_slots=8, max_len=2048)
CPU_SIZE = dict(replica_pages=64, page_size=8, max_slots=8, max_len=256)


def load_model(arch: str, full: bool, seed: int):
    """(config, random params from ``seed``): the published config with
    ``full``, else its reduced CPU variant."""
    cfg = get_config(arch) if full else get_reduced(arch)
    init = init_encdec_params if cfg.arch_type == "encdec" else init_params
    return cfg, init(jax.random.PRNGKey(seed), cfg)


def planning_perf(cfg, full: bool) -> PerfModel:
    """Virtual-time model the planner plans against: the v5e roofline of
    the float32 weights for a published config, the CPU-scale model for
    a reduced one."""
    if not full:
        return VIRTUAL_PERF
    return PerfModel.from_roofline(cfg.active_param_count(),
                                   4.0 * cfg.param_count(), TPU_V5E)


def build_cluster(cfg, params, *, full: bool, replicas: int = 1,
                  devices_per_replica: int = None, mesh=None,
                  telemetry=None, seed: int = 0):
    """The serving cluster every entry point runs: ``replicas`` engines
    behind the SLO router, sized for the chip (``full``) or the CPU."""
    size = FULL_SIZE if full else CPU_SIZE
    return make_real_cluster(
        replicas, cfg, params, planning_perf(cfg, full),
        policy=RoutingPolicy(max_hops=1),
        total_pages=size["replica_pages"] * replicas,
        replica_pages=size["replica_pages"], page_size=size["page_size"],
        max_slots=size["max_slots"], max_len=size["max_len"],
        sched_cfg=SchedulerConfig(page_size=size["page_size"],
                                  prefill_emits_first_token=True),
        telemetry=telemetry, devices_per_replica=devices_per_replica,
        mesh=mesh, seed=seed)


def serve_http(args) -> None:
    """Run the SSE gateway until SIGINT/SIGTERM, then drain gracefully."""
    import asyncio
    import signal

    from repro.serving.gateway import SSEGateway
    from repro.telemetry import ClusterTelemetry

    cfg, params = load_model(args.arch, args.full, args.seed)
    cluster = build_cluster(
        cfg, params, full=args.full, replicas=args.replicas,
        telemetry=ClusterTelemetry(enabled=True, wall_clock=True))

    async def amain():
        gw = await SSEGateway(cluster, host=args.host, port=args.port,
                              seed=args.seed).start()
        stop = asyncio.Event()
        loop = asyncio.get_running_loop()
        for sig in (signal.SIGINT, signal.SIGTERM):
            loop.add_signal_handler(sig, stop.set)
        print(f"serving {args.arch} x{args.replicas} at {gw.url} "
              f"(SSE on POST /v1/generate; Ctrl-C drains and exits)",
              flush=True)
        await stop.wait()
        print("draining in-flight streams...", flush=True)
        await gw.shutdown(drain=True)
        s = cluster.stats
        print(f"drained: served {s.served}/{s.submitted}, "
              f"attained {s.attained}, cancelled {s.cancelled}, "
              f"streams completed {gw.stats.completed}", flush=True)

    asyncio.run(amain())


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-135m")
    ap.add_argument("--full", action="store_true",
                    help="serve the published config at real request "
                         "lengths (a chip's worth of model)")
    ap.add_argument("--scenario", default="chatbot")
    ap.add_argument("--rate", type=float, default=2.0)
    ap.add_argument("--duration", type=float, default=8.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--time-scale", type=float, default=None,
                    help="scale request lengths (default 0.02 reduced, "
                         "1.0 with --full)")
    ap.add_argument("--max-requests", type=int, default=24)
    ap.add_argument("--http", action="store_true",
                    help="serve over HTTP/SSE instead of batch replay")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--replicas", type=int, default=None,
                    help="engine replicas (default 2 with --http, else 1)")
    args = ap.parse_args()
    enable_compile_cache()
    if args.replicas is None:
        args.replicas = 2 if args.http else 1

    if args.http:
        serve_http(args)
        return

    cfg, params = load_model(args.arch, args.full, args.seed)
    cluster = build_cluster(cfg, params, full=args.full,
                            replicas=args.replicas, seed=args.seed)
    scale = args.time_scale
    if scale is None:
        scale = 1.0 if args.full else 0.02
    reqs = generate_workload(args.scenario, args.rate, args.duration,
                             args.seed)[:args.max_requests]
    for r in reqs:
        for i, s in enumerate(r.stages):
            r.stages[i] = type(s)(s.slo, max(4, int(s.length * scale)))
        cluster.submit(r)
    stats = cluster.run_until_idle()
    print(f"served {stats.served}/{stats.submitted} requests "
          f"({stats.dropped} dropped), {stats.tokens_out} tokens generated "
          f"by the engine, SLO attained {stats.attained}/{stats.served} "
          f"(virtual time {cluster.clock:.1f}s)")
    c = {k: sum(d.engine.counters[k] for d in cluster.drivers)
         for k in ("prefill_calls", "decode_calls", "decode_tokens")}
    per_call = c["decode_tokens"] / max(c["decode_calls"], 1)
    print(f"device calls: {c['prefill_calls']} prefill chunks, "
          f"{c['decode_calls']} fused decode scans "
          f"({c['decode_tokens']} tokens, {per_call:.1f} tokens/scan)")


if __name__ == "__main__":
    main()

"""Benchmark of multi-SLO serving on the chip: one cell per run.

  python3 bench/run.py --workload NAME --seed N --seconds S --trace 0|1

builds the cell's model through the program's own entry, with the
program config and the weights from the seed that its configuration's
model family gives (``bench/families/<family>.py``), warms every shape
its traffic can reach, serves the traffic open-loop on the wall clock
for ``--seconds``, checks the served tokens against the family's plain
reference, and prints one JSON line last on standard output.
``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics from a profiler trace of the window.  It exits 3,
printing no result, where JAX finds no TPU or too few chips.

Modes the benchmark's own runs do not use, to set the cell files:

  --zero-load             single requests on an idle server across the
                          traffic's prompt lengths (the TTFT fit)
  --sweep R1,R2,..        the traffic at several fixed rates, on each of
                          --seeds, one set-up
  --readings S1,S2,..     the program's and the control's readings of
                          the comparison on several seeds in one process,
                          each through the verdict (the control's has to
                          come out not correct)
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
# A traced run traces the window's last TRACE_TAIL_S seconds and the step
# in flight at its close: writing and reading the trace takes about 3 s
# per traced second on the chip, so a trace of the whole window (51-72 s)
# would take the run past its time limit.
TRACE_TAIL_S = 20.0


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def chips(n: int):
    """The devices, or exit 3 where there is no TPU or too few chips."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < n:
        log(f"needs {n} TPU chip(s); JAX found {len(devs)} "
            f"{devs[0].platform} device(s)")
        sys.exit(3)
    return devs


def ttft_fit(cfg_file: dict):
    z = cfg_file["zero_load_ttft_s"]
    return lambda p: z["a"] + z["b"] * p


def dtype_of(cfg_file: dict):
    import jax.numpy as jnp
    return {"float32": jnp.float32,
            "bfloat16": jnp.bfloat16}[cfg_file["config"]["torch_dtype"]]


def setup(cfg_file, fam, name, seed, telemetry, full=True):
    """The cluster serving the weights of ``seed``, built through the
    model family ``fam`` (``spec.family(cfg_file)``)."""
    from bench.lib import driver
    dims = fam.dims(cfg_file)
    params = fam.make_weights(dims, seed, dtype_of(cfg_file))
    cluster = driver.build(fam.program_config(cfg_file, name), params,
                           seed, telemetry, full)
    return dims, cluster


def check(outs, prompts, fam, dims, seed, limits, w32=None, control=False):
    """Compare a sample of the finished requests, drawn from the seed with
    the longest among them, against the family's plain reference.  Returns
    the readings: the widest gap of a served token's reference logit below
    the reference's best (and, with ``control``, the control's)."""
    import numpy as np

    done = [o for o in outs if o.finished and not o.dropped]
    if not done:
        return {"sampled_requests": 0, "sampled_tokens": 0}
    longest = max(done, key=lambda o: o.prompt_len + o.out_len)
    rest = [o for o in done if o is not longest]
    rng = np.random.default_rng(seed)
    pick = [longest] + [rest[i] for i in rng.permutation(len(rest))[
        :limits["sample_requests"] - 1]]
    gap, cgap, n = 0.0, 0.0, 0
    for o in pick:
        served = o.tokens[:o.out_len]
        g = fam.served_gaps(dims, w32, prompts[o.rid], served)
        gap = max(gap, float(g.max()))
        n += len(served)
        if control:
            cg = fam.control_gaps(dims, w32, prompts[o.rid], served)
            cgap = max(cgap, float(cg.max()))
    out = {"sampled_requests": len(pick), "sampled_tokens": n,
           "max_logit_gap": gap}
    if control:
        out["control_max_logit_gap"] = cgap
    return out


def verdict(got: dict, limits: dict) -> tuple[bool, dict]:
    """``correct`` and each compared number beside its limit."""
    checks = {"max_logit_gap": {"value": got.get("max_logit_gap"),
                                "limit": limits["max_logit_gap"]}}
    if "compiles_in_window" in got:
        # a program the set-up did not warm compiles inside the window
        checks["compiles_in_window"] = {
            "value": got["compiles_in_window"], "limit": 0}
    ok = (got["sampled_requests"] > 0
          and all(c["value"] <= c["limit"] for c in checks.values()))
    return ok, checks


def serve(cfg_file, fam, traffic, rate, seconds, seed, name, drain_s, trace,
          full=True, on_cluster=None):
    """Set up, warm, and run one window.  Returns (run record pieces)."""
    from bench.lib import driver, traffic as gen
    reqs = gen.generate(traffic, rate, seconds, seed,
                        fam.dims(cfg_file).vocab)
    t = time.perf_counter()
    dims, cluster = setup(cfg_file, fam, name, seed, trace, full)
    t_built = time.perf_counter()
    n_prog = driver.warm(cluster, max(len(r.prompt) for r in reqs),
                         max(r.out_len for r in reqs)) if full else 0
    t_warm = time.perf_counter()
    driver.warm_request(cluster)
    log(f"set-up: imports {t - T_START:.3f} s, weights and cluster "
        f"{t_built - t:.3f} s, {n_prog} programs {t_warm - t_built:.3f} s, "
        f"warm request {time.perf_counter() - t_warm:.3f} s")
    if on_cluster is not None:
        on_cluster(cluster)
    calls = []
    if trace:
        calls = driver.instrument(cluster)
        shutil.rmtree(driver.TRACE_DIR, ignore_errors=True)
    counter = driver.CompileCounter()
    counter.armed = True

    def close():
        counter.armed = False
        if trace:
            import jax
            jax.profiler.stop_trace()

    res = driver.run_window(
        cluster, reqs, seconds, drain_s, ttft_fit(cfg_file),
        trace_from=max(0.0, seconds - TRACE_TAIL_S) if trace else None,
        on_close=close)
    red = None
    if trace:
        from bench.lib.trace import reduce
        t = time.perf_counter()
        red = reduce(str(next(driver.TRACE_DIR.rglob("*.xplane.pb"))))
        shutil.rmtree(driver.TRACE_DIR, ignore_errors=True)
        log(f"trace: {res['t_end'] - res['t_trace']:.3f} s traced, written "
            f"in {res['t_drain'] - res['t_end']:.3f} s, read in "
            f"{time.perf_counter() - t:.3f} s")
    res.update(reqs=reqs, dims=dims, cluster=cluster, trace=red,
               programs=n_prog, compiles=counter, calls=calls)
    return res


def report_window(res) -> None:
    import numpy as np
    late = np.asarray(res["late_s"]) * 1000
    clipped = sum(r.clipped for r in res["reqs"])
    log(f"window {res['t_end'] - res['t0']:.3f} s, drain "
        f"{res['t_drain'] - res['t_end']:.3f} s, {len(res['reqs'])} "
        f"requests due, {clipped} clipped "
        f"({100.0 * clipped / len(res['reqs']):.1f}%)")
    log(f"generator lateness ms: p50 {np.percentile(late, 50):.3f} "
        f"p90 {np.percentile(late, 90):.3f} max {late.max():.3f}")
    c = res["compiles"]
    log(f"warmed programs {res['programs']}; in the window: "
        + ", ".join(f"{k.rsplit('/', 1)[1]} {v}"
                    for k, v in c.counts.items())
        + f" ({c.seconds:.3f} s: {dict(sorted(c.names.items())[:8])})")
    log(f"engine counters over the window: {res['counters']}")


def cell_run(args) -> int:
    from bench.lib import roofline, spec
    from bench.lib.record import Run
    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    devs = chips(w["chips"])
    from bench.lib import driver
    driver.enable_cache()
    cfg_file = spec.config_file(bench, w["config"])
    fam = spec.family(cfg_file)
    traffic = spec.traffic_file(w["traffic"])
    cell = spec.cell_file(w["name"])
    dev = devs[0]
    pk = roofline.peaks(dev.device_kind)
    trace = bool(args.trace)
    res = serve(cfg_file, fam, traffic, cell["rate_per_s"], args.seconds,
                args.seed, w["config"], traffic["drain_s"], trace)
    report_window(res)
    run = Run(outs=res["outs"], t0=res["t0"], t_end=res["t_end"],
              t_drain=res["t_drain"], setup_s=res["t0"] - T_START,
              counters=res["counters"], plan=res["plan"], trace=res["trace"],
              family=fam, dims=res["dims"], peaks=pk, calls=res["calls"],
              t_trace=res["t_trace"])
    section = "per_layer" if trace else "end_to_end"
    metrics = {}
    for m in spec.metrics_of(bench, w["name"], section):
        v = spec.reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    peak = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)
    attempted = len(res["outs"])
    failed = sum(o.dropped or (traffic["drain_s"] > 0 and not o.finished)
                 for o in res["outs"])
    prompts = {r.rid: r.prompt for r in res["reqs"]}
    outs, red = res["outs"], res["trace"]
    compiles = res["compiles"].backend_compiles()
    del res, run
    driver.free_device()
    dims = fam.dims(cfg_file)
    limits = cell["check"]
    t = time.perf_counter()
    got = check(outs, prompts, fam, dims, args.seed, limits,
                fam.make_weights(dims, args.seed))
    got["compiles_in_window"] = compiles
    log(f"reference: {got['sampled_requests']} requests, "
        f"{got['sampled_tokens']} served tokens compared in "
        f"{time.perf_counter() - t:.3f} s")
    correct, checks = verdict(got, limits)
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    line = {"correct": bool(correct), "attempted": attempted,
            "failed": int(failed), "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red["busy_s"]
        device["window_s"] = red["window_s"]
        line["breakdown"] = {"device_ops": red["device_ops"],
                             "idle_gaps": red["idle_gaps"]}
        log(f"idle by host span (s): {red['idle_by_span']}")
    for k, v in checks.items():
        log(f"check {k} {v['value']} limit {v['limit']}")
    line["checks"] = checks
    print(json.dumps(line), flush=True)
    return 0


def tool_run(args) -> int:
    """--zero-load, --sweep, --readings: one process, one set-up."""
    import numpy as np

    from bench.lib import driver, judge, spec, traffic as gen
    bench = spec.benchmark()
    w = spec.workload(bench, args.workload)
    chips(w["chips"])
    driver.enable_cache()
    cfg_file = spec.config_file(bench, w["config"])
    fam = spec.family(cfg_file)
    traffic = spec.traffic_file(w["traffic"])
    dims = fam.dims(cfg_file)
    out = {"workload": w["name"], "mode": None, "rows": []}
    if args.zero_load:
        out["mode"] = "zero_load"
        lens = [int(x) for x in args.zero_load.split(",")]
        _, cluster = setup(cfg_file, fam, w["config"], args.seed, False)
        driver.warm(cluster, max(lens), 2)
        driver.warm_request(cluster)
        rng = np.random.default_rng(args.seed)
        for rep in range(args.repeats):
            for L in lens:
                r = gen.Req(rid=rep * 1000 + L, due=0.0, cls="zero-load",
                            prompt=rng.integers(1, dims.vocab, L).tolist(),
                            out_len=2, ttft_slowdown=5.0, tpot_s=0.1,
                            clipped=False)
                res = driver.run_window(cluster, [r], 0.0, 30.0,
                                        lambda p: 1.0)
                o = res["outs"][0]
                out["rows"].append({"prompt": L, "ttft_s": o.first - o.due})
                log(f"zero-load prompt {L}: ttft {o.first - o.due:.4f} s")
        med = {L: float(np.median([x["ttft_s"] for x in out["rows"]
                                   if x["prompt"] == L])) for L in lens}
        b, a = np.polyfit(list(med), list(med.values()), 1)
        out["fit"] = {"a": float(a), "b": float(b), "medians": med}
    elif args.sweep:
        out["mode"] = "sweep"
        rates = [float(x) for x in args.sweep.split(",")]
        seeds = [int(x) for x in args.seeds.split(",") if x] or [args.seed]
        reqs_all = {(s, r): gen.generate(traffic, r, args.seconds, s,
                                         dims.vocab)
                    for s in seeds for r in rates}
        _, cluster = setup(cfg_file, fam, w["config"], args.seed, False)
        driver.warm(cluster,
                    max(len(q.prompt) for v in reqs_all.values() for q in v),
                    max(q.out_len for v in reqs_all.values() for q in v))
        driver.warm_request(cluster)
        counter = driver.CompileCounter()
        for seed in seeds:
            for rate in rates:
                counter.reset()
                counter.armed = True
                res = driver.run_window(cluster, reqs_all[seed, rate],
                                        args.seconds, args.drain,
                                        ttft_fit(cfg_file))
                counter.armed = False
                outs = res["outs"]
                row = {"seed": seed, "rate": rate, "requests": len(outs),
                       "window_s": res["t_end"] - res["t0"],
                       "tok_s": judge.tokens_per_s(outs, res["t0"],
                                                   res["t_end"]),
                       "attained_pct": judge.attainment_pct(outs),
                       "p90_ttft_ms": 1000 * judge.ttft_p(
                           outs, 90, res["t_drain"]),
                       "p90_tpot_ms": 1000 * (judge.tpot_p(outs, 90) or 0),
                       "first_tokens_in_window": sum(
                           o.first is not None and o.first <= res["t_end"]
                           for o in outs),
                       "unfinished_at_close": sum(
                           not (o.finished and o.last <= res["t_end"])
                           for o in outs),
                       "late_p90_ms": 1000 * float(np.percentile(
                           res["late_s"], 90)),
                       "compiles": counter.backend_compiles()}
                out["rows"].append(row)
                log(json.dumps(row))
                driver.clear(cluster, outs)
    elif args.readings:
        out["mode"] = "readings"
        cell = spec.cell_file(w["name"])
        seeds = [int(x) for x in args.readings.split(",")]
        reqs_all = {s: gen.generate(traffic, cell["rate_per_s"],
                                    args.seconds, s, dims.vocab)
                    for s in seeds}
        _, cluster = setup(cfg_file, fam, w["config"], seeds[0], False)
        driver.warm(cluster,
                    max(len(q.prompt) for v in reqs_all.values() for q in v),
                    max(q.out_len for v in reqs_all.values() for q in v))
        driver.warm_request(cluster)
        counter = driver.CompileCounter()
        for seed in seeds:
            # the same programs with the seed's weights in place; no
            # prefix can hit across seeds: every prompt is random tokens
            driver.swap_weights(cluster, lambda: fam.make_weights(
                dims, seed, dtype_of(cfg_file)))
            reqs = reqs_all[seed]
            counter.reset()
            counter.armed = True
            res = driver.run_window(cluster, reqs, args.seconds, args.drain,
                                    ttft_fit(cfg_file))
            counter.armed = False
            driver.clear(cluster, res["outs"])
            params = cluster.drivers[0].engine.params
            got = check(res["outs"], {r.rid: r.prompt for r in reqs}, fam,
                        dims, seed, cell["check"], params, control=True)
            got["compiles_in_window"] = counter.backend_compiles()
            ok, checks = verdict(got, cell["check"])
            c_ok, c_checks = verdict(dict(got, max_logit_gap=got[
                "control_max_logit_gap"]), cell["check"])
            row = {"seed": seed, "correct": ok, "checks": checks,
                   "control_correct": c_ok, "control_checks": c_checks,
                   **got}
            out["rows"].append(row)
            log(json.dumps(row))
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--zero-load", default="")
    ap.add_argument("--repeats", type=int, default=3)
    ap.add_argument("--sweep", default="")
    ap.add_argument("--drain", type=float, default=30.0)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--readings", default="")
    args = ap.parse_args(argv)
    if args.zero_load or args.sweep or args.readings:
        return tool_run(args)
    return cell_run(args)


if __name__ == "__main__":
    sys.exit(main())

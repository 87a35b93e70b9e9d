#!/usr/bin/env python3
"""perfbench/run.py - one run of one cell of BENCHMARK.json.

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (boot, native library, SRS, proving key, one warm-up served prove of
a committee the window never uses), then the window, then the comparison
that decides `correct`. The last line of standard output is the result.
Exit codes: 0 a result line was printed (read `correct` in it); 2 no result
(no accelerator, unknown cell, a directory without the program)."""

from __future__ import annotations

import time

T0 = time.time()          # process start, as near as Python lets us read it

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.dirname(HERE))

from harness import cells, device, hostside, loadgen, warmup, workdir  # noqa: E402


EXIT_AFTER_RESULT = False     # set when run as the command


def log(msg: str):
    print(f"[perfbench {time.time() - T0:7.1f}s] {msg}", file=sys.stderr,
          flush=True)


def read_metrics(entries: list, ctx: dict) -> dict:
    out = {}
    for m in entries:
        value = cells.load_plugin("metrics", m["name"]).read(ctx)
        if value is None:            # nothing to read: left out of the line
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def run(opts, bench_path=None) -> int:
    cell = cells.load_cell(opts.workload, bench_path)
    config, traffic = cell.config, cell.traffic
    if not os.path.isdir(os.path.join(cells.ROOT, "spectre_tpu")):
        raise cells.BenchError("no spectre_tpu/ beside perfbench/: nothing "
                               "to measure")
    for key, val in (config.get("env") or {}).items():
        os.environ[key] = str(val)
    dev = device.require_platform(config["platform"], cell.chips)
    log(f"cell {cell.name}: device {json.dumps(dev)}")
    paths = workdir.prepare(config)

    requests = cells.load_plugin("requests", config["circuit"])
    reference = cells.load_plugin("reference", config["circuit"])
    served = cells.load_plugin("servers", config.get("server", "single")) \
        .boot(config, traffic, paths)
    try:
        return measure(opts, cell, dev, paths, requests, reference, served)
    finally:
        served.close()


def warm_up(opts, config, requests, served, client, methods):
    """One served prove of a committee of its own. The cyclic GC is paused
    for it (tracing and lowering 60 programs allocates tens of millions of
    objects that hold no cycles), and so is the host verifier, which is
    plain Python with nothing to warm; and a backend operation the
    configuration names under `warmup` runs each shape a few times and is
    answered from its last run after (harness/warmup.py says what that
    reaches on which backend). All three only shorten set-up, which every
    run of every later check pays in full."""
    warm = loadgen.Sent(index=-1,
                        request=requests.make(config, opts.seed, "warmup"))
    gc.disable()
    policy = os.environ.get("SPECTRE_SELF_VERIFY")
    os.environ["SPECTRE_SELF_VERIFY"] = "off"
    shapes = warmup.EachShape(
        served.backend(), (config.get("warmup") or {}).get("run_each_shape"),
        when=served.key_ready)
    warm.t_send = time.time()
    try:
        with shapes:
            warm.result = client._call(methods[0], warm.request["params"])
    finally:
        if policy is None:
            del os.environ["SPECTRE_SELF_VERIFY"]
        else:
            os.environ["SPECTRE_SELF_VERIFY"] = policy
        gc.enable()
    warm.t_done = time.time()
    loadgen.attach_records(client, methods[1], [warm])
    if warm.error:
        raise RuntimeError(f"warm-up request: {warm.error}")
    comp = warm.manifest["compile"]
    log(f"warm-up prove {warm.t_done - warm.t_send:.1f}s "
        f"(compile.count={comp['count']} compile.seconds={comp['seconds']} "
        f"persistent_cache={comp.get('persistent_cache')}); backend calls "
        f"in it: {json.dumps(shapes.summary())}")
    by_kind: dict = {}
    for e in comp.get("events", ()):
        if "seconds" in e:
            by_kind[e["event"]] = by_kind.get(e["event"], 0.0) + e["seconds"]
    log("warm-up compile events, seconds by kind: " + json.dumps(by_kind))
    log("warm-up phase seconds: " + json.dumps(warm.manifest["phase_seconds"]))
    return warm


def trace_whole_calls(calls, paths, dev) -> tuple:
    """Once the window has closed: one profiler session round a replay of
    one whole call of each operation and shape the window used and that is
    short enough (harness/tracing.py). Returns the session (`busy_s`,
    `window_s`: what the profiler saw, no more) and the estimate built from
    its calls and the window's exact counts."""
    from harness import trace_reduce, tracing
    got = tracing.replay(calls.last, paths["trace"],
                         dev["platform"] == "tpu", trace_reduce.load_xplane)
    parts = [dict(trace_reduce.reduce_call(got["rows"], c["session"]), **c)
             for c in got["calls"]]
    session = trace_reduce.reduce_session(got["rows"])
    log(f"replay: {len(parts)} whole call(s) under one profiler session, "
        f"stop_trace {got['stop_s']:.1f}s, {got['bytes']} bytes; the device "
        f"ran {session['busy_s']:.6f}s of the session's "
        f"{session['window_s']:.6f}s on {session['devices']} device plane(s)")
    return session, trace_reduce.estimate(parts, calls.calls)


def log_trace(est, calls):
    """The earlier lines of a traced run: every operation and shape the
    backend was asked for, what the profiler took, and what that covers."""
    for s in est["shapes"]:
        per_call = s["device_call_s"]
        log(f"backend {s['kind']:5s} {s['op']}({s['shape']}): "
            f"{s['calls']} call(s), host {s['host_s']:.4f}s, device "
            + (f"{per_call:.6f}s a call x {s['calls']} = {s['device_s']:.4f}s"
               if per_call is not None else "not traced"))
    held: dict = {}
    for c in calls.composite:
        held[f"{c.op}({c.shape})"] = held.get(f"{c.op}({c.shape})", 0) + 1
    log("backend calls that hold other calls (not counted): "
        + json.dumps(held))
    log(f"by kind {json.dumps(est['kinds'])}; traced shapes cover "
        f"{100 * est['covered']:.2f}% of the host seconds inside backend "
        f"calls")


def measure(opts, cell, dev, paths, requests, reference, served) -> int:
    config, traffic = cell.config, cell.traffic
    methods = (requests.METHOD, requests.SUBMIT_METHOD)
    client = served.client()
    log(f"serving on {served.url}; work dir {paths['work']}")

    # -- what only a traced run has: a wrapper round every operation of the
    # backend object (on for the warm-up too, so that both kinds of run
    # warm up alike), and after the window the profiler round whole calls
    # of it. A `--trace 0` run drives the backend's own methods
    traced = bool(opts.trace)
    calls = None
    wrapped = contextlib.ExitStack()
    if traced:
        from harness import breakdown, tracing
        calls = wrapped.enter_context(tracing.BackendCalls(served.backend()))
    with wrapped:
        warm = warm_up(opts, config, requests, served, client, methods)

        # -- the window ---------------------------------------------------
        before = served.counters()
        window = loadgen.Window(
            served, traffic, lambda i: requests.make(config, opts.seed, i),
            methods, opts.seconds)
        host = hostside.Watch()
        setup_s = time.time() - T0
        if traced:
            calls.reset()
        with host:
            window.run()
    after = served.counters()
    peak = device.memory_peak_bytes()
    log(f"window {window.wall_s:.1f}s: {len(window.sent)} sent, "
        f"{len(window.ok)} served; host side: {json.dumps(host.summary())}")
    loadgen.attach_records(client, methods[1], window.sent)
    # every run says where its seconds went, so that one that lands 3 % off
    # says in which phase
    for s in window.sent[:8]:
        if s.manifest:
            log(f"request {s.index}: sent at {s.t_send - window.t_first:.3f}s, "
                f"{s.t_done - s.t_send:.3f}s; phase "
                f"seconds: {json.dumps(s.manifest['phase_seconds'])}")

    session = trace = None
    if traced:
        session, trace = trace_whole_calls(calls, paths, dev)
        log_trace(trace, calls)

    # -- correct ----------------------------------------------------------
    verdict = decide(config, reference, served, window, before, after)

    ctx = {"config": config, "traffic": traffic, "cell": cell.name,
           "device": dev, "setup_s": setup_s, "window": window,
           "warmup": warm, "trace": trace,
           "backend_calls": calls.calls if calls else None,
           "memory_peak_bytes": peak}
    metrics = read_metrics(cell.per_layer if traced else cell.end_to_end,
                           ctx)
    dev_out = dict(dev, memory_peak_bytes=peak)
    result = {"correct": verdict["correct"], "attempted": len(window.sent),
              "failed": verdict["failed"], "metrics": metrics,
              "device": dev_out}
    if traced:
        # what the profiler saw, and only that: the replay session, not the
        # window (PERF.md section 3)
        if session["devices"] and session["busy_s"]:
            dev_out["busy_s"] = session["busy_s"]
            dev_out["window_s"] = session["window_s"]
        result["breakdown"] = breakdown.build(ctx)
    result["checks"] = verdict["checks"]
    for name, (value, limit) in verdict["checks"].items():
        print(f"check {name} {value} limit {limit}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    if EXIT_AFTER_RESULT:
        # the orderly close of the service (run()'s `finally`, for callers
        # that live on) takes ~4 s; a run's process starts no other process
        # and has printed all it has to say
        sys.stderr.flush()
        os._exit(0)
    return 0


def decide(config, reference, served, window, before, after) -> dict:
    """Every number compared, each beside its limit (all exact: limit 0).
    The reference runs once the window has closed and the peak is read."""
    ticked = sum(after.get(c, 0) - before.get(c, 0)
                 for c in served.zero_counters)
    compiles = sum(s.manifest["compile"]["count"] for s in window.sent
                   if s.manifest)
    unverified = len(window.ok) - (after.get("proofs_verified", 0)
                                   - before.get("proofs_verified", 0))
    t = time.time()
    ref = reference.Reference(config, served.verifying_key())
    rejected = 0
    for s in window.sent:
        if s.error is None and s.result:
            why = ref.check(s.request, s.result)
            if why:
                rejected += 1
                s.error = f"reference: {why}"
                log(f"request {s.index}: {s.error}")
    log(f"reference checked {len(window.ok) + rejected} proof(s) in "
        f"{time.time() - t:.1f}s")
    errors = sum(1 for s in window.sent if s.error is not None)
    checks = {
        "requests_failed": (errors - rejected, 0),
        "proofs_rejected_by_reference": (rejected, 0),
        "vk_digest_mismatch": (int(not ref.vk_ok), 0),
        "served_without_verify": (unverified, 0),
        "fallback_counters_ticked": (ticked, 0),
        "compiles_in_window": (compiles, 0),
    }
    correct = all(value == limit for value, limit in checks.values())
    return {"correct": bool(correct), "failed": errors, "checks": checks}


def main(argv=None, bench_path=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    opts = ap.parse_args(argv)
    try:
        return run(opts, bench_path)
    except cells.BenchError as exc:
        print(f"perfbench: no result: {exc}", file=sys.stderr, flush=True)
        return 2


if __name__ == "__main__":
    EXIT_AFTER_RESULT = True
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)      # no thread of the service may hold the exit up

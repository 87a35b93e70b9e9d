"""Calibrate the program's device timeline against the profiler (ISSUE 40).

The timeline (spectre_tpu/observability/tracing.py) stamps when each mark's
outputs are ready, on the spans' clock, from a watcher thread that needs the
interpreter lock to stamp; an upload (`on_upload`) has a mark of its own, so
that a program's stretch starts once its input has arrived. This script
opens ONE profiler session around whole calls of the cell's shapes
(k = 14), each under a job trace:

- `backend/ntt`, `backend/intt`, `backend/intt_many` (six columns): the NTT
  kinds, two crossings a call;
- `backend/msm_many` of two narrow columns against the Lagrange base: the
  two-column `lookup_permute` run (0.033 s on the chip, PR 38), and
  `backend/msm` of one such column;
- one LDE chunk of the quotient (eight columns, `quotient/extend/dispatch`
  as `quotient_device.py:ext_of_many` marks it, then a read).

It maps the timeline onto the profiler's host clock through the
`TraceAnnotation` every span writes (the median offset of the calls' own
spans) and the device's events onto that by the shift that puts the most
of them inside the stretches (`best_skew`: the session's device clock is
off the host's by a millisecond or two), then for every call sets the
timeline's device seconds (upper and lower reading) against the union of
the `XLA Modules` events that begin inside the call, and each stamp
against the end of the last program inside its stretch (the stamp lag).
Besides, the cost of a mark on the job thread.

    chiprun -- python scripts/calibrate_device_timeline.py

writes `chiprun_out/calibrate_device_timeline.json` and prints a summary.
`--cpu` rehearses it here (no device plane: no comparison)."""

from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import statistics
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)
sys.path.insert(0, os.path.join(ROOT, "perfbench"))

CALLS = ("backend/ntt", "backend/intt", "backend/intt_many", "backend/msm",
         "backend/msm_many", "quotient/extend")


def columns(rng, n: int, count: int, bits: int | None = None) -> list:
    """Field elements as [n, 4] u64 standard limbs: full width (the top
    limb under 2^61 < R's) or `bits` wide."""
    out = []
    for _ in range(count):
        if bits is None:
            c = rng.integers(0, 2**63, size=(n, 4), dtype=np.uint64)
            c[:, 3] >>= np.uint64(2)
        else:
            c = np.zeros((n, 4), dtype=np.uint64)
            c[:, 0] = rng.integers(0, 1 << bits, size=n, dtype=np.uint64)
        out.append(c)
    return out


def run_calls(bk, srs, dom, cols, narrow, lde_packed, engine):
    import jax

    from spectre_tpu.observability import tracing

    omega = dom.omega
    bk.ntt(cols[0], omega)
    bk.intt(cols[1], omega)
    bk.intt_many(cols[:6], omega)
    bk.msm_many(srs.g1_lagrange, narrow, basis="lagrange")
    bk.msm(srs.g1_lagrange, narrow[0], basis="lagrange")
    with tracing.span("quotient/extend"):
        with tracing.span("quotient/extend/dispatch"):
            outs = engine.lde(lde_packed)
            tracing.on_device(outs)
        with tracing.span("quotient/wait"):
            jax.block_until_ready(outs)


def mark_cost(n: int) -> dict:
    """Seconds a mark costs the job thread: `on_device` of a ready array
    inside a dispatch span, n times, the watcher running beside it."""
    import jax.numpy as jnp

    from spectre_tpu.observability import tracing

    x = jnp.ones(8)
    x.block_until_ready()
    with tracing.trace("mark-cost") as tr:
        with tracing.span("backend/probe/dispatch"):
            t = time.perf_counter()
            for _ in range(n):
                tracing.on_device(x)
            per_mark = (time.perf_counter() - t) / n
        t = time.perf_counter()
    drain = time.perf_counter() - t       # the trace's exit waited for it
    return {"marks": len(tr.device), "us_per_mark": per_mark * 1e6,
            "drain_s": drain}


def profile_rows(path: str):
    """Module events of the device planes and the program's annotations."""
    from jax.profiler import ProfileData

    from harness import trace_reduce

    modules, ann, other = [], [], []
    for plane in ProfileData.from_file(path).planes:
        dev = trace_reduce.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            for ev in line.events:
                t0 = int(ev.start_ns) / 1e9
                t1 = t0 + int(ev.duration_ns) / 1e9
                if dev and line.name in trace_reduce.MODULE_LINES:
                    modules.append((t0, t1, ev.name))
                elif dev:
                    other.append((t0, t1, line.name))
                elif ev.name in CALLS or ev.name.endswith("/dispatch"):
                    ann.append((t0, t1, ev.name))
    return sorted(modules), sorted(ann), sorted(other)


def union(iv) -> float:
    tot, cur = 0.0, None
    for a, b in sorted(iv):
        if cur is None or a > cur[1]:
            if cur:
                tot += cur[1] - cur[0]
            cur = [a, b]
        else:
            cur[1] = max(cur[1], b)
    return tot + (cur[1] - cur[0] if cur else 0.0)


def best_skew(modules, stretches, reach: float = 5e-3,
              step: float = 1e-5) -> tuple:
    """The shift of the device's clock onto the host's that puts the most
    module events wholly inside the timeline's stretches, on a grid of
    `step` over +-`reach`: (the middle of the shifts that do, the
    half-width of their range, how many fall inside). The session's
    device events can read as starting before the host enqueued them, so
    they are not taken on the annotations' clock as they are."""
    los = [lo for lo, _ in stretches]

    def inside(skew):
        n = 0
        for a, b, _ in modules:
            i = bisect.bisect_right(los, a + skew) - 1
            n += i >= 0 and b + skew <= stretches[i][1]
        return n

    grid = [k * step for k in range(-round(reach / step),
                                    round(reach / step) + 1)]
    counts = [inside(g) for g in grid]
    best = max(counts)
    fits = [g for g, c in zip(grid, counts) if c == best]
    return (fits[0] + fits[-1]) / 2, (fits[-1] - fits[0]) / 2, best


def compare(traces, modules, ann, other=()) -> dict:
    """Per call: the profiler's union of module events against the
    timeline's stretches of its programs (the uploads' stretches, which
    hold no module, beside them as `upload_s`); per mark: the stamp lag,
    the stamp less the end of the last program inside the mark's stretch
    (within +- the skew's half-range)."""
    from spectre_tpu.observability import tracing

    spans = []
    for tr in traces:
        for call in tr.root.children:
            spans.append((call, tr))
            spans += [(d, tr) for d in call.children
                      if d.name.endswith("/dispatch")]
    spans.sort(key=lambda st: st[0].t0)
    named = [a for a in ann if a[2] in {s.name for s, _ in spans}]
    offsets = [a[0] - s.t0 for (s, _), a in zip(spans, named)
               if a[2] == s.name]
    if len(offsets) != len(spans) or not offsets:
        return {"error": f"{len(named)} annotations for {len(spans)} spans"}
    off = statistics.median(offsets)
    skew, skew_pm, fitted = best_skew(modules, sorted(
        (m[3] + off, m[5] + off) for tr in traces for m in tr.device))
    modules = [(a + skew, b + skew, name) for a, b, name in modules]
    other = [(a + skew, b + skew, line) for a, b, line in other]
    rows, lags = [], []
    for call, tr in ((s, tr) for s, tr in spans if s.name in CALLS):
        lo, hi = call.t0 + off, call.t1 + off
        mods = [(max(a, lo), min(b, hi)) for a, b, _ in modules
                if lo <= a < hi]
        inside = [m for m in tr.device if call.t0 <= m[1] <= call.t1]
        up = "/" + tracing.UPLOAD
        marks = [m for m in inside if not m[0].endswith(up)]
        uploads = [m for m in inside if m[0].endswith(up)]
        upper = sum(m[5] - m[3] for m in marks)
        lower = sum(m[5] - m[4] for m in marks)
        for m in marks:
            done = m[5] + off
            ends = [b for a, b in mods if m[3] + off <= a <= done]
            if ends:
                lags.append(done - max(ends))
        lines: dict = {}
        for a, b, line in other:
            if lo <= a < hi:
                lines.setdefault(line, []).append((a, min(b, hi)))

        def ms(t):
            return round((t - lo) * 1e3, 4)

        rows.append({"call": call.name, "profiler_s": union(mods),
                     "timeline_upper_s": upper, "timeline_lower_s": lower,
                     "upload_s": sum(m[5] - m[3] for m in uploads),
                     "modules": len(mods), "marks": len(marks),
                     # where the marks and the programs lie, ms from the
                     # call's start on the spans' clock
                     "mark_ms": [[m[0].rsplit("/", 1)[-1],
                                  ms(m[1] + off), ms(m[2] + off),
                                  ms(m[3] + off), ms(m[5] + off)]
                                 for m in inside],
                     "module_ms": [[ms(a), ms(b), name]
                                   for a, b, name in modules
                                   if lo <= a < hi],
                     "other_lines_s": {k: union(v)
                                       for k, v in lines.items()}})
    kinds = {}
    for r in rows:
        kinds.setdefault(r["call"], []).append(r)
    out = {"offset_spread_s": max(offsets) - min(offsets),
           "device_skew_s": skew, "device_skew_pm_s": skew_pm,
           "modules_inside": fitted, "modules": len(modules), "kinds": {}}

    def agree(rs, key):
        return all(abs(r[key] - r["profiler_s"])
                   <= max(1e-3, 0.1 * r["profiler_s"]) for r in rs)

    for name, rs in kinds.items():
        out["kinds"][name] = {
            "calls": len(rs),
            "profiler_s": statistics.median(r["profiler_s"] for r in rs),
            "timeline_upper_s": statistics.median(
                r["timeline_upper_s"] for r in rs),
            "timeline_lower_s": statistics.median(
                r["timeline_lower_s"] for r in rs),
            "upload_s": statistics.median(r["upload_s"] for r in rs),
            "agree": agree(rs, "timeline_upper_s"),
            "agree_lower": agree(rs, "timeline_lower_s"),
            "rows": rs}
    if lags:
        out["stamp_lag_s"] = {"median": statistics.median(lags),
                              "max": max(lags), "min": min(lags),
                              "n": len(lags)}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--k", type=int, default=14)
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=2147620001)
    ap.add_argument("--mark-cost-n", type=int, default=20000)
    ap.add_argument("--out", default=os.path.join(
        ROOT, "chiprun_out", "calibrate_device_timeline.json"))
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on XLA:CPU (no device plane)")
    opts = ap.parse_args(argv)

    import jax

    platform = jax.devices()[0].platform
    if platform != "tpu" and not opts.cpu:
        print(f"refused: platform {platform!r}, not tpu (--cpu rehearses)",
              file=sys.stderr)
        return 2
    from spectre_tpu.observability import tracing
    from spectre_tpu.ops import limbs as L16
    from spectre_tpu.plonk import backend as B, quotient_device as Q
    from spectre_tpu.plonk.domain import Domain
    from spectre_tpu.plonk.srs import SRS

    os.environ.setdefault("SPECTRE_MESH_SHAPE", "1x1")
    rng = np.random.default_rng(opts.seed)
    n = 1 << opts.k
    dom = Domain(opts.k)
    srs = SRS.unsafe_setup(opts.k)
    bk = B.get_backend("tpu")
    cols = columns(rng, n, 8)
    narrow = columns(rng, n, 2, bits=8)
    engine = Q._LocalEngine(dom)
    lde_packed = L16.pack_u64limbs(B.stack_rows(cols, n))

    t = time.time()
    for _ in range(2):               # compile and warm every program
        with tracing.trace("warm"):
            run_calls(bk, srs, dom, cols, narrow, lde_packed, engine)
    warm_s = time.time() - t

    tdir = tempfile.mkdtemp(prefix="calib-trace-")
    traces = []
    jax.profiler.start_trace(tdir)
    try:
        for rep in range(opts.reps):
            with tracing.trace(f"calibrate-{rep}") as tr:
                run_calls(bk, srs, dom, cols, narrow, lde_packed, engine)
            traces.append(tr)
    finally:
        t = time.time()
        jax.profiler.stop_trace()
        stop_s = time.time() - t
    pbs = sorted(glob.glob(os.path.join(tdir, "**", "*.xplane.pb"),
                           recursive=True))
    modules, ann, other = profile_rows(pbs[-1]) if pbs else ([], [], [])
    result = {"platform": platform,
              "device_kind": jax.devices()[0].device_kind,
              "k": opts.k, "reps": opts.reps, "warm_s": warm_s,
              "stop_trace_s": stop_s, "module_events": len(modules),
              "annotations": len(ann),
              "summary": [tracing.device_summary(tr) for tr in traces],
              "compare": (compare(traces, modules, ann, other)
                          if modules else None),
              "mark_cost": mark_cost(opts.mark_cost_n)}
    os.makedirs(os.path.dirname(opts.out), exist_ok=True)
    with open(opts.out, "w") as f:
        json.dump(result, f, indent=1)
    short = {k: v for k, v in result.items() if k not in ("summary",)}
    if short["compare"] and "kinds" in short["compare"]:
        short["compare"] = dict(short["compare"], kinds={
            k: {kk: vv for kk, vv in v.items() if kk != "rows"}
            for k, v in short["compare"]["kinds"].items()})
    print(json.dumps(short, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python
"""Benchmark entry point: BN254 MSM + NTT throughput vs measured baselines.

Prints ONE JSON line PER METRIC:
  {"metric": "bn254_msm_2^k throughput", "value": N, "unit": "points/s",
   "vs_baseline": N, "backend": ..., "msm_mode": ..., "impl": ...}
  {"metric": "bn254_ntt_2^k throughput", "value": N, "unit": "polys/s",
   "vs_baseline": N, "backend": ..., "ntt_mode": ..., "impl": "batched"}

MSM metric (north star from BASELINE.md): BN254 MSM points/s (a dominant
prover cost). Baseline = this repo's native C++ single-thread Pippenger
measured on this machine (the reference Rust prover cannot run here; its
MSM is the same algorithm on the same hardware class). `backend` and
`msm_mode` are first-class JSON keys — the metric name is never mangled.

NTT metric (ISSUE 4): batched coset-LDE throughput in polys/s — B columns
of 2^k coefficients extended onto the 4x coset (the quotient-pass shape)
through the batched FUSED kernel (`ops/ntt.py:coset_lde_std`,
SPECTRE_NTT_MODE). Baseline = the pre-PR shape: a per-column jitted
scale-then-radix-2-NTT loop over the same columns on the same platform.
The batched result is checked byte-identical against the per-column loop
in-run, so a kernel bug fails loudly instead of producing a fast wrong
number. `ntt_mode` is a first-class JSON key. BENCH_METRIC=msm|ntt runs
one metric; default runs both.

MSM mode: SPECTRE_MSM_MODE if set, else the full `fixed` stack
(GLV + signed digits + per-SRS precomputed tables, ops/msm.py). The result
is checked in-run against the native oracle, so a mode bug fails loudly
instead of producing a fast wrong number.

Process split: each device phase runs in a SUBPROCESS with a hard deadline,
and this parent never imports JAX — a chip belongs to one process, so a
parent that touched it would starve its own children. A device phase that
fails, times out or comes up on the CPU platform is a FAILURE (non-zero
exit): no number measured on the CPU is ever printed under a device
metric's name. SPECTRE_BENCH_PLATFORM=cpu asks for the pinned-CPU phase
explicitly (the floors `make test` gates on); any other value is pinned
into the child's JAX_PLATFORMS.

`python bench.py --fast` is the CI tier: 2^12 on pinned CPU, compared
against the checked-in floor in bench_floor.json (fails on >20% regression).

`python bench.py --sweep-window` times the MSM at each window width c and
emits one points/s JSON line per width (see bench_sweep_window) — the
measurement behind the default_window tables; SPECTRE_MSM_WINDOW pins a
winner. Every MSM JSON line records the resolved `msm_impl`
(SPECTRE_MSM_IMPL), and `--impl xla|pallas` pins it for the invocation —
the pallas-vs-xla per-width sweep is `--sweep-window --impl pallas`. The NTT child additionally reports `ntt_kernel` and a byte-checked
stages-vs-matmul `kernel_compare` sample (SPECTRE_NTT_KERNEL).

Multichip tier (ISSUE 13): BENCH_METRIC=multichip (= `make bench-multichip`)
forces SPECTRE_BENCH_DEVICES virtual CPU devices in the child, runs the
sharded MSM/NTT micro-kernels (oracle-checked) AND a complete k=13 mesh
prove byte-checked against the host prover, and must finish inside
BENCH_MULTICHIP_TIMEOUT — the JSON carries n_devices, per-device points/s,
the ShardingPlan description, compile + persistent-cache telemetry, and on
failure the child's rc + stderr tail (the MULTICHIP_r01-r05 rc=124 history
is the reason this tier exists).
"""

import json
import os
import subprocess
import sys
import tempfile
import time

import numpy as np

FLOOR_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "bench_floor.json")


def bench_msm_mode() -> str:
    return os.environ.get("SPECTRE_MSM_MODE", "fixed")


def bench_ntt_mode() -> str:
    # radix2 is the measured-faster CPU default for the batched kernel;
    # fourstep is the TPU/MXU-shaped mode (see README "NTT modes")
    return os.environ.get("SPECTRE_NTT_MODE", "radix2")


def build_points(n: int) -> np.ndarray:
    """n distinct affine points as [n, 8] u64 limbs via the native lib."""
    from spectre_tpu.fields import bn254 as bn
    from spectre_tpu.native import host

    base = host.points_to_limbs([bn.G1_GEN])
    arrs = [base]
    total = 1
    while total < n:
        allp = np.concatenate(arrs)
        new = host.g1_add_affine_batch(allp, np.roll(allp, 1, axis=0))
        arrs.append(new)
        total *= 2
    return np.concatenate(arrs)[:n]


def bench_inputs(logn: int):
    n = 1 << logn
    pts64 = build_points(n)
    rng = np.random.default_rng(7)
    sc64 = rng.integers(0, 2**63, size=(n, 4), dtype=np.uint64)
    sc64[:, 3] &= (1 << 61) - 1
    return pts64, sc64


def device_phase(out_path: str):
    """Child process: run the device MSM benchmark; write JSON to out_path.

    BENCH_FORCE_CPU=1 pins the CPU platform (SPECTRE_BENCH_PLATFORM=cpu)."""
    if os.environ.get("BENCH_FORCE_CPU") == "1":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    from spectre_tpu.plonk.backend import setup_compile_cache
    setup_compile_cache()
    import jax.numpy as jnp

    from spectre_tpu.ops import ec, field_ops as F, limbs as L, msm as MSM

    logn = int(os.environ.get("BENCH_LOGN", "16"))
    n = 1 << logn
    mode = bench_msm_mode()
    # BENCH_C pins the window size; unset -> the mode's own tuning table
    c_env = os.environ.get("BENCH_C")
    c = int(c_env) if c_env else None
    pts64, sc64 = bench_inputs(logn)

    ctxq = F.fq_ctx()
    x16 = L.u64limbs_to_u16limbs(pts64[:, :4])
    y16 = L.u64limbs_to_u16limbs(pts64[:, 4:])
    to_mont = jax.jit(lambda v: F.to_mont(ctxq, v))
    xm, ym = to_mont(jnp.asarray(x16)), to_mont(jnp.asarray(y16))
    one = jnp.broadcast_to(jnp.asarray(ctxq.one_mont), (n, F.NLIMBS))
    pts = jnp.stack([xm, ym, one], axis=1)
    sc16 = jnp.asarray(L.u64limbs_to_u16limbs(sc64))

    def run_aos():
        # The mode dispatch (vanilla/glv/glv+signed/fixed) lives in MSM.msm;
        # the fixed-base table is built+cached on the first (untimed) call.
        return jax.block_until_ready(MSM.msm(pts, sc16, c=c, mode=mode,
                                             base_key=("bench", logn)))

    from spectre_tpu.ops import msm_pallas as MP
    _soa_cache = []

    def run_soa():
        # direct bucket-kernel SoA path (vanilla recode, no mode dispatch);
        # layout conversion cached outside the timed iterations
        c_soa = c or (11 if logn >= 18 else 10)
        if not _soa_cache:
            _soa_cache.append(MP.to_soa(pts))
        return jax.block_until_ready(MP.combine_windows_soa(
            MP.msm_bucket_windows(_soa_cache[0], sc16, None, c_soa, 254),
            c_soa))

    expect = os.environ.get("BENCH_EXPECT")

    def check(res):
        if not expect:
            return True
        ex, ey = (int(v, 16) for v in expect.split(","))
        return ec.decode_points(jnp.asarray(res)[None])[0] == (ex, ey)

    # impl order: the raw SoA kernel path first on real devices, with the
    # mode-dispatched AoS path (which itself honors SPECTRE_MSM_IMPL —
    # xla or the pallas bucket pipeline, every mode) as in-child fallback
    # (Mosaic availability varies by backend); BENCH_IMPL=aos|soa pins
    # one. run_soa times the vanilla recode only, so non-vanilla modes
    # pin the AoS dispatch path.
    impl_env = os.environ.get("BENCH_IMPL", "auto")
    if impl_env == "soa":
        impls = [("soa", run_soa)]
    elif (impl_env == "aos" or mode != "vanilla"
          or jax.default_backend() == "cpu"):
        impls = [("aos", run_aos)]
    else:
        impls = [("soa", run_soa), ("aos", run_aos)]

    # span-traced phases (ISSUE 7): bench JSON carries the SAME
    # phase_seconds schema production traces expose via getTrace, and
    # running the gated floors with tracing active doubles as the
    # instrumentation-overhead gate. Compile telemetry (ISSUE 8) rides
    # the same runs: the jax.monitoring hook splits compile_seconds out
    # of the record so floors keep gating steady-state run time only.
    from spectre_tpu.observability import compilelog, tracing
    from spectre_tpu.utils.profiling import phase
    compilelog.install()

    mismatch = None
    infra_fail = None
    for impl_name, run in impls:
        try:
            with tracing.trace(f"bench-msm-{impl_name}") as tr, \
                    compilelog.capture() as cev:
                with phase("bench/warmup_compile"):
                    # compile + first run (+ fixed-base table build)
                    res = run()
                if not check(res):
                    mismatch = f"{impl_name}: result mismatch"
                    break  # a wrong result is a correctness regression —
                           # do NOT mask it behind a working fallback impl
                dt = float("inf")
                for _ in range(3):
                    with phase("bench/run"):
                        t0 = time.time()
                        res = run()
                        dt = min(dt, time.time() - t0)
                if not check(res):
                    mismatch = f"{impl_name}: result mismatch"
                    break
        except Exception as exc:  # Mosaic/lowering failures -> next impl
            infra_fail = f"{impl_name}: {type(exc).__name__}: {exc}"
            print(f"# bench impl {impl_name} failed: {infra_fail}",
                  file=sys.stderr, flush=True)
            continue
        if F._USE_MXU:
            impl_name += "+mxu"    # SPECTRE_FIELD_IMPL=mxu matmul field path
        comp = compilelog.summarize(cev)
        with open(out_path, "w") as f:
            json.dump({"points_per_s": n / dt, "impl": impl_name,
                       "msm_mode": mode if impl_name.startswith("aos")
                       else "vanilla",
                       "msm_impl": MSM.msm_impl(),
                       "phase_seconds": tracing.phase_seconds(tr),
                       "compile_seconds": comp["seconds"],
                       "compile_count": comp["count"],
                       "backend": jax.default_backend()}, f)
        return
    if mismatch:
        # WRONG result (exit 0): the parent must fail loudly — a correctness
        # regression must not masquerade as unavailability
        with open(out_path, "w") as f:
            json.dump({"error": mismatch, "backend": jax.default_backend()}, f)
    else:
        # infra-only failures: exit nonzero so the parent retries/falls back
        raise SystemExit(f"device impls failed: {infra_fail}")


def ntt_device_phase(out_path: str):
    """Child process: batched fused coset-LDE vs the per-column pre-PR
    loop, SAME platform for both — the ratio isolates the pipeline win."""
    if os.environ.get("BENCH_FORCE_CPU") == "1":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    from spectre_tpu.plonk.backend import setup_compile_cache
    setup_compile_cache()
    import jax.numpy as jnp

    from spectre_tpu.fields import bn254 as bn
    from spectre_tpu.ops import field_ops as F, limbs as L, ntt as NTT
    from spectre_tpu.plonk.domain import COSET_GEN, EXTENSION

    logn = int(os.environ.get("BENCH_LOGN", "16"))
    batch = int(os.environ.get("BENCH_NTT_BATCH", "16"))
    mode = bench_ntt_mode()
    n = 1 << logn
    n_ext = n * EXTENSION
    log_ext = logn + 2
    omega_ext = bn.fr_root_of_unity(log_ext)
    g = COSET_GEN

    rng = np.random.default_rng(11)
    coeffs = rng.integers(0, 2**63, size=(batch, n, 4), dtype=np.uint64)
    coeffs[:, :, 3] &= (1 << 61) - 1          # < R
    stack = np.zeros((batch, n_ext, 4), dtype=np.uint64)
    stack[:, :n] = coeffs
    std16 = L.u64limbs_to_u16limbs(stack.reshape(-1, 4)).reshape(
        batch, n_ext, 16)
    stack_d = jnp.asarray(std16)

    fctx = F.fr_ctx()
    pow_tab = NTT._power_table(log_ext, g)
    to_mont_jit = jax.jit(lambda v: F.to_mont(fctx, v))

    def one_col_prepr(x_std):
        # the FAITHFUL pre-PR per-column shape (backend.ntt /
        # domain.coeff_to_extended): jitted boundary conversion, then a
        # separate coset-scale pass and an EAGER op-by-op radix-2 NTT —
        # the unjitted module functions the backend used to call, one
        # device dispatch per mont_mul/add/sub/gather per stage
        m16 = to_mont_jit(x_std)
        scaled = F.mont_mul(fctx, m16, jnp.asarray(pow_tab))
        return NTT._ntt_stages(scaled, log_ext, omega_ext)

    # jitted-loop reference (not the headline baseline): the same
    # per-column pipeline as ONE compiled program per column — isolates
    # how much of the win is batching+fusion vs dispatch amortization
    one_col_jit = jax.jit(
        lambda x: NTT._ntt_stages(
            F.mont_mul(fctx, F.to_mont(fctx, x), jnp.asarray(pow_tab)),
            log_ext, omega_ext))

    def run_batched():
        return np.asarray(NTT.coset_lde_std(stack_d, omega_ext, g,
                                            mode=mode))

    # span-traced phases (ISSUE 7): same schema as the MSM child / getTrace;
    # compile telemetry (ISSUE 8) separates compile from throughput
    from spectre_tpu.observability import compilelog, tracing
    from spectre_tpu.utils.profiling import phase
    compilelog.install()

    with tracing.trace(f"bench-ntt-{mode}") as tr, \
            compilelog.capture() as cev:
        # compile + correctness gate: the batched fused kernel must be
        # BYTE-IDENTICAL to the per-column jitted loop (exact arithmetic)
        with phase("bench/byte_check"):
            want = np.stack([np.asarray(one_col_jit(stack_d[i]))
                             for i in range(batch)])
            got = run_batched()
        if not np.array_equal(want, got):
            with open(out_path, "w") as f:
                json.dump({"error": f"ntt batched/{mode} result mismatch vs "
                           f"per-column loop",
                           "backend": jax.default_backend()}, f)
            return

        # the eager pre-PR loop is ~60x slower per column on this box —
        # time a small sample once and scale (it IS the thing being
        # replaced; burning the full batch x3 would dominate bench
        # wall-clock)
        base_cols = min(2, batch)
        with phase("bench/eager_baseline"):
            sample = np.asarray(one_col_prepr(stack_d[0]))  # warm caches
            assert np.array_equal(sample, want[0]), \
                "pre-PR loop result mismatch"
            t0 = time.time()
            for i in range(base_cols):
                np.asarray(one_col_prepr(stack_d[i]))
            base_dt = (time.time() - t0) / base_cols * batch

        jl_dt = float("inf")
        for _ in range(3):
            with phase("bench/jitted_loop"):
                t0 = time.time()
                for i in range(batch):
                    np.asarray(one_col_jit(stack_d[i]))
                jl_dt = min(jl_dt, time.time() - t0)

        dt = float("inf")
        for _ in range(3):
            with phase("bench/run"):
                t0 = time.time()
                run_batched()
                dt = min(dt, time.time() - t0)

        # short-transform kernel comparison (SPECTRE_NTT_KERNEL): time the
        # fourstep pipeline with butterfly stages vs the DFT-matmul body on
        # a small sample of the same columns, byte-checked against each
        # other — the honest stages-vs-matmul number for THIS platform
        # (BASELINE.md: the matmul body targets the MXU; CPU runs it on
        # im2col-style matmuls and is expected slower). BENCH_NTT_COMPARE=0
        # skips the sample.
        kcomp = None
        if os.environ.get("BENCH_NTT_COMPARE", "1") != "0":
            bc = min(batch, 4)
            sample_d = stack_d[:bc]

            def run_kernel(kern):
                return np.asarray(NTT.coset_lde_std(
                    sample_d, omega_ext, g, mode="fourstep", kernel=kern))

            with phase("bench/kernel_compare"):
                ks = {}
                outs = {}
                for kern in NTT.NTT_KERNELS:
                    outs[kern] = run_kernel(kern)      # compile + warm
                    kdt = float("inf")
                    for _ in range(2):
                        t0 = time.time()
                        run_kernel(kern)
                        kdt = min(kdt, time.time() - t0)
                    ks[kern] = round(bc / kdt, 3)
                if not np.array_equal(outs["stages"], outs["matmul"]):
                    with open(out_path, "w") as f:
                        json.dump({"error": "ntt kernel compare: matmul "
                                   "result differs from stages",
                                   "backend": jax.default_backend()}, f)
                    return
                kcomp = {"mode": "fourstep", "batch": bc,
                         "polys_per_s": ks}

        comp = compilelog.summarize(cev)
        with open(out_path, "w") as f:
            json.dump({"polys_per_s": batch / dt,
                       "baseline_polys_per_s": batch / base_dt,
                       "jitted_loop_polys_per_s": batch / jl_dt,
                       "ntt_mode": mode, "ntt_kernel": NTT.ntt_kernel(),
                       "kernel_compare": kcomp, "impl": "batched",
                       "phase_seconds": tracing.phase_seconds(tr),
                       "compile_seconds": comp["seconds"],
                       "compile_count": comp["count"],
                       "backend": jax.default_backend()}, f)


def quotient_device_phase(out_path: str):
    """Child process: time the quotient phase (`compute_quotient`) with
    PRODUCTION inputs — a real prove runs with the host quotient hooked, so
    blinds/grand products/challenges are the ones a prover would see — and
    byte-check every timed device run against the host result. With >1
    device up (the multichip variant) the mesh-sharded pipeline engages and
    `quotient_sharded_degraded` must stay at zero (BENCH_EXPECT_SHARDED=1
    turns any degrade into a hard error)."""
    if os.environ.get("BENCH_FORCE_CPU") == "1":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import jax
    from spectre_tpu.plonk.backend import setup_compile_cache
    setup_compile_cache()

    import spectre_tpu.plonk.prover as P
    from spectre_tpu.observability import compilelog, tracing
    from spectre_tpu.plonk import backend as B, quotient_device as QD
    from spectre_tpu.test_utils import (mesh_prove_fixture,
                                        seeded_blinding_rng)
    from spectre_tpu.utils.health import HEALTH
    from spectre_tpu.utils.profiling import phase
    compilelog.install()

    kk = int(os.environ.get("BENCH_QUOTIENT_K", "11"))
    srs, pk, asg = mesh_prove_fixture(k=kk)

    cap = {}
    orig_q = P._quotient_host

    def wrapped(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y):
        h_host = orig_q(cfg_, dom_, bk_, pk_, polys_, beta, gamma, y)

        def fetch(key):
            kind, j = key
            if key in polys_:
                return polys_[key]
            if kind == "shk":
                return pk_.sha_k_poly
            return {"q": pk_.selector_polys, "fix": pk_.fixed_polys,
                    "sig": pk_.sigma_polys, "tab": pk_.table_polys,
                    "shq": pk_.sha_selector_polys}[kind][j]

        cap.update(cfg=cfg_, dom=dom_, fetch=fetch, beta=beta,
                   gamma=gamma, y=y, h_host=h_host)
        return h_host

    with tracing.trace(f"bench-quotient-k{kk}") as tr, \
            compilelog.capture() as cev:
        with phase("bench/prove_host"):
            P._quotient_host = wrapped
            try:
                P.prove(pk, srs, asg, B.CpuBackend(),
                        blinding_rng=seeded_blinding_rng())
            finally:
                P._quotient_host = orig_q

        ndev = jax.local_device_count()
        deg0 = HEALTH.snapshot()["counters"].get(
            "quotient_sharded_degraded", 0)

        def run():
            return QD.compute_quotient(cap["cfg"], cap["dom"], cap["fetch"],
                                       cap["beta"], cap["gamma"], cap["y"])

        with phase("bench/warmup_compile"):
            got = run()
        dt = float("inf")
        for _ in range(3):
            with phase("bench/run"):
                t0 = time.time()
                got = run()
                dt = min(dt, time.time() - t0)
        degraded = HEALTH.snapshot()["counters"].get(
            "quotient_sharded_degraded", 0) - deg0
        if not np.array_equal(got, cap["h_host"]):
            with open(out_path, "w") as f:
                json.dump({"error": f"device quotient k={kk} != host "
                           "quotient bytes",
                           "backend": jax.default_backend()}, f)
            return
        if os.environ.get("BENCH_EXPECT_SHARDED") == "1" and degraded:
            with open(out_path, "w") as f:
                json.dump({"error": f"quotient mesh path degraded "
                           f"{degraded}x on the happy path "
                           f"(n_devices={ndev})",
                           "backend": jax.default_backend()}, f)
            return

    comp = compilelog.summarize(cev)
    with open(out_path, "w") as f:
        json.dump({"quotients_per_s": 1.0 / dt,
                   "quotient_s": round(dt, 3),
                   "quotient_k": kk,
                   "n_devices": ndev,
                   "sharded_degraded": degraded,
                   "ntt_mode": bench_ntt_mode(),
                   "ntt_kernel": os.environ.get("SPECTRE_NTT_KERNEL",
                                                "stages"),
                   "phase_seconds": tracing.phase_seconds(tr),
                   "compile_seconds": comp["seconds"],
                   "compile_count": comp["count"],
                   "backend": jax.default_backend()}, f)


def multichip_device_phase(out_path: str):
    """Child process: N virtual-device mesh prove + MSM/NTT micro-bench.

    The parent injects XLA_FLAGS=--xla_force_host_platform_device_count=N
    and pins the CPU platform before jax loads; the shard gates are forced
    low so 2^12 kernels and the k=13 prove actually ride the mesh path.
    Every result is correctness-gated in-run: MSM vs the native oracle,
    NTT vs the single-device CPU backend, and the prove BYTE-IDENTICAL to
    a host prove with the same seeded blinding — the rc=124 history of
    this path (MULTICHIP_r01-r05) is exactly why finishing inside the
    parent's deadline IS the metric."""
    import jax
    jax.config.update("jax_platforms", "cpu")
    from spectre_tpu.plonk.backend import setup_compile_cache
    setup_compile_cache()

    from spectre_tpu.native import host
    from spectre_tpu.observability import compilelog, tracing
    from spectre_tpu.ops import msm as MSM
    from spectre_tpu.parallel.plan import current_plan
    from spectre_tpu.utils.profiling import phase
    compilelog.install()

    want_dev = int(os.environ.get("SPECTRE_BENCH_DEVICES", "8"))
    ndev = jax.local_device_count()
    if ndev < want_dev:
        raise SystemExit(
            f"multichip bench: {want_dev} virtual devices requested, got "
            f"{ndev} — XLA_FLAGS applied after jax init?")
    plan = current_plan()

    from spectre_tpu.plonk import backend as B
    tbk = B.TpuBackend()
    logn = int(os.environ.get("BENCH_LOGN", "12"))
    n = 1 << logn
    assert tbk._use_mesh(n, tbk._shard_min_logn), \
        "multichip bench: shard gates not engaged"
    pts64, sc64 = bench_inputs(logn)

    with tracing.trace("bench-multichip") as tr, \
            compilelog.capture() as cev:
        # --- sharded MSM micro-bench (oracle-checked) ---
        with phase("bench/msm_warmup"):
            got = tbk.msm(pts64, sc64)
        ref = host.g1_msm(pts64, sc64)
        if (int(got[0]), int(got[1])) != (int(ref[0]), int(ref[1])):
            with open(out_path, "w") as f:
                json.dump({"error": "sharded MSM result mismatch vs native "
                           "oracle", "backend": jax.default_backend()}, f)
            return
        msm_dt = float("inf")
        for _ in range(3):
            with phase("bench/msm_run"):
                t0 = time.time()
                tbk.msm(pts64, sc64)
                msm_dt = min(msm_dt, time.time() - t0)

        # --- sharded NTT micro-bench (vs single-device CPU backend) ---
        from spectre_tpu.plonk.domain import Domain
        dom = Domain(logn)
        rng = np.random.default_rng(5)
        coeffs = rng.integers(0, 2**63, size=(n, 4), dtype=np.uint64)
        coeffs[:, 3] &= (1 << 61) - 1
        with phase("bench/ntt_warmup"):
            got_ntt = tbk.ntt(coeffs, dom.omega)
        if not np.array_equal(got_ntt, B.CpuBackend().ntt(coeffs,
                                                          dom.omega)):
            with open(out_path, "w") as f:
                json.dump({"error": "sharded NTT result mismatch vs CPU "
                           "backend", "backend": jax.default_backend()}, f)
            return
        ntt_dt = float("inf")
        for _ in range(3):
            with phase("bench/ntt_run"):
                t0 = time.time()
                tbk.ntt(coeffs, dom.omega)
                ntt_dt = min(ntt_dt, time.time() - t0)

        # --- the headline: a COMPLETE k-mesh prove, byte-checked ---
        from spectre_tpu.plonk.prover import prove
        from spectre_tpu.plonk.verifier import verify
        from spectre_tpu.test_utils import (mesh_prove_fixture,
                                            seeded_blinding_rng)
        kk = int(os.environ.get("BENCH_MULTICHIP_K", "13"))
        srs, pk, asg = mesh_prove_fixture(k=kk)
        with phase("bench/prove_host"):
            p_host = prove(pk, srs, asg, B.CpuBackend(),
                           blinding_rng=seeded_blinding_rng())
        with phase("bench/prove_mesh"):
            t0 = time.time()
            p_mesh = prove(pk, srs, asg, tbk,
                           blinding_rng=seeded_blinding_rng())
            prove_s = time.time() - t0
        if p_mesh != p_host:
            with open(out_path, "w") as f:
                json.dump({"error": f"mesh k={kk} proof bytes != host "
                           "prove bytes", "backend": jax.default_backend()},
                          f)
            return
        inst = [asg.instances[0]] if asg.instances else [[]]
        if not verify(pk.vk, srs, inst, p_mesh):
            with open(out_path, "w") as f:
                json.dump({"error": f"mesh k={kk} proof does not verify",
                           "backend": jax.default_backend()}, f)
            return

    comp = compilelog.summarize(cev)
    with open(out_path, "w") as f:
        json.dump({"points_per_s": n / msm_dt,
                   "points_per_s_per_device": n / msm_dt / ndev,
                   "polys_per_s": 1.0 / ntt_dt,
                   "prove_s": round(prove_s, 2),
                   "prove_k": kk,
                   "proof_bytes_identical": True,
                   "n_devices": ndev,
                   "plan": plan.describe(),
                   "msm_mode": bench_msm_mode(),
                   "msm_impl": MSM.msm_impl(),
                   "ntt_mode": bench_ntt_mode(),
                   "phase_seconds": tracing.phase_seconds(tr),
                   "compile_seconds": comp["seconds"],
                   "compile_count": comp["count"],
                   "persistent_cache": comp["persistent_cache"],
                   "backend": jax.default_backend()}, f)


def _run_child(force_cpu: bool, expect: str, timeout: float,
               platform: str | None = None, kind: str = "msm"):
    """Launch the device phase with a hard deadline; returns dict or None."""
    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    env = dict(os.environ, BENCH_PHASE="device", BENCH_EXPECT=expect,
               BENCH_OUT=out, BENCH_KIND=kind)
    if force_cpu:
        env["BENCH_FORCE_CPU"] = "1"
    elif platform:
        # operator-pinned device platform (SPECTRE_BENCH_PLATFORM)
        env["JAX_PLATFORMS"] = platform
    proc = subprocess.Popen([sys.executable, os.path.abspath(__file__)],
                            env=env, stdout=sys.stderr,
                            start_new_session=True)
    try:
        deadline = time.time() + timeout
        while time.time() < deadline:
            rc = proc.poll()
            if rc is not None:
                if rc == 0 and os.path.getsize(out):
                    with open(out) as f:
                        res = json.load(f)
                    if "error" in res:
                        raise SystemExit(
                            f"FATAL: device phase: {res['error']} "
                            f"(backend={res.get('backend')}) — correctness "
                            f"regression, not unavailability")
                    if not force_cpu and res.get("backend") == "cpu":
                        # the 'device' attempt silently came up on the CPU
                        # platform (round-1 failure mode) — treat as failed
                        return None
                    return res
                return None
            time.sleep(2.0)
        import signal
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except Exception:
            pass
        return None
    finally:
        try:
            os.unlink(out)
        except OSError:
            pass


def _device_result(expect: str, kind: str):
    """Run one metric's device phase in a child (hard deadline,
    BENCH_DEVICE_TIMEOUT / BENCH_DEVICE_ATTEMPTS). SPECTRE_BENCH_PLATFORM=cpu
    asks for the pinned-CPU phase explicitly; any other value is pinned
    into the child's JAX_PLATFORMS. There is NO CPU fallback: a device
    phase that fails, hangs or comes up on the CPU platform returns None
    and the metric fails — a CPU number never appears under a device
    metric's name."""
    platform = os.environ.get("SPECTRE_BENCH_PLATFORM")
    if platform == "cpu":
        result = _run_child(True, expect,
                            float(os.environ.get("BENCH_CPU_TIMEOUT", "1200")),
                            kind=kind)
        if not result:
            print(f"FAIL: {kind} pinned-CPU phase failed or timed out",
                  file=sys.stderr, flush=True)
        return result
    dev_timeout = float(os.environ.get("BENCH_DEVICE_TIMEOUT", "240"))
    for attempt in range(int(os.environ.get("BENCH_DEVICE_ATTEMPTS", "1"))):
        result = _run_child(False, expect, dev_timeout, platform=platform,
                            kind=kind)
        if result:
            return result
        print(f"# {kind} device attempt {attempt + 1} failed, timed out or "
              f"came up on the CPU platform", file=sys.stderr, flush=True)
    print(f"FAIL: no {kind} device result (set SPECTRE_BENCH_PLATFORM=cpu "
          f"to ask for the CPU tier explicitly)", file=sys.stderr, flush=True)
    return None


def _run_multichip_child(timeout: float, kind: str = "multichip",
                         extra_env: dict | None = None):
    """Launch the multichip phase: fresh process (XLA_FLAGS must precede
    jax init), hard deadline, rc + stderr tail captured for the failure
    record (the MULTICHIP_r01-r05 logs all died as bare rc=124 with no
    forensics — never again)."""
    import signal

    fd, out = tempfile.mkstemp(suffix=".json")
    os.close(fd)
    logfd, logpath = tempfile.mkstemp(suffix=".log")
    os.close(logfd)
    ndev = int(os.environ.get("SPECTRE_BENCH_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        flags += f" --xla_force_host_platform_device_count={ndev}"
    env = dict(os.environ, BENCH_PHASE="device", BENCH_KIND=kind,
               BENCH_OUT=out, JAX_PLATFORMS="cpu", XLA_FLAGS=flags.strip())
    # the shard gates must engage for 2^12 micro-kernels + the k=13 prove
    env.setdefault("SPECTRE_SHARD_MSM_MIN_LOGN", "10")
    env.setdefault("SPECTRE_SHARD_NTT_MIN_LOGN", "10")
    env.update(extra_env or {})
    rc, tail = None, ""
    try:
        with open(logpath, "w") as logf:
            proc = subprocess.Popen(
                [sys.executable, os.path.abspath(__file__)], env=env,
                stdout=logf, stderr=logf, start_new_session=True)
            deadline = time.time() + timeout
            while time.time() < deadline:
                rc = proc.poll()
                if rc is not None:
                    break
                time.sleep(2.0)
            if rc is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except Exception:
                    pass
                rc = 124
        with open(logpath) as f:
            tail = f.read()[-2000:]
        if rc == 0 and os.path.getsize(out):
            with open(out) as f:
                res = json.load(f)
            if "error" in res:
                raise SystemExit(
                    f"FATAL: multichip phase: {res['error']} — correctness "
                    f"regression, not unavailability")
            return res, rc, tail
        return None, rc, tail
    finally:
        for p in (out, logpath):
            try:
                os.unlink(p)
            except OSError:
                pass


def bench_multichip(fast: bool) -> bool:
    """N-virtual-device mesh bench (BENCH_METRIC=multichip): sharded
    MSM/NTT micro-throughput + a complete byte-checked k=13 mesh prove,
    all inside one hard wall-clock budget (BENCH_MULTICHIP_TIMEOUT).
    The MSM floor is gated like the other --fast floors; the prove
    *finishing* under budget is the regression gate the rc=124 history
    demanded."""
    ndev = int(os.environ.get("SPECTRE_BENCH_DEVICES", "8"))
    logn = int(os.environ.get("BENCH_LOGN", "12"))
    # measured on the 1-core reference box: ~29 min end-to-end with a
    # partially warm compile cache (the k=13 mesh prove alone is ~935s of
    # 8-way SPMD on one physical core). The budget is the REGRESSION gate —
    # the broken pre-13 path burned 600s+ without finishing the prove at
    # all; a real multi-chip host clears this with an order of magnitude
    # to spare
    budget = float(os.environ.get("BENCH_MULTICHIP_TIMEOUT", "2700"))
    result, rc, tail = _run_multichip_child(budget)
    if not result:
        print(json.dumps({
            "metric": f"multichip{ndev}_msm_2^{logn} throughput",
            "value": 0, "unit": "points/s", "vs_baseline": 0.0,
            "backend": None, "n_devices": ndev, "failed": True,
            "rc": rc, "tail": tail[-800:]}))
        return False

    value = result["points_per_s"]
    record = {
        "metric": f"multichip{ndev}_msm_2^{logn} throughput",
        "value": round(value),
        "unit": "points/s",
        "points_per_s_per_device": round(
            result["points_per_s_per_device"]),
        "ntt_polys_per_s": round(result["polys_per_s"], 2),
        "prove_s": result["prove_s"],
        "prove_k": result["prove_k"],
        "proof_bytes_identical": result["proof_bytes_identical"],
        "n_devices": result["n_devices"],
        "plan": result["plan"],
        "backend": result.get("backend"),
        "msm_mode": result.get("msm_mode"),
        "msm_impl": result.get("msm_impl"),
        "ntt_mode": result.get("ntt_mode"),
        "budget_s": budget,
    }
    if result.get("phase_seconds"):
        record["phase_seconds"] = result["phase_seconds"]
    if result.get("compile_seconds") is not None:
        record["compile_seconds"] = result["compile_seconds"]
        record["compile_count"] = result.get("compile_count", 0)
    if result.get("persistent_cache") is not None:
        # persistent compile-cache hits/misses (compilelog): a warm cache
        # shows hits>0, misses==0 — the "compile cost paid once" signal
        record["persistent_cache"] = result["persistent_cache"]
    return _emit(record, fast,
                 f"bn254_msm_2^{logn}_multichip{ndev}_points_per_s",
                 "points/s")


def bench_serve(fast: bool) -> bool:
    """Gateway read-plane drill (BENCH_METRIC=serve / make bench-serve):
    a scaled-down ISSUE-14 load drill — 10^4 simulated light clients,
    Zipf over a synthetic sealed-period store, in process. The floor
    gates requests/s; ZERO sealed-period store fallbacks is a hard
    assertion at every tier (a fallback means the pack plane silently
    stopped covering the sealed range — a correctness bug, not a perf
    regression)."""
    import tempfile

    from spectre_tpu.follower.updates import UpdateStore
    from spectre_tpu.gateway import Gateway
    from spectre_tpu.loadgen import InProcessTarget, run_drill
    from spectre_tpu.utils.health import ServiceHealth

    clients = int(os.environ.get("BENCH_SERVE_CLIENTS", "10000"))
    requests = int(os.environ.get("BENCH_SERVE_REQUESTS",
                                  str(2 * clients)))
    n_periods = int(os.environ.get("BENCH_SERVE_PERIODS", "32"))
    health = ServiceHealth()
    with tempfile.TemporaryDirectory() as tmp:
        store = UpdateStore(tmp, health=health)
        for p in range(1, n_periods + 1):
            store.append_committee(p, {
                "proof": "0x" + bytes([p % 251]).hex() * 64,
                "committee_poseidon": hex(p * 7919 + 13),
                "instances": [hex(p), hex(p + 1)]})
        gw = Gateway(store, pack_periods=8, cache_mb=32, health=health)
        tip = store.tip_period()
        rep = run_drill(InProcessTarget(gw),
                        periods=list(range(tip, 0, -1)), tip=tip,
                        clients=clients, requests=requests, seed=14,
                        health=health)
    fallbacks = rep["gateway_counters"].get("gateway_store_fallbacks", 0)
    record = {
        "metric": f"gateway_serve {clients}-client drill",
        "value": round(rep["rps"]),
        "unit": "requests/s",
        "requests": rep["requests"],
        "clients": clients,
        "periods": n_periods,
        "latency_ms": rep["latency_ms"],
        "ratio_304": rep["ratio_304"],
        "sealed_requests": rep["sealed_requests"],
        "sealed_store_fallbacks": fallbacks,
        "pack_hits": rep["gateway_counters"].get("gateway_pack_hits", 0),
    }
    if fallbacks != 0:
        record["failed"] = True
        print(json.dumps(record))
        print(f"FAIL: {fallbacks} sealed-period responses fell back to "
              "the update store — every sealed period must be served "
              "from the pack/304 plane", file=sys.stderr)
        return False
    return _emit(record, fast, "gateway_serve_requests_per_s",
                 "requests/s")


def main():
    if os.environ.get("BENCH_PHASE") == "device":
        kind = os.environ.get("BENCH_KIND")
        if kind == "ntt":
            ntt_device_phase(os.environ["BENCH_OUT"])
        elif kind == "quotient":
            quotient_device_phase(os.environ["BENCH_OUT"])
        elif kind == "multichip":
            multichip_device_phase(os.environ["BENCH_OUT"])
        else:
            device_phase(os.environ["BENCH_OUT"])
        return

    fast = "--fast" in sys.argv[1:]
    # --impl xla|pallas pins SPECTRE_MSM_IMPL for every metric this
    # invocation times (pallas-vs-xla window sweeps ride this); the
    # resolved impl is recorded in every MSM JSON line either way
    argv = sys.argv[1:]
    if "--impl" in argv:
        idx = argv.index("--impl")
        if idx + 1 >= len(argv):
            print("FAIL: --impl needs a value (xla|pallas)",
                  file=sys.stderr)
            sys.exit(2)
        os.environ["SPECTRE_MSM_IMPL"] = argv[idx + 1]
    # bench floors gate PROVE/kernel throughput, not the verify-before-
    # serve overhead (ISSUE 9) — off unless the operator pins it on; the
    # resolved value is recorded in every metric line
    os.environ.setdefault("SPECTRE_SELF_VERIFY", "off")
    if fast:
        # CI tier: seconds-scale 2^12 on pinned CPU, regression-gated
        # against the checked-in floors (bench_floor.json)
        os.environ.setdefault("BENCH_LOGN", "12")
        os.environ.setdefault("SPECTRE_BENCH_PLATFORM", "cpu")

    if "--sweep-window" in sys.argv[1:]:
        sys.exit(0 if bench_sweep_window() else 1)

    which = os.environ.get("BENCH_METRIC", "all")
    ok = True
    if which in ("all", "msm"):
        ok = bench_msm(fast) and ok
    if which in ("all", "ntt"):
        ok = bench_ntt(fast) and ok
    if which in ("all", "serve"):
        ok = bench_serve(fast) and ok
    if which in ("all", "quotient"):
        ok = bench_quotient(fast) and ok
    # multichip is opt-in (BENCH_METRIC=multichip / make bench-multichip):
    # the k=13 mesh prove is minutes-scale even warm, too heavy for "all"
    if which == "multichip":
        ok = bench_multichip(fast) and ok
    if which == "quotient_multichip":
        ok = bench_quotient_multichip(fast) and ok
    if not ok:
        sys.exit(1)


def bench_sweep_window() -> bool:
    """`python bench.py --sweep-window`: time the full MSM at each window
    width c and print one JSON line per width (points/s) plus a summary
    with the fastest c — the measurement that picks the default_window
    tables; SPECTRE_MSM_WINDOW then pins the winner fleet-wide.

    Runs in-process on the default JAX backend (SPECTRE_BENCH_PLATFORM
    pins it). BENCH_LOGN sizes the instance (default 2^12 — minutes-scale
    on CPU); BENCH_SWEEP_CS overrides the width list. Mode defaults to
    `vanilla` (SPECTRE_MSM_MODE overrides): the fixed-base path rebuilds
    its precomputed table per c, which would time table builds, not MSMs.
    Every width's result is checked equal (affine) to the first width's —
    a sweep that returns different points is a bug, not a datapoint."""
    platform = os.environ.get("SPECTRE_BENCH_PLATFORM")
    if platform:
        os.environ.setdefault("JAX_PLATFORMS", platform)
    import jax
    import jax.numpy as jnp

    from spectre_tpu.ops import ec, field_ops as F, limbs as L, msm as MSM

    logn = int(os.environ.get("BENCH_LOGN", "12"))
    n = 1 << logn
    mode = os.environ.get("SPECTRE_MSM_MODE", "vanilla")
    cs = [int(c) for c in os.environ.get(
        "BENCH_SWEEP_CS", "4,6,8,10,12").split(",")]
    pts64, sc64 = bench_inputs(logn)

    ctxq = F.fq_ctx()
    to_mont = jax.jit(lambda v: F.to_mont(ctxq, v))
    xm = to_mont(jnp.asarray(L.u64limbs_to_u16limbs(pts64[:, :4])))
    ym = to_mont(jnp.asarray(L.u64limbs_to_u16limbs(pts64[:, 4:])))
    one = jnp.broadcast_to(jnp.asarray(ctxq.one_mont), (n, F.NLIMBS))
    pts = jnp.stack([xm, ym, one], axis=1)
    sc16 = jnp.asarray(L.u64limbs_to_u16limbs(sc64))

    want_affine = None
    results = {}
    for c in cs:
        def run():
            return np.asarray(MSM.msm(pts, sc16, c=c, mode=mode,
                                      base_key=("sweep", logn, c)))

        res = run()                                # compile + warm
        affine = ec.decode_points(jnp.asarray(res)[None])[0]
        if want_affine is None:
            want_affine = affine
        elif affine != want_affine:
            print(f"FAIL: window sweep c={c} result diverges",
                  file=sys.stderr)
            return False
        dt = float("inf")
        for _ in range(2):
            t0 = time.time()
            run()
            dt = min(dt, time.time() - t0)
        results[c] = round(n / dt)
        print(json.dumps({"metric": f"bn254_msm_2^{logn} window sweep",
                          "c": c, "value": results[c], "unit": "points/s",
                          "msm_mode": mode, "msm_impl": MSM.msm_impl(),
                          "backend": jax.default_backend()}))
    best = max(results, key=results.get)
    print(json.dumps({"metric": f"bn254_msm_2^{logn} window sweep best",
                      "best_c": best, "value": results[best],
                      "unit": "points/s", "msm_mode": mode,
                      "msm_impl": MSM.msm_impl(),
                      "backend": jax.default_backend()}))
    return True


def bench_msm(fast: bool) -> bool:
    from spectre_tpu.native import host

    logn = int(os.environ.get("BENCH_LOGN", "16"))
    n = 1 << logn
    pts64, sc64 = bench_inputs(logn)

    # --- CPU baseline (native C++ Pippenger, single thread, min of 3) ---
    cpu_dt = float("inf")
    for _ in range(3):
        t0 = time.time()
        cpu_res = host.g1_msm(pts64, sc64)
        cpu_dt = min(cpu_dt, time.time() - t0)
    baseline = n / cpu_dt
    expect = f"{cpu_res[0]:x},{cpu_res[1]:x}"

    result = _device_result(expect, "msm")
    if not result:
        return False

    value = result["points_per_s"]
    record = {
        "metric": f"bn254_msm_2^{logn} throughput",
        "value": round(value),
        "unit": "points/s",
        "vs_baseline": round(value / baseline, 3),
        "backend": result.get("backend"),
        "msm_mode": result.get("msm_mode", bench_msm_mode()),
        "msm_impl": result.get("msm_impl"),
        "impl": result.get("impl"),
        "self_verify": os.environ.get("SPECTRE_SELF_VERIFY", "always"),
    }
    if result.get("phase_seconds"):
        # per-phase breakdown from the child's span trace (ISSUE 7) —
        # the same schema getTrace/phase_seconds exposes in production
        record["phase_seconds"] = result["phase_seconds"]
    if result.get("compile_seconds") is not None:
        # JIT compile cost recorded separately from steady-state
        # throughput (ISSUE 8): floors keep gating run time only
        record["compile_seconds"] = result["compile_seconds"]
        record["compile_count"] = result.get("compile_count", 0)
    return _emit(record, fast, f"bn254_msm_2^{logn}_cpu_points_per_s",
                 "points/s")


def bench_ntt(fast: bool) -> bool:
    """Batched coset-LDE throughput (polys/s): same subprocess + deadline
    machinery as the MSM metric; the child measures its own per-column
    baseline on the same platform and byte-checks the batched kernel
    against it (see ntt_device_phase)."""
    logn = int(os.environ.get("BENCH_LOGN", "16"))
    result = _device_result("", "ntt")
    if not result:
        return False

    value = result["polys_per_s"]
    baseline = result.get("baseline_polys_per_s") or value
    record = {
        "metric": f"bn254_ntt_2^{logn} throughput",
        "value": round(value, 2),
        "unit": "polys/s",
        "vs_baseline": round(value / baseline, 3),
        "backend": result.get("backend"),
        "ntt_mode": result.get("ntt_mode", bench_ntt_mode()),
        "ntt_kernel": result.get("ntt_kernel"),
        "impl": result.get("impl"),
        "self_verify": os.environ.get("SPECTRE_SELF_VERIFY", "always"),
    }
    if result.get("kernel_compare"):
        # stages-vs-matmul short-transform sample (byte-checked in-child)
        record["kernel_compare"] = result["kernel_compare"]
    jl = result.get("jitted_loop_polys_per_s")
    if jl:
        # decomposition: how much of vs_baseline is batching+fusion vs
        # plain dispatch amortization (BASELINE.md records both)
        record["vs_jitted_loop"] = round(value / jl, 3)
    if result.get("phase_seconds"):
        record["phase_seconds"] = result["phase_seconds"]
    if result.get("compile_seconds") is not None:
        record["compile_seconds"] = result["compile_seconds"]
        record["compile_count"] = result.get("compile_count", 0)
    return _emit(record, fast, f"bn254_ntt_2^{logn}_cpu_polys_per_s",
                 "polys/s")


def bench_quotient(fast: bool) -> bool:
    """Quotient-phase latency (BENCH_METRIC=quotient / make bench-quotient):
    the child runs a real prove with the host quotient hooked to capture
    production inputs, then times byte-checked `compute_quotient` runs.
    --fast gates k=11 against the checked-in floor; the full tier adds an
    ungated k=13 datapoint (BENCH_QUOTIENT_KS overrides)."""
    default_ks = "11" if fast else "11,13"
    ks = [int(s) for s in os.environ.get("BENCH_QUOTIENT_KS",
                                         default_ks).split(",") if s]
    timeout = float(os.environ.get("BENCH_QUOTIENT_TIMEOUT", "1800"))
    ok = True
    for kk in ks:
        os.environ["BENCH_QUOTIENT_K"] = str(kk)
        result = _run_child(True, "", timeout, kind="quotient")
        if not result:
            print(json.dumps({"metric": f"quotient_k{kk} latency",
                              "value": 0, "unit": "quotients/s",
                              "backend": None, "failed": True}))
            ok = False
            continue
        record = {
            "metric": f"quotient_k{kk} latency",
            "value": round(result["quotients_per_s"], 3),
            "unit": "quotients/s",
            "quotient_s": result["quotient_s"],
            "n_devices": result["n_devices"],
            "sharded_degraded": result["sharded_degraded"],
            "backend": result.get("backend"),
            "ntt_mode": result.get("ntt_mode"),
            "ntt_kernel": result.get("ntt_kernel"),
        }
        if result.get("phase_seconds"):
            record["phase_seconds"] = result["phase_seconds"]
        if result.get("compile_seconds") is not None:
            record["compile_seconds"] = result["compile_seconds"]
            record["compile_count"] = result.get("compile_count", 0)
        ok = _emit(record, fast, f"quotient_k{kk}_cpu_per_s",
                   "quotients/s") and ok
    return ok


def bench_quotient_multichip(fast: bool) -> bool:
    """8-virtual-device mesh quotient (BENCH_METRIC=quotient_multichip /
    make bench-quotient-multichip): same child as bench_quotient on an
    N-device mesh — the sharded pipeline MUST engage (BENCH_EXPECT_SHARDED
    turns any `quotient_sharded_degraded` tick into a hard error) and
    every timed run is byte-checked against the host quotient."""
    ndev = int(os.environ.get("SPECTRE_BENCH_DEVICES", "8"))
    kk = int(os.environ.get("BENCH_QUOTIENT_K", "13"))
    budget = float(os.environ.get("BENCH_QUOTIENT_TIMEOUT", "2700"))
    result, rc, tail = _run_multichip_child(
        budget, kind="quotient",
        extra_env={"BENCH_QUOTIENT_K": str(kk), "BENCH_EXPECT_SHARDED": "1",
                   "SPECTRE_SHARD_QUOTIENT_MIN_LOGN": "10"})
    if not result:
        print(json.dumps({
            "metric": f"quotient_k{kk}_multichip{ndev} latency",
            "value": 0, "unit": "quotients/s", "backend": None,
            "n_devices": ndev, "failed": True, "rc": rc,
            "tail": tail[-800:]}))
        return False
    record = {
        "metric": f"quotient_k{kk}_multichip{ndev} latency",
        "value": round(result["quotients_per_s"], 3),
        "unit": "quotients/s",
        "quotient_s": result["quotient_s"],
        "n_devices": result["n_devices"],
        "sharded_degraded": result["sharded_degraded"],
        "backend": result.get("backend"),
        "ntt_mode": result.get("ntt_mode"),
        "ntt_kernel": result.get("ntt_kernel"),
        "budget_s": budget,
    }
    if result.get("phase_seconds"):
        record["phase_seconds"] = result["phase_seconds"]
    if result.get("compile_seconds") is not None:
        record["compile_seconds"] = result["compile_seconds"]
        record["compile_count"] = result.get("compile_count", 0)
    return _emit(record, fast, f"quotient_k{kk}_multichip{ndev}_per_s",
                 "quotients/s")


def _emit(record: dict, fast: bool, floor_key: str, unit: str) -> bool:
    """Print the metric line; in --fast mode gate >20% regressions against
    the checked-in floor (bench_floor.json)."""
    value = record["value"]
    if fast:
        floor = None
        if os.path.exists(FLOOR_PATH):
            with open(FLOOR_PATH) as f:
                floors = json.load(f)
            floor = floors.get(floor_key)
        if floor is not None:
            record["floor"] = floor
            record["regression"] = bool(value < 0.8 * floor)
        print(json.dumps(record))
        if record.get("regression"):
            print(f"FAIL: {value} {unit} is >20% below the checked-in "
                  f"floor {floor} (bench_floor.json)", file=sys.stderr)
            return False
        return True
    print(json.dumps(record))
    return True


if __name__ == "__main__":
    main()

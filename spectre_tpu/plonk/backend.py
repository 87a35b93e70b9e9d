"""Pluggable bulk-math backend for the prover: cpu (native C++) or tpu (JAX).

All prover-side polynomial data lives as numpy [n, 4] uint64 limb arrays in
standard form (little-endian 64-bit limbs). The backend supplies the heavy
ops: batched field arithmetic, NTTs, MSMs. The reference's `--backend`
selection point (BASELINE.json north star: `ProverBackend` trait) is this
class.
"""

from __future__ import annotations

import os

import numpy as np

from ..fields import bn254
from ..native import host
from ..observability.tracing import annotate, span

R = bn254.R


def _host_fingerprint() -> str:
    """4-byte tag of this host's CPU feature flags + CPU MODEL + jaxlib
    version. AOT entries compiled on a machine with different features
    ABORT (SIGILL class) when loaded by XLA:CPU — observed as `Fatal Python
    error: Aborted` inside _cache_read when /tmp survived a host migration.
    Flags alone are not enough: XLA also tunes codegen by model
    (+prefer-no-scatter/gather), so same-flags/different-model hosts make
    every entry stale and force per-kernel recompiles (observed: commit
    phase 9min -> 2h). Keying the cache dir by all three makes foreign
    entries unreachable instead of fatal/slow."""
    import hashlib
    import platform
    feat = model = ""
    try:
        with open("/proc/cpuinfo") as f:
            for line in f:
                # x86 "flags", aarch64 "Features"
                if not feat and line.startswith(("flags", "Features")):
                    feat = line.strip()
                # XLA tunes codegen by CPU MODEL too (+prefer-no-scatter/
                # gather etc.): hosts with identical flag sets but different
                # models produce mutually-stale AOT entries (observed: every
                # kernel recompiled after a migration, commit phase 9min->2h)
                if not model and line.startswith("model name"):
                    model = line.strip()
                if feat and model:
                    break
    except OSError:
        pass
    try:
        import jaxlib
        jl = getattr(jaxlib, "__version__", "")
    except Exception:
        jl = ""
    ident = f"{platform.machine()}|{model}|{feat}|jaxlib-{jl}"
    return hashlib.blake2s(ident.encode(), digest_size=4).hexdigest()


# the persistent compile cache lives INSIDE the checkout (git-ignored): a
# path built from a temp name, pid or time never hits — the directory is
# part of the cache key
_CACHE_ROOT = os.path.normpath(os.path.join(
    os.path.dirname(os.path.abspath(__file__)), "..", "..", ".jax_cache"))


def setup_compile_cache():
    """Persistent JAX compile cache (shared policy for backends, tests,
    and entry points).

    `JAX_COMPILATION_CACHE_DIR` places the cache from outside: when it is
    set JAX reads it itself and this function sets NO directory in code.
    Otherwise the cache is `<checkout>/.jax_cache/<backend>_<host
    fingerprint>` — fixed per host, so the expensive entries (multichip
    SPMD programs, per-shape prover kernels) compile once per checkout,
    and foreign XLA:CPU AOT entries stay unreachable (see
    _host_fingerprint)."""
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR") \
            or jax.config.jax_compilation_cache_dir:
        return
    jax.config.update(
        "jax_compilation_cache_dir",
        os.path.join(_CACHE_ROOT,
                     f"{jax.default_backend()}_{_host_fingerprint()}"))


def to_arr(vals) -> np.ndarray:
    return host.ints_to_limbs([int(v) % R for v in vals])


def arr_to_ints(arr) -> list[int]:
    return host.limbs_to_ints(arr)


def zeros(n: int) -> np.ndarray:
    return np.zeros((n, 4), dtype=np.uint64)


def const_arr(s: int, n: int) -> np.ndarray:
    """[n, 4] array of the constant s — np.tile of one marshalled row
    (building the same list[int] n times through ints_to_limbs was seconds
    per call at extended-domain sizes)."""
    return np.tile(host.ints_to_limbs([int(s) % R]), (n, 1))


class CpuBackend:
    """Native C++ single-host backend (the measured baseline)."""

    name = "cpu"

    # -- batched Fr ops on [n,4] arrays --
    def mul(self, a, b):
        return host.fp_mul_batch(host.FR, a, b)

    def add(self, a, b):
        return host.fp_add_batch(host.FR, a, b)

    def sub(self, a, b):
        return host.fp_sub_batch(host.FR, a, b)

    def inv(self, a):
        return host.fp_inv_batch(host.FR, a)

    def scale(self, a, s: int):
        return host.fp_scale_batch(host.FR, a, s)

    def add_scalar(self, a, s: int):
        return host.fp_add_scalar_batch(host.FR, a, s % R)

    def axpy(self, a, s: int, b):
        """a*s + b elementwise, one pass (quotient y-combination)."""
        return host.fp_axpy_batch(host.FR, a, s % R, b)

    def powers(self, x: int, n: int):
        return host.fp_powers(host.FR, x, n)

    def prefix_prod(self, a):
        return host.fp_prefix_prod(host.FR, a)

    # -- NTT (in place on a copy; returns new array) --
    def ntt(self, coeffs, omega: int):
        data = np.array(coeffs, dtype=np.uint64)
        return host.fr_ntt(data, omega)

    def intt(self, evals, omega: int):
        n = evals.shape[0]
        data = np.array(evals, dtype=np.uint64)
        host.fr_ntt(data, pow(omega, -1, R))
        return host.fp_scale_batch(host.FR, data, pow(n, -1, R))

    # -- batched many-polynomial NTT (ISSUE 4 tentpole): ONE backend call
    #    per column stack. The native kernel is per-polynomial, so the CPU
    #    tier loops; the device backend overrides with a single compiled
    #    [B, n, 16] kernel. All lists hold same-length [n, 4] u64 arrays.
    def ntt_many(self, coeffs_list, omega: int) -> list:
        return [self.ntt(c, omega) for c in coeffs_list]

    def intt_many(self, evals_list, omega: int) -> list:
        return [self.intt(e, omega) for e in evals_list]

    def coset_lde_many(self, coeffs_list, omega: int, g: int, n_out: int,
                       powers=None) -> list:
        """Coset low-degree extension of several coefficient-form polys to
        the size-n_out coset g*<omega>: pad, scale by g^i, NTT. `powers`
        is an optional pre-computed [n_out, 4] table of g^i (the domain
        caches one per generator); the device backend ignores it and fuses
        the scale into stage 0 of its batched kernel."""
        if powers is None:
            powers = self.powers(g, n_out)
        out = []
        for cf in coeffs_list:
            padded = np.zeros((n_out, 4), dtype=np.uint64)
            padded[:cf.shape[0]] = cf
            out.append(self.ntt(self.mul(padded, powers), omega))
        return out

    # -- MSM: points [m, 8] u64 affine standard, scalars [m, 4] --
    def msm(self, points, scalars, base_key=None, basis="powers"):
        # base_key names a fixed base for the device table cache and `basis`
        # what the base is ("powers" of tau, or "lagrange") for
        # the device backend's spans; the native Pippenger has no precompute
        # path and no spans, so both are ignored here
        m = min(points.shape[0], scalars.shape[0])
        return host.g1_msm(points[:m], scalars[:m])

    def msm_many(self, points, scalars_list, base_key=None, basis="powers"):
        """Commit several scalar vectors against the same base points."""
        return [self.msm(points, sc, base_key=base_key)
                for sc in scalars_list]


class TpuBackend(CpuBackend):
    """JAX backend: MSM/NTT ride the device kernels; small ops stay native.

    Inherits the native implementations and overrides the ops where the device
    wins. Conversions to 16-bit limb tensors happen at the boundary.

    The commitment base (SRS tau powers) is encoded + shipped to device ONCE
    per distinct base array and cached — per-column commits were previously
    re-transferring the same 2^k-point base every call.

    Every operation that reaches the device opens one span named for the
    call (`backend/msm`, `backend/ntt`, ...) and inside it the stages
    `encode`, `dispatch`, `wait`, `decode` (observability/tracing.py). A
    `wait` closes on the read that blocked anyway; no stage adds a
    synchronisation. On one device a list of commits is one
    `backend/msm_many` span a run of MSM.CHUNK_WIDTH columns
    (`_msm_chunks`), with `batch`, `width`, the window `c`, `active` (the
    windows its columns ran) and `basis` on it. The NTT kinds cross
    the boundary twice a call (the transform's result comes to the host
    and goes straight back for `from_mont`): their spans show both
    crossings."""

    name = "tpu"
    # quotient phase as one device-resident XLA program (quotient_device.py)
    device_quotient = True

    def __init__(self):
        import jax  # noqa: F401  fail fast if jax unusable
        from ..ops import limbs as L16  # noqa: F401
        # per-shape compiles dominate small-circuit wall-clock; persist them
        setup_compile_cache()
        self._base_cache: dict = {}   # (id, n) -> device [n,3,16] points
        # (id, n, expand, plan) -> mesh-placed (expanded, padded) base:
        # the sharded MSM path previously re-ran endo expansion and
        # re-device_put the full base onto the mesh EVERY call
        self._mesh_base_cache: dict = {}
        self._shard_min_logn = int(os.environ.get(
            "SPECTRE_SHARD_MSM_MIN_LOGN", str(self.SHARD_MSM_MIN_LOGN)))
        self._shard_ntt_min_logn = int(os.environ.get(
            "SPECTRE_SHARD_NTT_MIN_LOGN", str(self.SHARD_NTT_MIN_LOGN)))

    def _encode_points(self, points):
        import jax
        import jax.numpy as jnp

        from ..ops import field_ops as F, limbs as L16

        m = points.shape[0]
        ctxq = F.fq_ctx()
        x16 = L16.u64limbs_to_u16limbs(points[:, :4])
        y16 = L16.u64limbs_to_u16limbs(points[:, 4:])
        if "toq" not in _mont_jits:
            def to_mont_fq(v):
                return F.to_mont(ctxq, v)

            _mont_jits["toq"] = jax.jit(to_mont_fq)
        to_mont = _mont_jits["toq"]
        xm, ym = to_mont(jnp.asarray(x16)), to_mont(jnp.asarray(y16))
        inf_mask = jnp.asarray(
            (np.asarray(x16).sum(1) == 0) & (np.asarray(y16).sum(1) == 0))[:, None]
        one = jnp.broadcast_to(jnp.asarray(ctxq.one_mont), (m, F.NLIMBS))
        # infinity must be the RCB identity (0:1:0) — (0:0:0) is absorbing
        ym = jnp.where(inf_mask, one, ym)
        z = jnp.where(inf_mask, 0, one)
        return jnp.stack([xm, ym, z], axis=1)

    def _base_points(self, points, m: int):
        """Device-resident encoded points, cached per (array, prefix-len).

        The cache holds a STRONG reference to the host array: the id() key
        then cannot be reused by a different array while the entry lives
        (and SRS bases are never mutated in place), so a hit always refers
        to the same base."""
        key = (id(points), m)
        hit = self._base_cache.get(key)
        if hit is not None and hit[0] is points:
            return hit[1]
        pts = self._encode_points(points[:m])
        # one base per backend instance is the norm (the SRS); keep the
        # cache tiny so entries (and their host refs) cannot accumulate
        if len(self._base_cache) > 8:
            self._base_cache.clear()
        self._base_cache[key] = (points, pts)
        return pts

    # single MSMs at least this large route through the mesh-sharded
    # kernel when >1 device is attached (SURVEY §2c(a): TP axis; override
    # via SPECTRE_SHARD_MSM_MIN_LOGN)
    SHARD_MSM_MIN_LOGN = 20

    def msm(self, points, scalars, base_key=None, basis="powers"):
        """basis: what `points` are, "powers" of tau or "lagrange", a label
        on the call's span. Only the one-device default path reads
        the scalars' width (`_msm_chunks`); the mesh and the other MSM modes
        commit against either base with every window."""
        import jax
        import jax.numpy as jnp

        from ..ops import ec, limbs as L16, msm as MSM

        m = min(points.shape[0], scalars.shape[0])
        if self._use_mesh(m, self._shard_min_logn):
            return self._msm_sharded(points, scalars, m, base_key=base_key,
                                     basis=basis)
        if self._one_chip_default():
            # a chunk of one: the programs a list's runs have loaded already
            return self._msm_chunks("backend/msm", points, [scalars],
                                    basis)[0]
        with span("backend/msm", n=m, basis=basis):
            with span("backend/msm/encode"):
                pts = self._base_points(points, m)
                sc16 = jnp.asarray(L16.u64limbs_to_u16limbs(scalars[:m]))
                annotate(bytes=sc16.nbytes)
            with span("backend/msm/dispatch"):
                res = MSM.msm(pts, sc16, base_key=base_key)
            return ec.decode_points(res[None], call="backend/msm")[0]

    def _mesh_base(self, points, m: int, plan, expand: bool):
        """Mesh-resident commitment base: encoded, optionally endomorphism-
        expanded, row-padded to the plan's data axis and placed per
        plan.point_spec — ONCE per (array, prefix, plan, expansion).

        Strong host ref pins the id() key, same contract as _base_points.
        Before this cache the sharded path re-ran _expand_endo and
        re-device_put the full base onto the mesh for every MSM of a
        prove."""
        key = (id(points), m, expand, plan.key)
        hit = self._mesh_base_cache.get(key)
        if hit is not None and hit[0] is points:
            return hit[1]
        import jax.numpy as jnp

        from ..ops import ec, msm as MSM

        pts = self._base_points(points, m)
        if expand:
            pts = MSM._expand_endo(pts)
        m2 = pts.shape[0]
        mp = plan.pad_rows(m2)
        if mp > m2:
            # RCB identity (0:1:0) padding — zero scalars ride these rows
            pts = jnp.concatenate(
                [pts, ec.inf_point((mp - m2,)).astype(pts.dtype)], axis=0)
        placed = plan.place(pts, plan.point_spec)
        if len(self._mesh_base_cache) > 4:
            self._mesh_base_cache.clear()
        self._mesh_base_cache[key] = (points, placed)
        return placed

    def _msm_sharded(self, points, scalars, m: int, base_key=None,
                     basis="powers"):
        """One MSM sharded over the ShardingPlan's ("data", "win") mesh.
        Points are padded with infinity (zero scalars) so the data axis
        divides evenly. Every window runs, whatever the scalars hold.

        GLV modes ride the mesh too: the host scalar-prep stage (Babai
        decomposition) runs per call, but the endomorphism-expanded base
        stays mesh-resident via _mesh_base, so each data shard holds
        aligned (point, half-scalar, sign) rows with no per-call base
        transfer. `fixed` mode runs SHARDED (ISSUE 13): the per-SRS window
        table is built by the mesh and stays resident with T[w] row slices
        co-resident with their point shards; it degrades to glv+signed
        only when even the per-device table slice busts the
        SPECTRE_MSM_TABLE_MB budget (health counter msm_fixed_degraded)."""
        import importlib

        import jax.numpy as jnp

        from ..ops import ec, limbs as L16, msm as MSM
        from ..parallel.plan import current_plan
        # the package re-exports the sharded_msm FUNCTION under the module's
        # name, so attribute-style module import resolves to the function
        SM = importlib.import_module("spectre_tpu.parallel.sharded_msm")

        mode = MSM.msm_mode()
        plan = current_plan()
        call = "backend/msm_sharded"

        def read(res):
            # the mesh result comes to the host whole and goes back up for
            # `_affine_mont`: a second crossing, inside decode_points
            with span(call + "/wait", bytes=res.nbytes):
                proj = np.asarray(res)
            return ec.decode_points(proj[None], call=call)[0]

        with span(call, n=m, basis=basis):
            with span(call + "/encode"):
                sc16 = L16.u64limbs_to_u16limbs(scalars[:m])
                nbits, signed = 254, False
                if mode != "vanilla":
                    from ..ops import glv
                    a1, a2, n1, n2 = glv.decompose_limbs16(sc16)
                    sc16 = np.concatenate([a1, a2], axis=0)
                    neg_np = np.concatenate([n1, n2], axis=0)
                    nbits = glv.glv_bits()
                    signed = mode in ("glv+signed", "fixed")
                    m2 = 2 * m
                else:
                    neg_np = np.zeros(m, dtype=bool)
                    m2 = m
                mp = plan.pad_rows(m2)
                sc = np.zeros((mp, sc16.shape[1]), dtype=np.uint32)
                sc[:m2] = sc16
                ng = np.zeros(mp, dtype=bool)
                ng[:m2] = neg_np
                annotate(bytes=sc.nbytes + ng.nbytes)

            if mode == "fixed":
                c = MSM.default_window_fixed(mp)
                nwin = (nbits + c) // c
                if not SM._degrade_fixed_mesh(mp, c, nbits, plan):
                    with span(call + "/dispatch"):
                        base = self._mesh_base(points, m, plan, expand=True)
                        tab = SM.sharded_fixed_table(base, c, nwin, plan,
                                                     base_key=base_key)
                        sd = plan.place(jnp.asarray(sc), plan.scalar_spec)
                        ngd = plan.place(jnp.asarray(ng), plan.sign_spec)
                        res = SM.sharded_msm_fixed(tab, sd, ngd, c, plan,
                                                   nbits)
                    return read(res)
                # per-device table slice over budget: glv+signed fallback
                # below

            with span(call + "/dispatch"):
                base = self._mesh_base(points, m, plan,
                                       expand=(mode != "vanilla"))
                if mode != "vanilla" and not signed:
                    # unsigned glv folds the sign into the points — scalar-
                    # dependent, so applied on device against the resident
                    # base
                    base = MSM._apply_sign(
                        base, plan.place(jnp.asarray(ng), plan.sign_spec))
                    ng = np.zeros_like(ng)
                if mode == "vanilla":
                    # mesh-tuned static window; SPECTRE_MSM_WINDOW still
                    # wins so a sweep exercises the sharded path too
                    c = MSM.window_override() or (
                        13 if mp >= (1 << 18) else 10)
                else:
                    c = MSM.default_window(mp, signed=signed)
                sd = plan.place(jnp.asarray(sc), plan.scalar_spec)
                ngd = plan.place(jnp.asarray(ng), plan.sign_spec) \
                    if signed else None
                res = SM.sharded_msm(base, sd, c, plan.mesh, nbits=nbits,
                                     signed=signed, neg=ngd, plan=plan)
            return read(res)

    def msm_many(self, points, scalars_list, base_key=None, basis="powers"):
        """Commit several scalar vectors against one cached device base
        (`basis`: as in `msm`).

        With >1 local device the batch axis is sharded over a 1-D mesh
        (SURVEY §2c(b): inter-proof/column DP). On one device, in the
        default mode, the columns go through `_msm_chunks`, 16 to a device
        run and a read (PERF.md section 5 has the chip's readings of it
        against a loop of `msm`), each with the windows its scalars need;
        the other modes, which have not run on the chip, loop `msm`, and
        they and the mesh run every window. GLV modes thread the
        scalar-prep stage through the DP path: half-scalars and sign masks
        are stacked per batch row against ONE replicated
        endomorphism-expanded base (`fixed` uses the glv+signed kernels
        here — replicating a per-window table across the mesh would
        multiply its memory by the device count)."""
        import jax
        import jax.numpy as jnp

        from ..ops import ec, limbs as L16, msm as MSM

        if not scalars_list:
            return []
        from ..parallel.plan import current_plan
        plan = current_plan()
        batch = len(scalars_list)
        if plan.n_devices > 1 and batch > 1:
            from ..parallel.batch_msm import batch_msm_dp
            bmesh = plan.batch_mesh
            call = "backend/msm_many"
            # uniform batch length: pad shorter scalar vectors with zeros
            # (zero scalars select the empty bucket — identity contribution)
            mmax = min(points.shape[0],
                       max(s.shape[0] for s in scalars_list))
            mode = MSM.msm_mode()
            with span(call, batch=batch, n=mmax, basis=basis):
                with span(call + "/encode"):
                    pts = self._base_points(points, mmax)
                    if mode == "vanilla":
                        sc = np.zeros((batch, mmax, 16), dtype=np.uint32)
                        for i, s in enumerate(scalars_list):
                            mi = min(mmax, s.shape[0])
                            sc[i, :mi] = np.asarray(
                                L16.u64limbs_to_u16limbs(s[:mi]))
                        kw = {}
                    else:
                        from ..ops import glv
                        sc = np.zeros((batch, 2 * mmax, glv.HALF_LIMBS),
                                      dtype=np.uint32)
                        ng = np.zeros((batch, 2 * mmax), dtype=bool)
                        for i, s in enumerate(scalars_list):
                            mi = min(mmax, s.shape[0])
                            sc64 = np.zeros((mmax, 4), dtype=np.uint64)
                            sc64[:mi] = s[:mi]
                            a1, a2, n1, n2 = glv.decompose_limbs16(
                                L16.u64limbs_to_u16limbs(sc64))
                            sc[i] = np.concatenate([a1, a2], axis=0)
                            ng[i] = np.concatenate([n1, n2], axis=0)
                        kw = dict(neg_batch=ng, nbits=glv.glv_bits(),
                                  signed=mode in ("glv+signed", "fixed"))
                    annotate(bytes=sc.nbytes)
                with span(call + "/dispatch"):
                    if kw:
                        pts = MSM._expand_endo(pts)
                    res = batch_msm_dp(pts, sc, mesh=bmesh, **kw)  # [B,3,16]
                # the mesh result comes to the host whole and goes back up
                # for `_affine_mont`: a second crossing, in decode_points
                with span(call + "/wait", bytes=res.nbytes):
                    proj = np.asarray(res)
                return list(ec.decode_points(proj, call=call))
        if self._one_chip_default():
            return self._msm_chunks("backend/msm_many", points, scalars_list,
                                    basis)
        return [self.msm(points, s, base_key=base_key, basis=basis)
                for s in scalars_list]

    @staticmethod
    def _one_chip_default() -> bool:
        """One device and the MSM as the chip has run it (SPECTRE_MSM_MODE
        vanilla): what `_msm_chunks` serves."""
        from ..ops import msm as MSM
        from ..parallel.plan import current_plan
        return current_plan().n_devices == 1 and MSM.msm_mode() == "vanilla"

    def _msm_chunks(self, call: str, points, scalars_list,
                    basis: str = "powers") -> list:
        """Commit `scalars_list` against one resident base, MSM.CHUNK_WIDTH
        columns a device run: each column's window phase (`msm_windows`,
        the one window-phase program of its n), then for the whole run the
        combine chain at width W, the affine conversion of W points and
        one blocking read, with no host synchronisation between them. On
        the chip the window phase is bound by its additions and gains
        nothing from a batch axis; combine and affine conversion are
        chains of dependent steps that cost less at width 16 than at width
        1 (PERF.md section 5 has the readings).

        A column's window phase runs the windows its largest scalar
        reaches and no more (`MSM.windows_needed`, read here from the host
        column; the count goes to the program as data, so every width is
        the one program and a run may mix them): 1 of 32 at c = 8 for a
        column of bits committed as values against the Lagrange base, all
        of them for coefficients, whose scalars are full field elements.
        Nothing is declared and nothing can be exceeded: the count is the
        column's own.

        ONE width: a run of fewer columns is filled with identity window
        sums, whose points are read with the rest and dropped; a longer
        list is split; a shorter scalar vector is zero-extended. One span
        named `call` a run, with `batch` (its columns), `width`, the
        window `c` its columns were committed with, `active` (the windows
        they ran, summed) and `basis` (what the base is: "powers" or
        "lagrange") on it."""
        import jax.numpy as jnp

        from ..ops import ec, limbs as L16, msm as MSM

        n = min(points.shape[0], max(s.shape[0] for s in scalars_list))
        c = MSM.default_window(n)
        width = MSM.CHUNK_WIDTH
        out = []
        for at in range(0, len(scalars_list), width):
            chunk = scalars_list[at:at + width]
            with span(call, n=n, batch=len(chunk), width=width, c=c,
                      basis=basis):
                with span(call + "/encode"):
                    pts = self._base_points(points, n)
                    cols, active, sent = [], [], 0
                    for col in chunk:
                        active.append(MSM.windows_needed(col[:n], c))
                        sc = L16.u64limbs_to_u16limbs(col[:n])
                        if sc.shape[0] < n:
                            sc = np.pad(sc, ((0, n - sc.shape[0]), (0, 0)))
                        cols.append(jnp.asarray(sc))
                        sent += sc.nbytes
                    annotate(bytes=sent)
                annotate(active=int(sum(active)))
                with span(call + "/dispatch"):
                    wins = tuple(MSM.msm_windows(pts, sc, c, a)
                                 for sc, a in zip(cols, active))
                    affine = ec.affine_points(MSM.combine_windows_batch(
                        MSM.pad_window_sums(wins, width), c))
                out += ec.read_points(affine, call, keep=len(chunk))
        return out

    # NTTs at least this large ride the four-step mesh-sharded kernel
    # (all-to-all transpose over ICI, parallel/sharded_ntt.py) when >1
    # device is attached — the same gate pattern as SHARD_MSM_MIN_LOGN;
    # override via SPECTRE_SHARD_NTT_MIN_LOGN (the mesh-prove dryrun/test
    # forces it low so a full tiny prove exercises the path end-to-end)
    SHARD_NTT_MIN_LOGN = 18

    def _use_mesh(self, n: int, min_logn: int) -> bool:
        # plan-aware gate: SPECTRE_MESH_SHAPE=1x1 means "prove on a
        # 1-device mesh" -> the plain single-device kernels (which IS the
        # degenerate mesh result; the identity tests lean on this)
        from ..parallel.plan import current_plan
        return current_plan().n_devices > 1 and n >= (1 << min_logn)

    def ntt(self, coeffs, omega: int):
        from ..ops import ntt as NTT

        if self._use_mesh(coeffs.shape[0], self._shard_ntt_min_logn):
            return self._ntt_sharded(coeffs, omega)
        call = "backend/ntt"
        with span(call, n=coeffs.shape[0]):
            packed = _ship_packed(coeffs, call)
            with span(call + "/dispatch"):
                out = NTT.ntt(_to_mont_packed(packed), omega)
            return _fetch_u64_std(out, call)

    def intt(self, evals, omega: int):
        import jax.numpy as jnp

        from ..ops import field_ops as F, ntt as NTT

        call = "backend/intt"
        if self._use_mesh(evals.shape[0], self._shard_ntt_min_logn):
            n = evals.shape[0]
            with span(call, n=n):
                res = self._ntt_sharded(evals, pow(omega, -1, R),
                                        mont_out=True)
                with span(call + "/dispatch"):
                    ninv = F.fr_ctx().encode([pow(n, -1, R)])[0]
                    # through a cached jit: an eager mont_mul is a top-level
                    # lax.scan that recompiles on EVERY call
                    from .quotient_device import _helpers
                    out = _helpers()["mul_s"](res, jnp.asarray(ninv))
                return _fetch_u64_std(out, call)
        with span(call, n=evals.shape[0]):
            packed = _ship_packed(evals, call)
            with span(call + "/dispatch"):
                out = NTT.intt(_to_mont_packed(packed), omega)
            return _fetch_u64_std(out, call)

    def _ntt_sharded(self, arr_u64, omega: int, mont_out: bool = False):
        """One NTT over the ("data",) mesh axis; exact same result as the
        single-device kernel (pinned by tests/test_parallel.py)."""
        from ..parallel.plan import current_plan
        from ..parallel.sharded_ntt import sharded_ntt

        plan = current_plan()
        call = "backend/ntt_sharded"
        with span(call, n=arr_u64.shape[0]):
            packed = _ship_packed(arr_u64, call)
            with span(call + "/dispatch"):
                res = sharded_ntt(_to_mont_packed(packed), omega, plan.mesh,
                                  plan=plan)
            if mont_out:
                return res
            return _fetch_u64_std(res, call)

    # batch sizes are padded up to a power of two (zero columns transform
    # to zero columns and are sliced off) so the jitted [B, n, 16] kernels
    # compile for at most log2(chunk) distinct batch shapes per n instead
    # of one executable per ragged chunk length — XLA:CPU compile churn is
    # this box's known instability (see TestMsmModeCommitments note)
    @staticmethod
    def _pad_batch(stack: np.ndarray) -> np.ndarray:
        b = stack.shape[0]
        bp = 1 << max(b - 1, 0).bit_length()
        if bp == b:
            return stack
        pad = np.zeros((bp,) + stack.shape[1:], dtype=stack.dtype)
        pad[:b] = stack
        return pad

    def _ntt_many_device(self, arrs, omega: int, inverse: bool) -> list:
        """[B, n, 16] batched kernel path (single device, any NTT mode)."""
        from ..ops import ntt as NTT

        b, n = len(arrs), arrs[0].shape[0]
        call = "backend/intt_many" if inverse else "backend/ntt_many"
        with span(call, batch=b, n=n):
            packed = _ship_packed(
                lambda: self._pad_batch(np.stack(arrs)).reshape(-1, 4), call)
            with span(call + "/dispatch"):
                mont = _to_mont_packed(packed).reshape(-1, n, 16)
                fn = NTT.intt_many if inverse else NTT.ntt_many
                out = fn(mont, omega)
            return _fetch_u64_std(
                out, call, lambda std: list(std.reshape(-1, n, 4)[:b]))

    def ntt_many(self, coeffs_list, omega: int) -> list:
        if not coeffs_list:
            return []
        n = coeffs_list[0].shape[0]
        if len(coeffs_list) == 1 or self._use_mesh(
                n, self._shard_ntt_min_logn):
            return [self.ntt(c, omega) for c in coeffs_list]
        return self._ntt_many_device(coeffs_list, omega, inverse=False)

    def intt_many(self, evals_list, omega: int) -> list:
        if not evals_list:
            return []
        n = evals_list[0].shape[0]
        if len(evals_list) == 1 or self._use_mesh(
                n, self._shard_ntt_min_logn):
            return [self.intt(e, omega) for e in evals_list]
        return self._ntt_many_device(evals_list, omega, inverse=True)

    def coset_lde_many(self, coeffs_list, omega: int, g: int, n_out: int,
                       powers=None) -> list:
        """Batched FUSED coset-LDE: the coefficients go up as they are, the
        device splits their limbs and pads the rows to n_out, then one
        compiled kernel per stack — the std→mont conversion and the g^i
        coset scale both fold into stage 0 of the batched NTT
        (ops/ntt.py:coset_lde_std), so the extension itself is a single
        device program with no separate scale pass and no intermediate
        Montgomery array."""
        from ..ops import limbs as L16, ntt as NTT

        if not coeffs_list:
            return []
        if self._use_mesh(n_out, self._shard_ntt_min_logn):
            # mesh path: per-poly sharded NTT (scale via the host table)
            return super().coset_lde_many(coeffs_list, omega, g, n_out,
                                          powers=powers)
        b = len(coeffs_list)
        call = "backend/coset_lde_many"
        with span(call, batch=b, n=n_out):
            packed = _ship_packed(
                lambda: self._pad_batch(stack_rows(coeffs_list)), call)
            with span(call + "/dispatch"):
                out = NTT.coset_lde_std(L16.split_limbs16(packed, n_out),
                                        omega, g)
            return _fetch_u64_std(
                out, call, lambda std: list(std.reshape(-1, n_out, 4)[:b]))


# stable jitted boundary converters: a fresh `jax.jit(lambda ...)` per
# call (the previous shape) re-traces every time — jit caches by function
# identity — which taxed every NTT/MSM boundary crossing in the prove
_mont_jits: dict = {}

# runner registry (trace-cache hygiene contract, parallel/plan.py):
# analysis/trace_lint cross-checks these (builder, cache) pairs against
# the AST (TC-UNCACHED-RUNNER).
TRACE_RUNNER_CACHES = (
    ("_mont_fns", "_mont_jits"),
    ("_encode_points", "_mont_jits"),
)


def _mont_fns():
    # key-presence check, NOT dict truthiness — _encode_points shares this
    # dict for its "toq" jit, and its insertion must not mask ours
    if "to" not in _mont_jits:
        import jax

        from ..ops import field_ops as F

        ctx = F.fr_ctx()

        def to_mont_fr(v):
            return F.to_mont(ctx, v)

        def from_mont_fr(v):
            return F.from_mont(ctx, v)

        _mont_jits["to"] = jax.jit(to_mont_fr)
        _mont_jits["from"] = jax.jit(from_mont_fr)
    return _mont_jits


def stack_rows(cols, n: int | None = None) -> np.ndarray:
    """[<=n, 4] u64 columns -> one [B, n, 4] stack (n: the longest column
    unless given); a shorter column's tail is zeroed, nothing else is."""
    n = max(c.shape[0] for c in cols) if n is None else n
    stack = np.empty((len(cols), n, 4), dtype=np.uint64)
    for i, c in enumerate(cols):
        stack[i, :c.shape[0]] = c
        stack[i, c.shape[0]:] = 0
    return stack


def _ship_packed(arr, call: str):
    """Stage `encode` of `call`: [..., 4] u64 standard (or a function that
    stacks it first) -> the same rows on the device, [..., 8] u32, 32 bytes
    a field element (ops/limbs.py has the wire format). The caller's
    `dispatch` splits the limbs as its first device step."""
    import jax.numpy as jnp

    from ..ops import limbs as L16

    with span(call + "/encode"):
        if callable(arr):
            arr = arr()
        packed = jnp.asarray(L16.pack_u64limbs(arr))
        annotate(bytes=packed.nbytes)
    return packed


def _to_mont_packed(packed):
    """Packed standard rows -> [..., 16] u32 Montgomery limbs: the limb
    split, then the `to_mont_fr` every NTT kind has always run."""
    from ..ops import limbs as L16

    return _mont_fns()["to"](L16.split_limbs16(packed))


def _fetch_u64_std(out, call: str, unstack=None):
    """Device [..., 16] u32 Montgomery -> host [n,4] u64 standard, in two
    crossings (the spans show them, nothing repairs them yet): the
    Montgomery result is read to the host (`wait`), shipped straight back
    (`encode`) for from_mont (`dispatch`), read again (`wait`) and its
    limbs joined (`decode`; `unstack` then cuts a batch into its list)."""
    import jax.numpy as jnp

    from ..ops import limbs as L16

    with span(call + "/wait", bytes=out.nbytes):
        mont = np.asarray(out).reshape(-1, 16)
    with span(call + "/encode", bytes=mont.nbytes):
        back = jnp.asarray(mont)
    with span(call + "/dispatch"):
        std16 = _mont_fns()["from"](back)
    with span(call + "/wait", bytes=std16.nbytes):
        std16 = np.asarray(std16)
    with span(call + "/decode"):
        std = L16.u16limbs_to_u64limbs(std16)
        return unstack(std) if unstack else std


_backends = {}


def get_backend(name: str = "cpu"):
    if name not in _backends:
        _backends[name] = CpuBackend() if name == "cpu" else TpuBackend()
    return _backends[name]


# ---------------------------------------------------------------------------
# graceful degradation: device prove -> CPU retry (PR 3, resilient service)
# ---------------------------------------------------------------------------

def is_device_oom(exc: BaseException) -> bool:
    """Device out-of-memory classification: XLA surfaces RESOURCE_EXHAUSTED
    through XlaRuntimeError (type name matched — jaxlib moves the class
    between releases); injected faults carry an explicit kind."""
    from ..utils.faults import InjectedFault
    if isinstance(exc, InjectedFault):
        return exc.kind == "oom"
    msg = str(exc)
    return type(exc).__name__ == "XlaRuntimeError" and (
        "RESOURCE_EXHAUSTED" in msg or "Out of memory" in msg
        or "out of memory" in msg)


def is_compile_failure(exc: BaseException) -> bool:
    """Mosaic/XLA compilation failure classification (compile churn on new
    shapes is an expected hazard of accelerator-resident proving)."""
    from ..utils.faults import InjectedFault
    if isinstance(exc, InjectedFault):
        return exc.kind == "compile"
    msg = str(exc)
    if "Mosaic" in msg and ("failed" in msg or "error" in msg.lower()):
        return True
    return type(exc).__name__ == "XlaRuntimeError" and (
        "Compilation failure" in msg or "INTERNAL: Mosaic" in msg)


def prove_with_fallback(prove_fn, bk, health=None):
    """Run `prove_fn(bk)`; on device OOM or compile failure retry ONCE on
    the CPU backend instead of failing the request (ISSUE 3 tentpole (5)).

    `prove_fn` must be a closure over everything but the backend and
    byte-deterministic given the same backend + transcript randomness —
    the CPU retry produces exactly the proof a clean CPU prove would.
    Fault-injection site `backend.prove` fires here so the degradation
    path is deterministically testable without a real device OOM.
    Non-degradable exceptions (witness rejection, bugs) propagate
    untouched, as does anything raised while already on the CPU backend.
    """
    from ..utils import faults
    if health is None:
        from ..utils.health import HEALTH as health
    try:
        faults.check("backend.prove")
        return prove_fn(bk)
    except Exception as exc:
        if not (is_device_oom(exc) or is_compile_failure(exc)):
            raise
        cpu = get_backend("cpu")
        if bk is cpu or getattr(bk, "name", None) == "cpu":
            raise                     # already on the fallback tier
        kind = "oom" if is_device_oom(exc) else "compile"
        health.incr(f"prove_cpu_fallbacks_{kind}")
        # stamp the degradation onto the job's span tree (getTrace
        # `args`) AND the job's provenance manifest: a proof produced on
        # the fallback tier must say so everywhere it is inspected
        from ..observability import manifest, tracing
        tracing.annotate(cpu_fallback=kind)
        manifest.record_event("cpu_fallback", fallback_kind=kind,
                              from_backend=getattr(bk, "name", "device"))
        import sys
        print(f"[prover] device prove failed ({kind}: {exc}); retrying "
              f"once on the CPU backend", file=sys.stderr, flush=True)
        return prove_fn(cpu)

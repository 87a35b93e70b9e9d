"""Device-resident quotient evaluation.

The CPU prover evaluates `all_expressions` through the native batch backend
(~6k sequential host calls over 32MB numpy arrays at k=18 — the dominant
prove phase, 1067s of the 512-committee prove). TPU-first shape: every
column is coset-NTT'd to the extended domain ON DEVICE and stays resident as
a [4n, 16] Montgomery tensor; the expression tree, the y-fold, the vanishing
division, and the inverse coset NTT all run as device ops with no host
round-trips between them.

ISSUE 4: the per-column `to_ext` dispatch is now a BATCHED FUSED prefetch —
the expression tree's column keys are enumerated up front
(`expressions.referenced_keys`), stacked in fixed-size chunks, and extended
through ONE compiled kernel per chunk (`ops/ntt.py:coset_lde_std`: the
std→mont conversion and the coset pre-scale fold into stage 0 of the
batched NTT, honoring SPECTRE_NTT_MODE). The inverse path folds the 1/n
iNTT scale, the g^{-i} coset unscale and the mont→std boundary into one
table multiply (`coset_intt_std`).

ISSUE 19: the pipeline is ENGINE-parameterized. The single-device engine
below is the original path verbatim; when more than one device is up (and
the domain clears the size gate) `compute_quotient` dispatches the same
pipeline through `parallel/sharded_quotient.py`, which runs the LDE
prefetch, every expression primitive, the rotations and the fused inverse
as shard_map programs over the ShardingPlan mesh. Ineligible shapes or a
mesh-path failure fall back here VISIBLY — the `quotient_sharded_degraded`
ServiceHealth counter plus a provenance event — never silently.

What a prove extends and what stays resident: the columns the prove
itself made (advice, lookup, SHA region, grand products, instance) are
extended every prove; the proving key's own columns (`keygen.KEY_KINDS`:
selectors, fixed, sigmas, tables, the SHA region's selectors and round
constants) are extended on the key's first prove and kept on the device
for the next (`_key_ext`, a key and an engine placement), and the three
synthetic rows (l0, l_last, l_blind) beside `xcol` in `_static_cache`, a
(cfg, k). What stays is bounded by `keygen.ext_cache_budget_bytes`
(SPECTRE_EXT_CACHE_MB, else 15 % of the device's memory): the synthetic
rows first, then the key's columns in the expression tree's order while
they fit; the rest is extended every prove. A fill extends in the same
chunks as every other column, under `_fill_lock`;
`ProvingKey.release_ext_cache` drops the key's store and its circuit's
synthetic rows.

Design note (learned the hard way): tracing the WHOLE tree into one jitted
XLA program blows up LLVM codegen on the CPU backend (`Cannot allocate
memory` from the execution engine at ~6k fused scan-heavy ops). The ops are
therefore dispatched EAGERLY through a small set of jitted primitives
(mont mul/add/sub, batched NTT) — data residency, not mega-fusion, is where
the device win lives (each op is HBM-bandwidth-bound either way), and
compile cost stays bounded per primitive shape.

Spans (observability/tracing.py): `quotient/extend` (with `resident`, the
columns read from the stores, `extended`, the columns extended, and
`resident_bytes`, what the stores hold) holds the coefficient fetch, the
stacking (`quotient/extend/encode`: a chunk's columns as their n
rows of 32 bytes, with the bytes the engine's `lde` then ships; the limb
split and the zero rows from n to 4n are the engine's first device step,
`ops/limbs.py:split_limbs16`) and the LDE dispatches
(`quotient/extend/dispatch`);
`quotient/expressions` the expression tree's dispatches; `quotient/wait`
the one blocking read, in the engine's `inverse_std`; `quotient/decode` the
limb join. From the first `quotient/extend/dispatch` to the end of
`quotient/wait` the device has work queued; when it works in that stretch
the spans cannot say, the device timeline (`tracing.on_device`) can: a
mark each LDE chunk in `quotient/extend/dispatch`, one after the last fold
of the expressions (kind `quotient/expressions`, from that span's start)
and one for the inverse (`quotient/inverse`).

Parity: the device path produces EXACTLY the host path's u64 coefficient
arrays, compared in-situ during real proves
(tests/test_plonk.py::TestDeviceQuotient, gate+lookup and wide-SHA shapes;
mesh-vs-host in tests/test_quotient_sharded.py).
"""

from __future__ import annotations

import os
import threading
import time
import weakref

import numpy as np

from ..fields import bn254
from ..observability.tracing import annotate, on_device, span
from ..ops.msm import _TableLRU, _record_event
from .constraint_system import CircuitConfig
from .domain import COSET_GEN, Domain
from .expressions import all_expressions, referenced_keys
from .keygen import KEY_KINDS, ROT_LAST, ext_cache_budget_bytes

R = bn254.R

_jit_helpers: dict = {}
_static_cache: dict = {}

# The proving key's columns (`KEY_KINDS`) extended on the device, a key:
# id(pk) -> (a weak reference to the key, {engine placement: {column key:
# extended array}}). Held here and not on the key, so a pickled key holds
# what it always held; bounded by the byte budget a prove and as
# `prover._key_limbs` is, dropped by `ProvingKey.release_ext_cache`.
_key_ext: dict = {}
# held by a prove that fills a store: two jobs proving one key extend its
# columns once
_fill_lock = threading.Lock()

SYNTHETIC = (("_l0",), ("_llast",), ("_lblind",))

# runner registry (trace-cache hygiene contract, parallel/plan.py):
# analysis/trace_lint cross-checks these (builder, cache) pairs against
# the AST (TC-UNCACHED-RUNNER).
TRACE_RUNNER_CACHES = (("_helpers", "_jit_helpers"),)


def _fused_vinv() -> bool:
    """SPECTRE_QUOTIENT_FUSED_VINV=0 keeps the explicit [4n, 16] vanishing-
    inverse mont_mul pass (the pre-fusion path, byte-identical — kept as the
    oracle for tests/test_ntt_kernels.py). Default: fold it into stage 0 of
    the inverse coset NTT, one fewer full-width elementwise pass per proof."""
    return os.environ.get("SPECTRE_QUOTIENT_FUSED_VINV", "1") != "0"


def _helpers():
    """Jitted primitive ops, created once (stable trace cache)."""
    if not _jit_helpers:
        import jax

        from ..ops import field_ops as F

        fctx = F.fr_ctx()

        # named, so that a device trace tells one program from another
        def quotient_to_mont(v):
            return F.to_mont(fctx, v)

        def quotient_from_mont(v):
            return F.from_mont(fctx, v)

        def quotient_mul(a, b):
            return F.mont_mul(fctx, a, b)

        def quotient_add(a, b):
            return F.add(fctx, a, b)

        def quotient_sub(a, b):
            return F.sub(fctx, a, b)

        def quotient_mul_scalar(a, s):
            return F.mont_mul(fctx, a, s[None, :])

        def quotient_add_scalar(a, s):
            return F.add(fctx, a, s[None, :].repeat(a.shape[0], 0))

        def quotient_fold(acc, y, e):
            return F.add(fctx, F.mont_mul(fctx, acc, y[None, :]), e)

        for key, fn in (("to_mont", quotient_to_mont),
                        ("from_mont", quotient_from_mont),
                        ("mul", quotient_mul), ("add", quotient_add),
                        ("sub", quotient_sub),
                        ("mul_s", quotient_mul_scalar),
                        ("add_s", quotient_add_scalar),
                        ("fold", quotient_fold)):
            _jit_helpers[key] = jax.jit(fn)
    return _jit_helpers


def _scalar_budget_bytes() -> int:
    mb = os.environ.get("SPECTRE_QUOTIENT_SCALAR_MB")
    return (int(mb) if mb is not None else 4) << 20


# Montgomery [16] device scalars keyed by field value — gate coefficients,
# challenges, eval points. Previously a per-prove dict with a clear-at-4096
# panic valve that threw the WHOLE working set away mid-prove; now the same
# byte-budgeted LRU as the MSM/NTT tables (ISSUE 19): eviction is oldest-
# first, counted, and a recompute after eviction is visible in stats()
# (pinned by tests/test_quotient_sharded.py). ~64 bytes/entry — the default
# 4 MB holds every scalar any real circuit has produced; the knob exists so
# the bound is explicit, not so it's ever hit.
_scalar_cache = _TableLRU(_scalar_budget_bytes(),
                          label="quotient mont scalar",
                          budget_var="SPECTRE_QUOTIENT_SCALAR_MB",
                          on_event=_record_event)


def scalar_lru_stats() -> dict:
    """Quotient scalar-cache stats for GET /metrics."""
    return _scalar_cache.stats()


# columns per batched coset-LDE prefetch chunk: fixed so the [B, 4n, 16]
# kernel compiles once per domain, capped by transient bytes (chunk * 4n *
# 16 u32 lanes) so huge extended domains don't spike device memory
def _ext_chunk(m: int) -> int:
    cap = max(1, (256 << 20) // (m * 16 * 4))
    return min(8, 1 << (cap.bit_length() - 1))


def _key_store(pk, placement) -> dict:
    """The device store of `pk`'s columns placed as `placement` says (under
    `_fill_lock`)."""
    hit = _key_ext.get(id(pk))
    if hit is None or hit[0]() is not pk:
        if len(_key_ext) > 4:
            _key_ext.clear()
        hit = _key_ext[id(pk)] = (weakref.ref(pk), {})
    return hit[1].setdefault(placement, {})


def release_key_ext(pk):
    """Drop the key's extended columns and its circuit's synthetic rows
    (`ProvingKey.release_ext_cache`)."""
    cfg = pk.vk.config
    with _fill_lock:
        _key_ext.pop(id(pk), None)
        _static_cache.get((cfg, cfg.k), {}).pop("ext", None)


def _device_budget_bytes() -> int:
    """`ext_cache_budget_bytes` of the device's memory, where it reports
    its limit."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return ext_cache_budget_bytes(stats.get("bytes_limit"))


class _DeviceCtx:
    """all_expressions context over device-resident [m, 16] Montgomery
    tensors, dispatching through the jitted primitives."""

    def __init__(self, cols, m: int, last_row: int, mont_scalar):
        self._h = _helpers()
        self._cols = cols
        self._m = m
        self._last_row = last_row
        self._mont = mont_scalar      # int -> [16] mont device scalar
        self._rot_cache: dict = {}
        self.l0 = cols[("_l0",)]
        self.llast = cols[("_llast",)]
        self.lblind = cols[("_lblind",)]
        self.x_col = cols[("_xcol",)]

    def var(self, key, rot):
        import jax.numpy as jnp

        arr = self._cols[key]
        if rot == 0:
            return arr
        hit = self._rot_cache.get((key, rot))
        if hit is None:
            r = self._last_row if rot == ROT_LAST else rot
            # extended-coset index shift: omega == omega_ext^EXTENSION
            hit = jnp.roll(arr, -4 * r, axis=0)
            self._rot_cache[(key, rot)] = hit
        return hit

    def mul(self, a, b):
        return self._h["mul"](a, b)

    def add(self, a, b):
        return self._h["add"](a, b)

    def sub(self, a, b):
        return self._h["sub"](a, b)

    def scale(self, a, s):
        return self._h["mul_s"](a, self._mont(s))

    def add_const(self, a, s):
        return self._h["add_s"](a, self._mont(s))

    def const(self, s):
        import jax.numpy as jnp

        return jnp.broadcast_to(self._mont(s), (self._m, 16))

    def fold(self, acc, y, e):
        return self._h["fold"](acc, self._mont(y), e)


class _LocalEngine:
    """Single-device quotient engine: the original pipeline, expressed
    through the same seam the mesh engine plugs into."""

    name = "local"
    placement = name      # what the resident stores key its arrays by

    def __init__(self, dom: Domain):
        self.dom = dom
        self.m = dom.n_ext

    def chunk(self, base: int) -> int:
        return base

    def lde(self, packed: np.ndarray):
        """Batched fused coset-LDE of a [B, n, 8] packed standard-form
        stack (ops/limbs.py: the wire format): the device splits the limbs
        and pads the rows to m, then ONE compiled kernel (std→mont + g^i
        scale fused into stage 0; SPECTRE_NTT_MODE selects
        radix2/fourstep)."""
        import jax.numpy as jnp

        from ..ops import limbs as L16, ntt as NTT

        std16 = L16.split_limbs16(jnp.asarray(packed), self.m)
        out = NTT.coset_lde_std(std16, self.dom.omega_ext, COSET_GEN)
        return [out[i] for i in range(packed.shape[0])]

    def device_col(self, arr16):
        return arr16

    def ctx(self, cols, last_row: int, mont_scalar) -> _DeviceCtx:
        return _DeviceCtx(cols, self.m, last_row, mont_scalar)

    def inverse_std(self, acc, vinv_vals) -> np.ndarray:
        from ..ops import ntt as NTT

        since = time.perf_counter()
        if vinv_vals is None:
            std = NTT.coset_intt_std(acc, self.dom.omega_ext, COSET_GEN)
        else:
            std = NTT.coset_intt_std_vinv(acc, self.dom.omega_ext,
                                          COSET_GEN, vinv_vals)
        on_device(std, kind="quotient/inverse", since=since)
        # the quotient's one blocking read: the whole queue drains here
        with span("quotient/wait", bytes=std.nbytes):
            return np.asarray(std)


def _shard_min_logn() -> int:
    """Extended domains below 2^this stay single-device without noise: at
    small m the per-op collective + dispatch overhead swamps the shard win,
    and a dev-box 8-virtual-device mesh would otherwise silently route every
    ordinary test prove through the mesh runners on one physical core. The
    default mirrors SHARD_NTT_MIN_LOGN (the quotient is NTT-dominated):
    high enough that only an explicit opt-in (the sharded-quotient tests)
    engages the mesh on a virtual-device box."""
    return int(os.environ.get("SPECTRE_SHARD_QUOTIENT_MIN_LOGN", "18"))


def _degrade(reason: str, **detail):
    from ..utils.health import HEALTH
    HEALTH.incr("quotient_sharded_degraded")
    _record_event("quotient_sharded_degraded", reason=reason, **detail)


def _mesh_engine(dom: Domain):
    """The sharded engine when the mesh prove path applies, else None.

    Silent single-device: kill switch off, one device, or below the size
    gate. VISIBLE degrade (`quotient_sharded_degraded` counter + provenance
    event): a real mesh and a big enough domain, but a shape the Bailey
    row partition can't cover."""
    if os.environ.get("SPECTRE_QUOTIENT_SHARDED", "1") == "0":
        return None
    import jax
    if jax.device_count() <= 1:
        return None
    logm = dom.n_ext.bit_length() - 1
    if logm < _shard_min_logn():
        return None
    from ..parallel import sharded_quotient as SQ
    from ..parallel.plan import current_plan

    plan = current_plan()
    if plan.n_devices <= 1:
        return None
    if not SQ.eligible(plan, dom.n_ext):
        _degrade("ineligible_shape", n_ext=dom.n_ext,
                 n_devices=plan.n_devices)
        return None
    return SQ.MeshQuotientEngine(plan, dom)


def compute_quotient(cfg: CircuitConfig, dom: Domain, fetch_coeffs,
                     beta: int, gamma: int, y: int, pk=None) -> np.ndarray:
    """Device quotient: returns h coefficients as [4n, 4] u64 standard form
    (drop-in for the host path's extended_to_coeff output).

    fetch_coeffs(key) -> [<=n, 4] u64 coefficient-form poly for every column
    key the expression tree reads. pk: the proving key whose columns the
    `KEY_KINDS` keys are; given, they stay extended on the device for its
    next prove (None: every column is extended)."""
    engine = _mesh_engine(dom)
    if engine is not None:
        try:
            return _quotient_impl(cfg, dom, fetch_coeffs, beta, gamma, y,
                                  engine, pk)
        except Exception as e:  # mesh-path failure: fall back, visibly
            _degrade("mesh_exception", error=f"{type(e).__name__}: {e}",
                     n_ext=dom.n_ext)
    return _quotient_impl(cfg, dom, fetch_coeffs, beta, gamma, y,
                          _LocalEngine(dom), pk)


def _quotient_impl(cfg: CircuitConfig, dom: Domain, fetch_coeffs,
                   beta: int, gamma: int, y: int, engine,
                   pk=None) -> np.ndarray:
    import jax.numpy as jnp

    from ..ops import limbs as L16
    from . import backend as B

    h = _helpers()
    to_mont16 = h["to_mont"]

    def mont_of_rows(arr_u64):
        # up as the packed rows, like the columns: the device splits them
        return to_mont16(L16.split_limbs16(
            jnp.asarray(L16.pack_u64limbs(arr_u64))))

    mont_of = lambda ints: mont_of_rows(B.to_arr(ints))

    def mont_scalar(s):
        v = int(s) % R
        hit = _scalar_cache.get(v, None)
        if hit is None:
            hit = _scalar_cache.put(v, None, mont_of([v])[0])
        return hit

    # per-(cfg, domain) static device inputs: synthetic rows, x column —
    # built once, reused every proof (the coset scale / unscale tables now
    # live inside ops/ntt.py's budgeted table LRU as part of the fused
    # kernels, and the vanishing inverse rides the fused inverse path as a
    # stage-0 table; the explicit [4n, 16] tensor materializes lazily only
    # when SPECTRE_QUOTIENT_FUSED_VINV=0)
    n, m = dom.n, dom.n_ext
    ck = (cfg, dom.k)
    st = _static_cache.get(ck)
    if st is None:
        def row_of(idx_vals):
            vals = [0] * n
            for i in idx_vals:
                vals[i] = 1
            return dom.lagrange_to_coeff(B.to_arr(vals))

        st = {
            "xcol": mont_of([COSET_GEN * pow(dom.omega_ext, i, R) % R
                             for i in range(m)]),
            "l0": row_of([0]),
            "llast": row_of([cfg.last_row]),
            "lblind": row_of(range(cfg.usable_rows + 1, n)),
        }
        if len(_static_cache) > 4:
            _static_cache.clear()
        _static_cache[ck] = st

    def ext_of_many(arrs_u64):
        """Stack a coefficient-array list as ONE packed standard-form
        [B, n, 8] array and extend it through the engine's batched LDE."""
        with span("quotient/extend/encode", bytes=len(arrs_u64) * n * 32):
            packed = L16.pack_u64limbs(B.stack_rows(arrs_u64, n))
        with span("quotient/extend/dispatch"):
            outs = engine.lde(packed)
            on_device(outs)
            return outs

    def ext_of_coeffs(arr_u64):
        return ext_of_many([arr_u64])[0]

    plan = referenced_keys(cfg)      # the synthetic rows are not among them

    def extend(resident: dict):
        """Every column `resident` lacks, extended; returns them all and
        how many were (padding not counted). The synthetic rows extend as
        one batched call, the other columns in fixed-size chunks
        enumerated from the expression tree."""
        cols = dict(resident)
        if SYNTHETIC[0] not in cols:
            cols.update(zip(SYNTHETIC, ext_of_many(
                [st["l0"], st["llast"], st["lblind"]])))
        todo = [k for k in plan if k not in cols]
        chunk_sz = engine.chunk(_ext_chunk(m))
        for base in range(0, len(todo), chunk_sz):
            chunk = todo[base:base + chunk_sz]
            # pad the tail chunk with the first key so the kernel sees one
            # batch shape per domain (duplicates are free — same NTT,
            # sliced)
            padded = chunk + [chunk[0]] * (chunk_sz - len(chunk))
            outs = ext_of_many([fetch_coeffs(k) for k in padded])
            cols.update(zip(chunk, outs))
        return cols, len(cols) - len(resident)

    with span("quotient/extend", n_ext=m):
        # What stays extended on the device: the synthetic rows beside
        # `xcol` (they depend on (cfg, k) alone), then the key's columns in
        # the tree's order, while their bytes fit the budget. A store keeps
        # exactly that: a fill extends what it lacks under `_fill_lock`
        fits = _device_budget_bytes() // (m * 16 * 4)
        keys = [k for k in plan if pk is not None and k[0] in KEY_KINDS]
        wanted = (list(SYNTHETIC) + keys)[:fits] if fits >= 3 else []
        filled = None
        with _fill_lock:
            synth = st.setdefault("ext", {}).setdefault(engine.placement, {})
            keyed = _key_store(pk, engine.placement) if pk is not None \
                else {}
            stores = (synth, keyed)
            for store in stores:
                for k in [k for k in store if k not in wanted]:
                    del store[k]
            resident = {k: store[k] for store in stores for k in wanted
                        if k in store}
            if len(resident) < len(wanted):
                filled = extend(resident)
                for k in wanted:
                    (synth if k in SYNTHETIC else keyed)[k] = filled[0][k]
            held = sum(a.nbytes for store in stores for a in store.values())
        cols, extended = filled or extend(resident)
        annotate(resident=len(resident), extended=extended,
                 resident_bytes=held)
        cols[("_xcol",)] = engine.device_col(st["xcol"])

    class LazyCols(dict):
        # safety net: any key the recorder missed still materializes
        def __missing__(self, key):
            arr = ext_of_coeffs(fetch_coeffs(key))
            self[key] = arr
            return arr

    with span("quotient/expressions"):
        ctx = engine.ctx(LazyCols(cols), cfg.last_row, mont_scalar)
        acc = None
        for e in all_expressions(cfg, ctx, beta, gamma):
            acc = e if acc is None else ctx.fold(acc, y, e)
        if acc is None:
            raise ValueError("config yields no constraint expressions — "
                             "nothing to fold into a quotient")
        # the device timeline's one mark of the folds, from this span's
        # start (the expressions have no dispatch span of their own)
        on_device(acc, kind="quotient/expressions")
    # h = acc / Z_H on the coset, then the fused inverse path: ONE kernel —
    # the 1/Z_H stage-0 pre-scale, the iNTT, and the combined
    # g^{-i}·n^{-1}·(mont→std) output table all ride a single transform.
    # The engine reads the result inside `quotient/wait`
    if _fused_vinv():
        std = engine.inverse_std(acc, dom.vanishing_inv_period_vals())
    else:
        vinv = st.get("vinv")
        if vinv is None:
            vinv = st["vinv"] = mont_of_rows(
                dom.vanishing_inv_on_extended())
        hacc = ctx.mul(acc, engine.device_col(vinv))
        std = engine.inverse_std(hacc, None)
    with span("quotient/decode"):
        return L16.u16limbs_to_u64limbs(std)

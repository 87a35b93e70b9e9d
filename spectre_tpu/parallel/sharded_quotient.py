"""Mesh-sharded quotient pipeline (ISSUE 19).

`plonk/quotient_device.py` evaluates the quotient on ONE device even when an
8-way mesh is up: every [4n, 16] extended-domain tensor, every gate
expression, and the two full-width NTT boundaries run on device 0. This
module shards all three phases over the interned `ShardingPlan`'s batch mesh
(every device, one axis) while keeping the eager-primitive dispatch
discipline the quotient engine is built on (tracing the whole expression
tree into one program blows up LLVM codegen — see quotient_device's design
note). Layers:

  * LDE prefetch (`_lde_runner`): the chunked `coset_lde_std` batch is
    sharded over the BATCH axis — the packed [B, n, 8] stack goes up
    batch-sharded, each device splits its own columns' limbs and pads
    their rows to 4n (`ops/limbs.py:split_limbs16`) and runs the same
    fused single-device `_fwd_kernel` body on them (embarrassingly
    parallel, byte-identical by construction) — then ONE all_to_all
    resharding turns the batch-sharded [B, 4n, 16] stack into row-sharded
    [4n, 16] columns for the pointwise phase.
  * Gate evaluation (`_eval_runner` family): mont mul/add/sub, scalar
    broadcast ops and the y-fold as tiny shard_map programs over row-sharded
    tensors — pure local math, no collectives.
  * Rotations (`_roll_runner`): `jnp.roll` does not shard; a static-shift
    roll decomposes into at most two `ppermute`s (the whole-block shift
    s // block and the remainder halo) plus a local concat. Any shift works
    — the SHA region reaches 65 base rows back and the permutation argument
    rotates by `last_row`, so a fixed small halo would not cover the
    expression stream (this is the "rotation-closed" requirement: the
    blockwise partition is closed under arbitrary static rolls at the cost
    of one neighbor exchange).
  * Fused inverse (`_inv_runner`): the `coset_intt_std_vinv` boundary as a
    sharded Bailey/four-step transform — the vanishing-inverse stage-0
    pre-scale, the inverse-root row/col short transforms, the all_to_all
    transpose, and the combined g^{-i}·n^{-1}·(mont→std) output table all
    inside one SPMD program, mirroring `sharded_ntt` with the quotient's
    boundary fusions riding along as mesh-resident tables.

Runner discipline (TC-FRESH-JIT): every program is built once per
(plan, shape, static-config) key in a module-level cache declared in
`TRACE_RUNNER_CACHES`, registered in `plan.RUNNER_REGISTRY_MODULES`, and
exercised by the trace-lint double-call probe. Byte-identity with the
single-device path across {mesh shape} x {SPECTRE_NTT_MODE} x
{SPECTRE_NTT_KERNEL} is pinned by tests/test_quotient_sharded.py.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import NamedSharding, PartitionSpec as P

from ..fields import bn254
from ..observability import compilelog
from ..observability.tracing import on_device, span
from ..ops import field_ops as F, limbs as L16, ntt as NTT
from .plan import ShardingPlan

R = bn254.R

# compiled SPMD programs, keyed on (plan.key, <static shape/config>); the
# resident-table caches hold mesh-placed device arrays (device_put only —
# no compiles), like sharded_ntt._TWIDDLES
_RUNNERS: dict = {}
_ROLLS: dict = {}
_LDES: dict = {}
_INVS: dict = {}
_INV_TABLES: dict = {}

# runner registry (trace-cache hygiene contract, parallel/plan.py):
# declared builders are cross-checked by analysis/trace_lint
# (TC-UNCACHED-RUNNER) and exercised by its retrace probes.
TRACE_RUNNER_CACHES = (
    ("_eval_runner", "_RUNNERS"),
    ("_roll_runner", "_ROLLS"),
    ("_lde_runner", "_LDES"),
    ("_inv_runner", "_INVS"),
)


def _clear_caches():
    for c in (_RUNNERS, _ROLLS, _LDES, _INVS, _INV_TABLES):
        c.clear()


def _fence(x):
    """Serialize rendezvous programs on the CPU backend.

    XLA:CPU runs each partition of a collective execution as a thread-pool
    task; with async dispatch, two rendezvous-bearing programs (ppermute
    rolls, all_to_all reshards) in flight at once can interleave their
    partition tasks and starve each other's rendezvous — observed as the
    k=13 collective-permute hang of a mesh prove on 8 virtual devices
    after ~2.4k clean collective runs. Blocking after every collective
    launch keeps at most ONE rendezvous program in flight. Real
    accelerators execute programs in per-core launch order, so they skip
    the barrier and keep the async pipeline."""
    if jax.default_backend() == "cpu":
        jax.block_until_ready(x)
    return x


# --- per-shard local compute (no collectives) -------------------------------
# Extracted from the shard_map closures so the kernel linter can trace them
# at tiny shapes without a mesh (analysis/kernel_lint known-root table).

def _lde_local(stack, omega: int, g, mode: str, kernel: str):
    """Local slice of the batch-sharded fused coset-LDE: full-length
    transforms of this device's columns — the SAME `_fwd_kernel` body as the
    single-device batched prefetch, so results are byte-identical column by
    column. stack: [B_local, n, 16] standard-form limbs."""
    return NTT._fwd_kernel.__wrapped__(stack, omega, ("std", g), mode, kernel)


def _inv_rows_local(block, scb, twb, omega_row: int, mode: str, kernel: str):
    """Fused-inverse steps 0-2 on one shard: the stage-0 pre-scale (the
    quotient's vanishing inverse — an explicit mont_mul, byte-identical to
    the single-device stage-0 fusion since elementwise order commutes with
    the Bailey reshape), length-Cc inverse-root NTTs along each local row,
    then the inter-pass twiddle. block/scb/twb: [rows_local, Cc, 16]."""
    fctx = F.fr_ctx()
    y = F.mont_mul(fctx, block, scb)
    y = jax.vmap(
        lambda row: NTT._fwd_kernel.__wrapped__(row, omega_row, None,
                                                mode, kernel))(y)
    return F.mont_mul(fctx, y, twb)


def _inv_cols_local(y, outb, omega_col: int, mode: str, kernel: str):
    """Fused-inverse step 4 + output boundary on one shard: length-Rr NTTs
    along each post-transpose row, then ONE multiply by the combined
    g^{-i}·n^{-1}·(mont→std) table slice (raw table: output is standard
    form). y/outb: [cols_local, Rr, 16]."""
    fctx = F.fr_ctx()
    y = jax.vmap(
        lambda row: NTT._fwd_kernel.__wrapped__(row, omega_col, None,
                                                mode, kernel))(y)
    return F.mont_mul(fctx, y, outb)


# --- cached SPMD runners ----------------------------------------------------

def _eval_runner(plan: ShardingPlan, op: str, m: int):
    """Pointwise expression primitive over row-sharded [m, 16] tensors:
    op in {mul, add, sub, mul_s, add_s, fold}. Scalars ride replicated."""
    key = (plan.key, op, m)
    hit = _RUNNERS.get(key)
    if hit is not None:
        return hit
    fctx = F.fr_ctx()
    ax = plan.batch_axis
    row, rep = P(ax, None), P(None)
    if op == "mul":
        specs, body = (row, row), lambda a, b: F.mont_mul(fctx, a, b)
    elif op == "add":
        specs, body = (row, row), lambda a, b: F.add(fctx, a, b)
    elif op == "sub":
        specs, body = (row, row), lambda a, b: F.sub(fctx, a, b)
    elif op == "mul_s":
        specs, body = (row, rep), lambda a, s: F.mont_mul(fctx, a, s[None, :])
    elif op == "add_s":
        specs, body = (row, rep), lambda a, s: F.add(
            fctx, a, jnp.broadcast_to(s[None, :], a.shape))
    elif op == "fold":
        specs = (row, rep, row)
        body = lambda acc, y, e: F.add(
            fctx, F.mont_mul(fctx, acc, y[None, :]), e)
    else:
        raise ValueError(f"unknown quotient eval op {op!r}")
    fn = jax.jit(functools.partial(
        shard_map, mesh=plan.batch_mesh, in_specs=specs, out_specs=row,
        check_vma=False)(body))
    if len(_RUNNERS) > 64:
        _RUNNERS.clear()
    _RUNNERS[key] = fn
    return fn


def _roll_runner(plan: ShardingPlan, m: int, shift: int):
    """`jnp.roll(arr, -shift, axis=0)` of a row-sharded [m, 16] tensor as a
    shard_map program: out[j] = arr[(j + shift) mod m]. With block size
    B = m/D the static shift decomposes as q·B + r — each device needs
    shard (d+q) and, when r > 0, a halo from shard (d+q+1): at most two
    ppermutes and one local concat, for ANY shift (rotation-closed under
    the blockwise partition)."""
    d = plan.n_devices
    shift = shift % m
    key = (plan.key, m, shift)
    hit = _ROLLS.get(key)
    if hit is not None:
        return hit
    ax = plan.batch_axis
    block = m // d
    q, rem = shift // block, shift % block
    spec = P(ax, None)

    @functools.partial(
        shard_map, mesh=plan.batch_mesh, in_specs=(spec,), out_specs=spec,
        check_vma=False)
    def run(x):
        a = x if q % d == 0 else jax.lax.ppermute(
            x, ax, [((i + q) % d, i) for i in range(d)])
        if rem == 0:
            return a
        b = x if (q + 1) % d == 0 else jax.lax.ppermute(
            x, ax, [((i + q + 1) % d, i) for i in range(d)])
        return jnp.concatenate([a[rem:], b[:rem]], axis=0)

    fn = jax.jit(run)
    if len(_ROLLS) > 256:
        _ROLLS.clear()
    _ROLLS[key] = fn
    return fn


def _lde_runner(plan: ShardingPlan, b: int, logm: int, omega: int, g):
    """Batch-sharded fused coset-LDE + ONE all_to_all reshard: [B, n, 16]
    standard-form columns in (batch over devices), row-sharded Montgomery
    evaluations out. B must be a multiple of the device count."""
    mode = NTT._resolve_mode(None, logm)
    kernel = NTT._resolve_kernel(None, mode)
    key = (plan.key, b, logm, omega, g, mode, kernel)
    hit = _LDES.get(key)
    if hit is not None:
        return hit
    ax = plan.batch_axis

    @functools.partial(
        shard_map, mesh=plan.batch_mesh, in_specs=(P(ax, None, None),),
        out_specs=P(None, ax, None), check_vma=False)
    def run(stack):                       # [B/D, n, 16] local columns
        y = _lde_local(stack, omega, g, mode, kernel)
        # batch-sharded -> row-sharded: split the row axis, gather batch
        return jax.lax.all_to_all(y, ax, split_axis=1, concat_axis=0,
                                  tiled=True)        # [B, n/D, 16]

    fn = jax.jit(run)
    if len(_LDES) > 32:
        _LDES.clear()
    _LDES[key] = fn
    return fn


def _inv_runner(plan: ShardingPlan, logm: int, omega: int, g,
                vinv_vals: tuple | None):
    """Sharded fused inverse boundary (`coset_intt_std_vinv` semantics):
    Bailey decomposition at size m = 2^logm with the inverse root, the
    vanishing-inverse pre-scale and the combined output table fused into the
    shard-local legs. In/out: Bailey-matrix layout (see `_inv_apply`)."""
    logr = logm // 2
    logc = logm - logr
    row_mode = NTT._resolve_mode(None, logc)
    col_mode = NTT._resolve_mode(None, logr)
    row_kernel = NTT._resolve_kernel(None, row_mode)
    col_kernel = NTT._resolve_kernel(None, col_mode)
    key = (plan.key, logm, omega, g, vinv_vals, row_mode, col_mode,
           row_kernel, col_kernel)
    hit = _INVS.get(key)
    if hit is not None:
        return hit
    d = plan.n_devices
    rr, cc = 1 << logr, 1 << logc
    assert rr % d == 0 and cc % d == 0, \
        f"shard count {d} must divide both matrix dims {rr}x{cc}"
    omega_inv = pow(omega, -1, R)
    omega_row = pow(omega_inv, rr, R)    # length-Cc root (step 1)
    omega_col = pow(omega_inv, cc, R)    # length-Rr root (step 4)
    ax = plan.batch_axis
    spec = P(ax, None, None)

    @functools.partial(
        shard_map, mesh=plan.batch_mesh, in_specs=(spec,) * 4,
        out_specs=spec, check_vma=False)
    def run(block, scb, twb, outb):
        y = _inv_rows_local(block, scb, twb, omega_row, row_mode, row_kernel)
        y = jax.lax.all_to_all(y, ax, split_axis=1, concat_axis=0,
                               tiled=True)           # [rr, cc/d, 16]
        y = y.transpose(1, 0, 2)                     # [cc/d, rr, 16]
        return _inv_cols_local(y, outb, omega_col, col_mode, col_kernel)

    fn = jax.jit(run)
    if len(_INVS) > 16:
        _INVS.clear()
    _INVS[key] = fn
    return fn


def _inv_tables(plan: ShardingPlan, logm: int, omega: int, g,
                vinv_vals: tuple | None):
    """Mesh-resident table triple for `_inv_runner`: the stage-0 pre-scale
    (tiled vanishing inverse, identity when None), the inverse-root
    inter-pass twiddles, and the combined raw output table — each reshaped
    into its shard-local layout and device_put row-sharded ONCE per
    (plan, size, root, vinv) like sharded_ntt's resident twiddles."""
    key = (plan.key, logm, omega, g, vinv_vals)
    hit = _INV_TABLES.get(key)
    if hit is not None:
        return hit
    logr = logm // 2
    logc = logm - logr
    rr, cc = 1 << logr, 1 << logc
    omega_inv = pow(omega, -1, R)
    sc = NTT._vinv_in_table(logm, vinv_vals if vinv_vals is not None
                            else (1,))               # [m, 16] mont
    # A[jr, jc] = x[jc*rr + jr]: same view the data enters the runner in
    sc_a = np.moveaxis(np.asarray(sc).reshape(cc, rr, 16), 0, 1)
    tw = NTT._twiddle_matrix(logr, logc, omega_inv)  # [rr, cc, 16]
    out = NTT._fused_out_table(logm, g, True)        # [m, 16] raw (std out)
    # final layout Y[kc, kr] = X[kr*cc + kc]
    out_y = np.transpose(np.asarray(out).reshape(rr, cc, 16), (1, 0, 2))
    sh = NamedSharding(plan.batch_mesh, P(plan.batch_axis, None, None))
    tables = tuple(jax.device_put(jnp.asarray(t), sh)
                   for t in (sc_a, tw, out_y))
    if len(_INV_TABLES) > 8:
        _INV_TABLES.clear()
    _INV_TABLES[key] = tables
    return tables


def _inv_apply(plan: ShardingPlan, acc, logm: int, omega: int, g,
               vinv_vals: tuple | None):
    """Run the sharded fused inverse on a row-sharded [m, 16] accumulator;
    returns the natural-order standard-form [m, 16] result (host numpy)."""
    logr = logm // 2
    rr, cc = 1 << logr, 1 << (logm - logr)
    run = _inv_runner(plan, logm, omega, g, vinv_vals)
    scb, twb, outb = _inv_tables(plan, logm, omega, g, vinv_vals)
    sh = NamedSharding(plan.batch_mesh, P(plan.batch_axis, None, None))
    # A[jr, jc] = acc[jc*rr + jr], rows (jr) sharded
    with span("quotient/inverse/encode", bytes=acc.nbytes):
        a = jax.device_put(acc.reshape(cc, rr, 16).transpose(1, 0, 2), sh)
    with span("quotient/inverse/dispatch"), \
            compilelog.entry_point("parallel.sharded_quotient.inverse"):
        out = run(a, scb, twb, outb)                 # [cc, rr, 16]
        on_device(out)
    with span("quotient/inverse/wait", bytes=out.nbytes):
        out = np.asarray(out)
    return out.transpose(1, 0, 2).reshape(1 << logm, 16)


# --- eligibility + the expression-evaluation context ------------------------

def eligible(plan: ShardingPlan, m: int) -> bool:
    """Shape feasibility of the sharded pipeline: the device count must
    divide both Bailey dims of the extended domain (which also gives an
    exact blockwise row partition for the pointwise/roll phase)."""
    d = plan.n_devices
    if d <= 1 or m % d:
        return False
    logm = m.bit_length() - 1
    if 1 << logm != m:
        return False
    logr = logm // 2
    return (1 << logr) % d == 0 and (1 << (logm - logr)) % d == 0


class MeshCtx:
    """`all_expressions` context over ROW-SHARDED [m, 16] Montgomery
    tensors: the mesh twin of quotient_device._DeviceCtx, dispatching every
    primitive through the cached shard_map runners."""

    def __init__(self, plan: ShardingPlan, cols, m: int, last_row: int,
                 mont_scalar):
        self._plan = plan
        self._cols = cols
        self._m = m
        self._last_row = last_row
        self._base_mont = mont_scalar  # int -> [16] mont scalar (any device)
        self._scalars: dict = {}       # value -> mesh-replicated [16]
        self._rot_cache: dict = {}
        self._rep = NamedSharding(plan.batch_mesh, P(None))
        zero = jnp.zeros((m, 16), jnp.uint32)
        self._zero = jax.device_put(
            zero, NamedSharding(plan.batch_mesh, P(plan.batch_axis, None)))
        self.l0 = cols[("_l0",)]
        self.llast = cols[("_llast",)]
        self.lblind = cols[("_lblind",)]
        self.x_col = cols[("_xcol",)]

    def _mont(self, s):
        v = int(s) % R
        hit = self._scalars.get(v)
        if hit is None:
            hit = jax.device_put(self._base_mont(v), self._rep)
            self._scalars[v] = hit
        return hit

    def _run(self, op, *args):
        fn = _eval_runner(self._plan, op, self._m)
        with compilelog.entry_point("parallel.sharded_quotient.eval"):
            return fn(*args)

    def var(self, key, rot):
        arr = self._cols[key]
        if rot == 0:
            return arr
        hit = self._rot_cache.get((key, rot))
        if hit is None:
            r = self._last_row if rot == ROT_LAST else rot
            # extended-coset index shift: omega == omega_ext^EXTENSION
            roll = _roll_runner(self._plan, self._m, 4 * r)
            with compilelog.entry_point("parallel.sharded_quotient.roll"):
                hit = _fence(roll(arr))
            self._rot_cache[(key, rot)] = hit
        return hit

    def mul(self, a, b):
        return self._run("mul", a, b)

    def add(self, a, b):
        return self._run("add", a, b)

    def sub(self, a, b):
        return self._run("sub", a, b)

    def scale(self, a, s):
        return self._run("mul_s", a, self._mont(s))

    def add_const(self, a, s):
        return self._run("add_s", a, self._mont(s))

    def const(self, s):
        # a row-sharded constant column: 0 + s through the add_s runner
        # keeps the result on the mesh without a host-side materialize
        return self._run("add_s", self._zero, self._mont(s))

    def fold(self, acc, y_m, e):
        return self._run("fold", acc, self._mont(y_m), e)


# imported late to avoid a plonk <-> parallel import cycle at module load
from ..plonk.keygen import ROT_LAST  # noqa: E402


class MeshQuotientEngine:
    """Quotient-pipeline engine over the ShardingPlan batch mesh — the
    drop-in mesh twin of quotient_device's single-device engine (same
    skeleton, sharded runners). Built per compute_quotient call; all
    compiled programs and resident tables live in the module caches."""

    name = "sharded"

    def __init__(self, plan: ShardingPlan, dom):
        self.plan = plan
        self.dom = dom
        self.m = dom.n_ext
        self._logm = self.m.bit_length() - 1
        self._row_sh = NamedSharding(plan.batch_mesh,
                                     P(plan.batch_axis, None))

    def chunk(self, base: int) -> int:
        """LDE prefetch chunk: the single-device transient-bytes cap,
        rounded to a multiple of the device count for the batch shard."""
        d = self.plan.n_devices
        return max(d, (base // d) * d)

    def lde(self, packed: np.ndarray):
        """[B, n, 8] packed standard-form stack (ops/limbs.py: the wire
        format) -> list of B row-sharded Montgomery [m, 16] evaluations
        (pads the batch up to a device-count multiple; duplicate tail
        columns are computed and dropped). The stack goes up batch-sharded
        as it is; each device splits the limbs of its own columns and pads
        their rows to m (`split_limbs16`: nothing crosses the batch axis,
        no collective) before the LDE program."""
        b = packed.shape[0]
        d = self.plan.n_devices
        bp = max(d, ((b + d - 1) // d) * d)
        if bp != b:
            packed = np.concatenate(
                [packed, np.repeat(packed[:1], bp - b, axis=0)], axis=0)
        run = _lde_runner(self.plan, bp, self._logm, self.dom.omega_ext,
                          self._g())
        sh = NamedSharding(self.plan.batch_mesh,
                           P(self.plan.batch_axis, None, None))
        stack = L16.split_limbs16(jax.device_put(packed, sh), self.m)
        with compilelog.entry_point("parallel.sharded_quotient.lde"):
            out = _fence(run(stack))
        return [out[i] for i in range(b)]

    def _g(self):
        from ..plonk.domain import COSET_GEN
        return COSET_GEN

    def device_col(self, arr16):
        """Place a host-built [m, 16] Montgomery column row-sharded."""
        return jax.device_put(jnp.asarray(arr16), self._row_sh)

    def ctx(self, cols, last_row: int, mont_scalar) -> MeshCtx:
        return MeshCtx(self.plan, cols, self.m, last_row, mont_scalar)

    def inverse_std(self, acc, vinv_vals) -> np.ndarray:
        """The h-path boundary: fused vanishing-inverse + inverse coset NTT
        + std output, sharded. vinv_vals None = identity pre-scale (the
        SPECTRE_QUOTIENT_FUSED_VINV=0 oracle path multiplies explicitly
        before calling in)."""
        # the mesh path crosses twice: the accumulator comes to the host
        # whole (the queue drains here, as in the local engine) and goes
        # back up re-laid-out for the sharded inverse (`quotient/inverse/*`)
        with span("quotient/wait", bytes=acc.nbytes):
            acc = np.asarray(acc)
        return _inv_apply(self.plan, acc, self._logm,
                          self.dom.omega_ext, self._g(), vinv_vals)

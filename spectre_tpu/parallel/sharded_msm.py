"""MSM sharded over a device mesh (tensor-parallel analog for the prover).

Decomposition (SURVEY.md §2c(a)): points are sharded along the mesh "data"
axis — each shard computes per-window partial sums over its local points —
and Pippenger windows are sharded along the "win" axis. Partial window sums
are combined with an all-gather over "data" followed by a local projective
tree-fold (EC addition is not a psum-able monoid over limb tensors, so the
reduction is an explicit gather+fold riding ICI), then windows are gathered
over "win" and the final double-and-add combine runs replicated.

Program caching (ISSUE 13 tentpole): every SPMD program here is built ONCE
per (ShardingPlan, static-shape-class) and held in module-level runner
caches. The previous shape — a fresh shard_map closure wrapped in a fresh
`jax.jit` per call — re-traced and re-lowered the full 8-way SPMD program
for every MSM in a prove, which is exactly the MULTICHIP_r01/r05 rc=124
timeout. The persistent compile cache never helped because tracing +
lowering (not XLA compilation) was the per-call cost.

Fixed-base mode (`SPECTRE_MSM_MODE=fixed`) runs sharded since ISSUE 13:
the [nwin, N, 3, 16] window table is built BY the mesh (each data shard
runs the doubling chains over its own point rows) and stays resident
sharded along the row axis — `T[w]` slices co-resident with their point
shards, per `ShardingPlan.table_spec`. Cross-window bucket merge before a
single aggregation pass is still sound (the table bases carry `2^{cw}`),
and the per-DEVICE table budget is what gates degradation: a mesh can
afford fixed tables a single device cannot.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..observability import compilelog
from ..ops import ec, msm as MSM
from .plan import ShardingPlan, plan_for_mesh


def _fold_points(stacked):
    """Tree-fold [k, *, 3, 16] partial sums -> [*, 3, 16]."""
    return MSM._tree_sum(stacked, 0)


# compiled SPMD programs, one per (plan, shape-class). Keys embed plan.key
# plus every static parameter the closure bakes in; values are stable
# jitted function objects so jax's trace cache actually hits.
_RUNNERS: dict = {}

# runner registry (the trace-cache hygiene contract, parallel/plan.py):
# every builder that stores a jitted program in a module cache is declared
# here; analysis/trace_lint cross-checks the pairs against the AST
# (TC-UNCACHED-RUNNER) and its retrace probes exercise the runners.
TRACE_RUNNER_CACHES = (
    ("_windows_runner", "_RUNNERS"),
    ("_table_build_runner", "_RUNNERS"),
    ("_fixed_runner", "_RUNNERS"),
)


def _nwin_for(c: int, nbits: int, signed: bool) -> int:
    return (nbits + c) // c if signed else (nbits + c - 1) // c


# --- per-shard local compute (no collectives) -------------------------------
# Extracted from the shard_map closures so the kernel linter can trace them
# at tiny shapes without a mesh (analysis/kernel_lint registers each as a
# known root); the SPMD bodies below call these with widx = axis_index.

def _pad_digit_windows(digs, nwin_padded):
    if nwin_padded > digs.shape[0]:
        digs = jnp.concatenate(
            [digs, jnp.zeros((nwin_padded - digs.shape[0],) + digs.shape[1:],
                             dtype=digs.dtype)])
    return digs


def _shard_windows_signed(pts, sc, ng, widx, c, nwin, nwin_padded, nloc,
                          nbuckets):
    """One shard's window partial sums, signed-digit path: local recode
    (carry chains stay within whole scalars, so per-shard recode is exact),
    sign-folded bucket accumulation, aggregation. Returns [nloc, 3, 16]."""
    digs = _pad_digit_windows(
        MSM.signed_digit_stream(sc, c, nwin), nwin_padded)  # [nwin_p, n_local]
    local_digs = jax.lax.dynamic_slice_in_dim(
        digs, widx * nloc, nloc, axis=0)

    def one_window(i):
        s = local_digs[i]
        eff = ec.cneg((s < 0) ^ ng, pts)
        return MSM._segmented_bucket_sums(eff, jnp.abs(s), nbuckets)

    bucket_sums = jax.lax.map(one_window, jnp.arange(nloc))
    return MSM._aggregate_buckets(bucket_sums, c)           # [nloc, 3, 16]


def _shard_windows_unsigned(pts, sc, widx, c, nwin, nwin_padded, nloc,
                            nbuckets):
    """One shard's window partial sums, vanilla unsigned digits; windows
    past the real count contribute digit 0 (bucket 0 is dropped by the
    aggregation). Returns [nloc, 3, 16]."""
    def one_window(i):
        w = widx * nloc + i
        d = MSM._digits_traced(sc, w, c)
        d = jnp.where(w < nwin, d, 0)
        return MSM._segmented_bucket_sums(pts, d, nbuckets)

    bucket_sums = jax.lax.map(one_window, jnp.arange(nloc))
    return MSM._aggregate_buckets(bucket_sums, c)


def _shard_fixed_local(tab, sc, ng, widx, c, nwin, nwin_padded, nloc,
                       nbuckets):
    """One shard of the fixed-base phase: window slices taken locally from
    the resident table, bucket sums merged ACROSS the shard's windows (the
    table bases carry 2^{cw}, so one aggregation pass at the end of the
    full reduction is sound). Returns [nbuckets, 3, 16]."""
    digs = _pad_digit_windows(
        MSM.signed_digit_stream(sc, c, nwin), nwin_padded)
    local_digs = jax.lax.dynamic_slice_in_dim(
        digs, widx * nloc, nloc, axis=0)
    local_tab = jax.lax.dynamic_slice_in_dim(
        tab, widx * nloc, nloc, axis=0)       # [nloc, n_local, 3, 16]

    def one_window(args):
        tw, s = args
        eff = ec.cneg((s < 0) ^ ng, tw)
        return MSM._segmented_bucket_sums(eff, jnp.abs(s), nbuckets)

    bucket_sums = jax.lax.map(
        one_window, (local_tab, local_digs))  # [nloc, nb, 3, 16]
    return _fold_points(bucket_sums)          # [nb, 3, 16]


def _build_table_local(pts_local, c, nwin, nwin_padded):
    """One shard of the fixed-base table build: c-doubling chains over the
    shard's own expanded rows (pointwise per row, fully local), padded
    windows filled with infinity. Returns [nwin_padded, n_local, 3, 16]."""
    tab = MSM._build_window_table.__wrapped__(pts_local, c, nwin)
    if nwin_padded > nwin:
        pad = ec.inf_point((nwin_padded - nwin, tab.shape[1]))
        tab = jnp.concatenate([tab, pad.astype(tab.dtype)], axis=0)
    return tab


def _windows_runner(plan: ShardingPlan, c: int, nbits: int, signed: bool):
    """Cached jitted windows-phase program for variable-base MSM."""
    key = (plan.key, "windows", c, nbits, signed)
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn

    nwin = _nwin_for(c, nbits, signed)
    nwin_padded = plan.pad_windows(nwin)
    nbuckets = (1 << (c - 1)) + 1 if signed else 1 << c
    n_win_shards = plan.nwin_shards
    data_axis, win_axis = plan.data_axis, plan.win_axis

    in_specs = [plan.point_spec, plan.scalar_spec]
    if signed:
        in_specs.append(plan.sign_spec)

    @functools.partial(
        shard_map, mesh=plan.mesh,
        in_specs=tuple(in_specs),
        out_specs=P(None, None, None),
        check_vma=False,  # scan carries start as unvarying constants (vma mismatch)
    )
    def windows_phase(pts, sc, *rest):
        widx = jax.lax.axis_index(win_axis)
        nloc = nwin_padded // n_win_shards

        if signed:
            local = _shard_windows_signed(
                pts, sc, rest[0], widx, c, nwin, nwin_padded, nloc, nbuckets)
        else:
            local = _shard_windows_unsigned(
                pts, sc, widx, c, nwin, nwin_padded, nloc, nbuckets)
        # combine partials across the data axis: gather + projective fold
        gathered = jax.lax.all_gather(local, data_axis)     # [ndata, nloc, 3, 16]
        folded = _fold_points(gathered)                     # [nloc, 3, 16]
        # gather window shards: [nwin_shards, nloc, 3, 16] -> flatten
        wins = jax.lax.all_gather(folded, win_axis)
        return wins.reshape(nwin_padded, 3, ec.F.NLIMBS)

    fn = jax.jit(windows_phase)
    if len(_RUNNERS) > 64:
        _RUNNERS.clear()
    _RUNNERS[key] = fn
    return fn


def sharded_msm(points, scalars, c: int, mesh: Mesh, nbits: int = 254,
                signed: bool = False, neg=None,
                plan: ShardingPlan | None = None):
    """MSM over a ("data", "win") mesh.

    points [n, 3, 16] projective Montgomery, scalars [n, L] standard limbs
    (L=16 full scalars, L=8 GLV half-scalar magnitudes with nbits set to
    glv.glv_bits()); n must divide evenly by the data-axis size. Returns a
    replicated [3, 16] projective result.

    The GLV scalar-prep stage happens BEFORE sharding (backend._msm_sharded:
    host decomposition, endomorphism expansion, sign handling), so rows here
    are already aligned (point, scalar[, sign]) triples and the data axis
    shards them uniformly. signed=True runs the signed-digit recode PER
    SHARD (each shard holds whole scalars, so the carry chain never crosses
    a shard boundary) with `neg` [n] bool sign masks folded into the digit
    signs; buckets halve to 2^(c-1)+1."""
    plan = plan or plan_for_mesh(mesh)
    nwin = _nwin_for(c, nbits, signed)
    args = [points, scalars]
    if signed:
        args.append(neg if neg is not None
                    else jnp.zeros(points.shape[0], dtype=bool))
    # any compile fired here is attributed to THIS runner (not lumped
    # into the parent prove phase) — per-entry-point compile telemetry
    with compilelog.entry_point("parallel.sharded_msm"):
        window_sums = _windows_runner(plan, c, nbits, signed)(*args)[:nwin]
        return MSM.combine_windows(window_sums, c)


def shard_points(points, scalars, mesh: Mesh,
                 plan: ShardingPlan | None = None):
    """Place host arrays onto the mesh with data-axis sharding."""
    plan = plan or plan_for_mesh(mesh)
    return (plan.place(points, plan.point_spec),
            plan.place(scalars, plan.scalar_spec))


# ---------------------------------------------------------------------------
# fixed-base mode on the mesh (sharded window tables)
# ---------------------------------------------------------------------------

def _sharded_table_bytes(n_expanded: int, c: int, nbits: int,
                         plan: ShardingPlan) -> int:
    """Exact bytes of the mesh table [nwin_padded, n_expanded, 3, 16] u32
    (n_expanded = endo-expanded, row-padded point count)."""
    nwin_padded = plan.pad_windows(_nwin_for(c, nbits, signed=True))
    return nwin_padded * n_expanded * 3 * 16 * 4


def fixed_fits_mesh(n_expanded: int, c: int, nbits: int,
                    plan: ShardingPlan) -> bool:
    """Per-DEVICE budget check for a mesh-sharded fixed-base table: each
    data shard holds table_bytes/ndata (the win axis replicates its row
    slice), so the SPECTRE_MSM_TABLE_MB budget applies per shard — a mesh
    affords tables `ndata`x larger than one device."""
    return _sharded_table_bytes(n_expanded, c, nbits, plan) // plan.ndata \
        <= MSM._TABLES.budget


def _degrade_fixed_mesh(n_expanded: int, c: int, nbits: int,
                        plan: ShardingPlan) -> bool:
    """Mesh analog of ops.msm._degrade_fixed: fall back to glv+signed when
    even the per-shard table slice busts the budget, recording the same
    `msm_fixed_degraded` health counter + manifest event."""
    if fixed_fits_mesh(n_expanded, c, nbits, plan):
        return False
    from ..utils.health import HEALTH
    HEALTH.incr("msm_fixed_degraded")
    MSM._record_event(
        "msm_fixed_degraded", n=n_expanded, window=c,
        table_mb=_sharded_table_bytes(n_expanded, c, nbits, plan) >> 20,
        budget_mb=MSM._TABLES.budget >> 20, mesh_ndata=plan.ndata)
    return True


def _table_build_runner(plan: ShardingPlan, c: int, nwin: int,
                        nwin_padded: int):
    """Cached SPMD table builder: each data shard runs the c-doubling
    chains over ITS OWN expanded point rows (the chains are pointwise per
    row — fully local, no collectives), so the [nwin, N, 3, 16] table is
    born sharded along the row axis and never transits whole. Padded
    windows hold infinity (their digits are always zero anyway)."""
    key = (plan.key, "tbuild", c, nwin, nwin_padded)
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn

    @functools.partial(
        shard_map, mesh=plan.mesh,
        in_specs=(plan.point_spec,),
        out_specs=plan.table_spec,
        check_vma=False)
    def build(pts_local):
        return _build_table_local(pts_local, c, nwin, nwin_padded)

    fn = jax.jit(build)
    _RUNNERS[key] = fn
    return fn


# resident sharded tables: (base key, shape statics, plan) -> device table.
# Strong host ref pins id()-keyed bases (same contract as ops.msm._TABLES);
# tiny cap — one SRS base per prover is the norm, and each entry is budget-
# sized per device.
_SHARD_TABLES: dict = {}


def sharded_fixed_table(points, c: int, nwin: int, plan: ShardingPlan,
                        base_key=None):
    """[nwin_padded, N, 3, 16] fixed-base window table, built by and
    resident on the mesh (rows sharded along "data", windows whole).

    `points` is the endomorphism-EXPANDED, row-padded base already placed
    with `plan.point_spec` (backend._mesh_base). Unlike the single-device
    `fixed_base_table`, the doubling chains here run over the expanded rows
    directly (phi rows double exactly like P rows) — a one-time build cost
    traded for never shipping the table across hosts."""
    n = points.shape[0]
    nwin_padded = plan.pad_windows(nwin)
    key = (base_key if base_key is not None else ("id", id(points)),
           int(n), int(c), int(nwin_padded), plan.key)
    ref = None if base_key is not None else points
    hit = _SHARD_TABLES.get(key)
    if hit is not None:
        return hit[1]
    with compilelog.entry_point("parallel.sharded_fixed_table"):
        tab = _table_build_runner(plan, c, nwin, nwin_padded)(points)
    if len(_SHARD_TABLES) > 4:
        _SHARD_TABLES.clear()
    _SHARD_TABLES[key] = (ref, tab)
    return tab


def _fixed_runner(plan: ShardingPlan, c: int, nbits: int):
    """Cached jitted fixed-base MSM program over a sharded window table.

    Mirrors ops.msm.msm_fixed_run on the mesh: per-shard signed-digit
    recode, window slices taken locally from the resident table, bucket
    sums MERGED ACROSS WINDOWS before one aggregation pass (sound because
    table bases carry 2^{cw}), then gather+fold over both mesh axes."""
    key = (plan.key, "fixed", c, nbits)
    fn = _RUNNERS.get(key)
    if fn is not None:
        return fn

    nwin = _nwin_for(c, nbits, signed=True)
    nwin_padded = plan.pad_windows(nwin)
    nbuckets = (1 << (c - 1)) + 1
    n_win_shards = plan.nwin_shards
    data_axis, win_axis = plan.data_axis, plan.win_axis

    @functools.partial(
        shard_map, mesh=plan.mesh,
        in_specs=(plan.table_spec, plan.scalar_spec, plan.sign_spec),
        out_specs=P(None, None),
        check_vma=False)
    def fixed_phase(tab, sc, ng):
        widx = jax.lax.axis_index(win_axis)
        nloc = nwin_padded // n_win_shards
        # cross-window merge INSIDE the shard (bases carry 2^{cw}), then
        # across both mesh axes — one aggregation pass total
        merged = _shard_fixed_local(
            tab, sc, ng, widx, c, nwin, nwin_padded, nloc, nbuckets)
        merged = _fold_points(jax.lax.all_gather(merged, data_axis))
        merged = _fold_points(jax.lax.all_gather(merged, win_axis))
        return MSM._aggregate_buckets(merged[None], c)[0]  # [3, 16]

    fn = jax.jit(fixed_phase)
    _RUNNERS[key] = fn
    return fn


def sharded_msm_fixed(table, scalars, neg, c: int, plan: ShardingPlan,
                      nbits: int):
    """Fixed-base MSM against a mesh-resident sharded table. scalars
    [N, 8] GLV half-scalar magnitudes placed per plan.scalar_spec, neg [N]
    signs per plan.sign_spec. Returns a replicated [3, 16] result."""
    with compilelog.entry_point("parallel.sharded_msm_fixed"):
        return _fixed_runner(plan, c, nbits)(table, scalars, neg)

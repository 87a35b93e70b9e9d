"""NTT sharded over a device mesh (four-step/Bailey decomposition).

SURVEY.md §2c/§5: the reference's FFT is rayon shared-memory; the TPU-native
equivalent shards one large NTT across chips with the transpose riding ICI as
an all-to-all — the classic distributed-FFT structure:

    view x as A[jr, jc] = x[jc*Rr + jr]            (Rr x Cc matrix, Rr*Cc = n)
    1. per-row NTT of length Cc with root omega^Rr     (local: rows sharded)
    2. elementwise twiddle A[jr, kc] *= omega^(jr*kc)  (local)
    3. transpose                                        (all_to_all over ICI)
    4. per-row NTT of length Rr with root omega^Cc     (local)

and X[kr*Cc + kc] lands at out[kc, kr] — `sharded_ntt` returns the flat
natural-order result. Identity with the single-device kernel is pinned by
`tests/test_parallel.py::TestShardedNTT` on the virtual 8-device mesh.

Program + twiddle residency (ISSUE 13): the SPMD program is built once per
(plan, logn, omega) and the [Rr, Cc, 16] twiddle matrix is device_put onto
the mesh once and kept resident — the prover hits the same (domain, root)
pair for every polynomial of a proof, and the previous per-call re-jit +
twiddle re-transfer was (with sharded_msm's identical bug) the
MULTICHIP rc=124 root cause: ~40 NTTs per prove, each paying a full 8-way
SPMD retrace/relower on a 1-core host.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P

from ..fields import bn254
from ..observability import compilelog
from ..ops import field_ops as F, ntt as NTT
from .plan import ShardingPlan, plan_for_mesh

R = bn254.R


# Montgomery [Rr, Cc, 16] table of omega^(jr*kc). Shared with the
# single-device four-step kernel and LRU-budgeted there
# (SPECTRE_NTT_TABLE_MB): the prover reuses one omega per domain, but a
# long-running service touching many circuit sizes must stay bounded.
_twiddle_matrix = NTT._twiddle_matrix

# compiled SPMD programs keyed (plan, axis, logn, omega); mesh-resident
# twiddles keyed the same. Stable function objects are the point — see
# module docstring.
_RUNNERS: dict = {}
_TWIDDLES: dict = {}

# runner registry (trace-cache hygiene contract, parallel/plan.py):
# declared builders are cross-checked by analysis/trace_lint
# (TC-UNCACHED-RUNNER) and exercised by its retrace probes.
TRACE_RUNNER_CACHES = (("_ntt_runner", "_RUNNERS"),)


# --- per-shard local compute (no collectives) -------------------------------
# Extracted from the shard_map closure so the kernel linter can trace them
# at tiny shapes without a mesh (analysis/kernel_lint known-root table).

def _rows_local(block, twb, omega_row: int, mode: str,
                kernel: str = "stages"):
    """Steps 1-2 on one shard: length-Cc NTT along each local row, then the
    elementwise twiddle multiply. block/twb: [rows_local, Cc, 16]."""
    y = jax.vmap(
        lambda row: NTT._fwd_kernel.__wrapped__(row, omega_row, None,
                                                mode, kernel))(block)
    return F.mont_mul(F.fr_ctx(), y, twb)


def _cols_local(y, omega_col: int, mode: str, kernel: str = "stages"):
    """Step 4 on one shard: length-Rr NTT along each post-transpose row."""
    return jax.vmap(
        lambda row: NTT._fwd_kernel.__wrapped__(row, omega_col, None,
                                                mode, kernel))(y)


def _ntt_runner(plan: ShardingPlan, axis: str, logn: int, omega: int):
    s = plan.mesh.shape[axis]
    logr = logn // 2
    logc = logn - logr
    # the LOCAL transforms are sqrt(n)-sized; resolve their mode/kernel once
    # at build time and key the cached program on them (the env knobs must
    # not silently go stale inside a resident program)
    row_mode = NTT._resolve_mode(None, logc)
    col_mode = NTT._resolve_mode(None, logr)
    row_kernel = NTT._resolve_kernel(None, row_mode)
    col_kernel = NTT._resolve_kernel(None, col_mode)
    key = (plan.key, axis, logn, omega, row_mode, col_mode,
           row_kernel, col_kernel)
    hit = _RUNNERS.get(key)
    if hit is not None:
        return hit

    rr, cc = 1 << logr, 1 << logc
    assert rr % s == 0 and cc % s == 0, \
        f"shard count {s} must divide both matrix dims {rr}x{cc}"
    omega_row = pow(omega, rr, R)        # length-Cc root (step 1)
    omega_col = pow(omega, cc, R)        # length-Rr root (step 4)
    spec = P(axis, None, None)

    @functools.partial(
        shard_map, mesh=plan.mesh, in_specs=(spec, spec), out_specs=spec,
        check_vma=False)
    def run(block, twb):
        y = _rows_local(block, twb, omega_row, row_mode, row_kernel)
        # step 3: transpose via all-to-all (split columns, gather rows)
        y = jax.lax.all_to_all(y, axis, split_axis=1, concat_axis=0,
                               tiled=True)              # [rr, cc/s, 16]
        y = y.transpose(1, 0, 2)                        # [cc/s, rr, 16]
        return _cols_local(y, omega_col, col_mode, col_kernel)

    fn = jax.jit(run)
    if len(_RUNNERS) > 32:
        _RUNNERS.clear()
    _RUNNERS[key] = fn
    return fn


def _resident_twiddle(plan: ShardingPlan, axis: str, logn: int, omega: int):
    key = (plan.key, axis, logn, omega)
    tw = _TWIDDLES.get(key)
    if tw is None:
        logr = logn // 2
        tw = jax.device_put(
            jnp.asarray(_twiddle_matrix(logr, logn - logr, omega)),
            plan.sharding(P(axis, None, None)))
        if len(_TWIDDLES) > 8:
            _TWIDDLES.clear()
        _TWIDDLES[key] = tw
    return tw


def sharded_ntt(a: jax.Array, omega: int, mesh: Mesh,
                axis: str = "data",
                plan: ShardingPlan | None = None) -> jax.Array:
    """Distributed NTT of a [n, 16] Montgomery limb tensor; returns the same
    natural-order [n, 16] result as `ops.ntt.ntt(a, omega)`.

    n must split as Rr*Cc with the shard count dividing both Rr and Cc."""
    plan = plan or plan_for_mesh(mesh)
    n = a.shape[0]
    logn = n.bit_length() - 1
    assert 1 << logn == n, "n must be a power of two"
    logr = logn // 2
    rr, cc = 1 << logr, 1 << (logn - logr)

    run = _ntt_runner(plan, axis, logn, omega)
    twd = _resident_twiddle(plan, axis, logn, omega)

    # A[jr, jc] = x[jc*rr + jr]
    A = a.reshape(cc, rr, 16).transpose(1, 0, 2)
    Ad = jax.device_put(A, plan.sharding(P(axis, None, None)))
    # compile attribution: a miss here is THIS runner's retrace, not the
    # parent prove phase's (per-entry-point compile telemetry)
    with compilelog.entry_point("parallel.sharded_ntt"):
        out = run(Ad, twd)                               # [cc, rr, 16]
    # out[kc, kr] = X[kr*cc + kc]
    return out.transpose(1, 0, 2).reshape(n, 16)

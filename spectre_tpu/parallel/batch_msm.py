"""Batched MSM with the batch axis sharded over the device mesh (DP axis).

SURVEY.md §2c(b): inter-proof / multi-column batching. One commitment base
(the SRS tau powers), B scalar vectors (advice columns of one proof, or
columns of several proofs); each device computes full Pippenger MSMs for its
slice of the batch — embarrassingly parallel, no collectives beyond the
output gather. Complements `sharded_msm` (intra-MSM TP axis): this one wins
when there are many independent MSMs; that one when a single MSM is huge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..observability import compilelog
from ..ops import msm as MSM


def _batch_mesh(ndev: int | None = None) -> Mesh:
    if ndev is None:
        # the interned plan's 1-D batch mesh: same device subset as the
        # ("data","win") mesh (honors SPECTRE_MESH_SHAPE), stable object so
        # the runner caches below never churn
        from .plan import current_plan
        return current_plan().batch_mesh
    devs = jax.devices()[:ndev]
    return Mesh(devs, ("batch",))


# replicated-base and jitted-SPMD caches: commit_many calls this once per
# chunk with the SAME base — without these every chunk re-broadcasts the
# full SRS to all devices and re-wraps jit (losing its trace cache)
_repl_cache: dict = {}      # (id(points), n, mesh key) -> (strong ref, dev arr)
_runner_cache: dict = {}    # (mesh key, c) -> jitted shard_map program

# runner registry (trace-cache hygiene contract, parallel/plan.py):
# declared builders are cross-checked by analysis/trace_lint
# (TC-UNCACHED-RUNNER) and exercised by its retrace probes.
TRACE_RUNNER_CACHES = (
    ("_runner", "_runner_cache"),
    ("_runner_glv", "_runner_cache"),
)


def _mesh_key(mesh: Mesh) -> tuple:
    return tuple(d.id for d in mesh.devices.flat)


def _replicated_base(points, mesh: Mesh):
    key = (id(points), getattr(points, "shape", (0,))[0], _mesh_key(mesh))
    hit = _repl_cache.get(key)
    if hit is not None and hit[0] is points:
        return hit[1]
    dev = jax.device_put(points, NamedSharding(mesh, P(None, None, None)))
    if len(_repl_cache) > 8:
        _repl_cache.clear()
    _repl_cache[key] = (points, dev)
    return dev


def _runner(mesh: Mesh, c: int):
    key = (_mesh_key(mesh), c)
    fn = _runner_cache.get(key)
    if fn is None:
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, None), P("batch", None, None)),
            out_specs=P("batch", None, None),
            check_vma=False,
        )
        def run(p, sc):
            # lax.map (not vmap): sequential per local batch element keeps
            # HBM traffic flat — the parallelism is the mesh axis
            return jax.lax.map(
                lambda s: MSM.combine_windows.__wrapped__(
                    MSM.msm_windows.__wrapped__(p, s, c), c), sc)

        fn = jax.jit(run)
        _runner_cache[key] = fn
    return fn


def _runner_glv(mesh: Mesh, c: int, nbits: int, signed: bool):
    """GLV-prepped variant: scalars are half-scalar magnitudes riding with a
    per-row sign mask; `signed` picks the signed-digit window kernel (sign
    folded into the digit mask) vs point-level negation + unsigned windows."""
    key = (_mesh_key(mesh), c, nbits, signed)
    fn = _runner_cache.get(key)
    if fn is None:
        @functools.partial(
            shard_map, mesh=mesh,
            in_specs=(P(None, None, None), P("batch", None, None),
                      P("batch", None)),
            out_specs=P("batch", None, None),
            check_vma=False,
        )
        def run(p, sc, ng):
            def one(args):
                s, g = args
                if signed:
                    wins = MSM.msm_windows_signed.__wrapped__(
                        p, s, g, c, nbits)
                else:
                    wins = MSM._msm_windows_impl(
                        MSM._apply_sign.__wrapped__(p, g), s, c, nbits)
                return MSM.combine_windows.__wrapped__(wins, c)

            return jax.lax.map(one, (sc, ng))

        fn = jax.jit(run)
        _runner_cache[key] = fn
    return fn


def batch_msm_dp(points, scalars_batch, c: int | None = None,
                 mesh: Mesh | None = None, neg_batch=None, nbits: int = 254,
                 signed: bool = False):
    """points [n,3,16] projective Montgomery (replicated); scalars_batch
    [B,n,L] standard limbs. Returns [B,3,16] projective results.

    B is padded to a multiple of the mesh size with zero scalar vectors
    (their MSM is the identity; padding is dropped before returning).

    GLV threading (backend.msm_many): pass the endomorphism-EXPANDED base,
    half-scalar magnitudes (L=8), `neg_batch` [B,n] sign masks, and
    nbits=glv.glv_bits(); signed=True routes through the signed-digit
    kernels (halved buckets).

    Window width: explicit `c` wins; otherwise `MSM.default_window`, which
    honors the SPECTRE_MSM_WINDOW override before its tuned table — one env
    knob sweeps every MSM path."""
    n = points.shape[0]
    if c is None:
        c = MSM.default_window(n, signed=signed)
    mesh = mesh or _batch_mesh()
    ndev = mesh.shape["batch"]
    b = scalars_batch.shape[0]
    pad = (-b) % ndev
    if pad:
        scalars_batch = jnp.concatenate(
            [jnp.asarray(scalars_batch),
             jnp.zeros((pad,) + scalars_batch.shape[1:],
                       dtype=scalars_batch.dtype)])
        if neg_batch is not None:
            neg_batch = jnp.concatenate(
                [jnp.asarray(neg_batch),
                 jnp.zeros((pad,) + neg_batch.shape[1:], dtype=bool)])
    sb = jax.device_put(jnp.asarray(scalars_batch),
                        NamedSharding(mesh, P("batch", None, None)))
    pts = _replicated_base(points, mesh)
    # per-entry-point compile attribution (innermost entry wins)
    with compilelog.entry_point("parallel.batch_msm"):
        if neg_batch is None:
            out = _runner(mesh, c)(pts, sb)
        else:
            ngb = jax.device_put(jnp.asarray(neg_batch),
                                 NamedSharding(mesh, P("batch", None)))
            out = _runner_glv(mesh, c, nbits, signed)(pts, sb, ngb)
    return out[:b]

"""Multi-device sharding tests on the virtual CPU mesh.

Tier-1 holds the mesh kernels to their oracles at the smallest shapes that
still cross a shard boundary, on a 2x2 mesh of four of the eight virtual
devices (the layout of PERF.md section 7's row-4 cell): `sharded_msm` and
the signed `batch_msm_dp` against the host curve, `sharded_ntt` against the
one-device kernel, and `TpuBackend.msm` / `msm_many` routed through the mesh
in the MSM modes tier-1 can afford. Each MSM runner compiles an SPMD program
of its own on XLA:CPU, which no one-device kernel's compiled program can
stand in for, and what it costs is the `padd` call sites it holds, not its
rows: so the window is the narrowest that still has a bucket of every weight
(C below), not tests/_shapes.py's, and cases meet in one program where they
can (CHANGES.md, PR 33, has the seconds). Behind RUN_SLOW stays what needs a
fifth such program (the 4x1 mesh, unsigned `glv`, the sharded `fixed` table)
and what costs minutes: whole proves on the mesh (`TestMeshProve`) and
`__graft_entry__.dryrun_multichip`, which no driver runs.
"""

import os
import secrets

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _shapes import MSM_N_OTHER_MODES
from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import ec, limbs as L
from spectre_tpu.parallel import make_mesh, sharded_msm
from spectre_tpu.parallel.sharded_msm import shard_points

pytestmark = pytest.mark.skipif(len(jax.devices()) < 4,
                                reason="needs 4 devices")
needs8 = pytest.mark.skipif(len(jax.devices()) < 8, reason="needs 8 devices")
run_slow = pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                              reason="SPMD compiles beyond tier-1's "
                                     "budget; set RUN_SLOW=1")

# rows of the tier-1 MSM cases: two shards of four on the 2x2 mesh, four of
# two on the 4x1
N = MSM_N_OTHER_MODES
# their window: buckets 1..3 unsigned, 1..2 signed; 127 or 64 windows, padded
# to a multiple of the mesh's window axis
C = 2


def _affine(p):
    return None if p is None else (int(p[0]), int(p[1]))


def _points(n):
    """n small multiples of the generator with an infinity among them (host
    scalar multiplication is the slow part of these tests: keep it short)."""
    pts = [bn.g1_curve.mul(bn.G1_GEN, 3 * k + 2) for k in range(n)]
    pts[n // 2] = None
    return pts


def _scalars(n):
    """n random scalars with 0, 1 and r - 1 among them, the edge rows in
    different shards."""
    scalars = [secrets.randbelow(bn.R) for _ in range(n)]
    scalars[0], scalars[1], scalars[n - 1] = 0, 1, bn.R - 1
    return scalars


class TestShardedMSM:
    """`parallel.sharded_msm` against the host curve (at the parent: behind
    RUN_SLOW, so compared with an oracle in no run the driver makes)."""

    @pytest.mark.parametrize("data_axis", [
        2, pytest.param(4, marks=run_slow)], ids=["2x2", "4x1"])
    def test_matches_oracle(self, data_axis):
        mesh = make_mesh(4, data_axis=data_axis)
        assert dict(mesh.shape) == {"data": data_axis, "win": 4 // data_axis}
        pts, scalars = _points(N), _scalars(N)
        pd, sd = shard_points(ec.encode_points(pts),
                              jnp.asarray(L.ints_to_limbs16(scalars)), mesh)
        got = ec.decode_points(
            sharded_msm(pd, sd, C, mesh)[None])[0]
        assert got == _affine(bn.g1_curve.msm(pts, scalars))


class TestBatchMsmDP:
    """`parallel.batch_msm_dp`, three columns on a four-device batch mesh
    (padded to four), each against the host curve."""

    BATCH = 3

    def _mesh(self):
        from spectre_tpu.parallel.batch_msm import _batch_mesh
        return _batch_mesh(4)

    def test_batch_matches_oracle(self):
        from spectre_tpu.parallel.batch_msm import batch_msm_dp

        pts = _points(N)
        scalars = [_scalars(N) for _ in range(self.BATCH)]
        sc = jnp.stack([jnp.asarray(L.ints_to_limbs16(s)) for s in scalars])
        res = batch_msm_dp(ec.encode_points(pts), sc,
                           c=C, mesh=self._mesh())
        got = ec.decode_points(np.asarray(res))
        for b in range(self.BATCH):
            assert got[b] == _affine(bn.g1_curve.msm(pts, scalars[b])), b

    def test_signed_batch_matches_oracle(self):
        """The signed-digit runner (`_runner_glv`, signed=True) as
        `TpuBackend.msm_many` feeds it: the expanded base, half-scalar
        magnitudes and sign rows. At the parent its one tier-1 run was
        stubbed and checked a counter, never the point."""
        from spectre_tpu.ops import glv, msm as MSM
        from spectre_tpu.parallel.batch_msm import batch_msm_dp

        pts = _points(N)
        scalars = [_scalars(N) for _ in range(self.BATCH)]
        sc = np.zeros((self.BATCH, 2 * N, glv.HALF_LIMBS), np.uint32)
        ng = np.zeros((self.BATCH, 2 * N), bool)
        for b, s in enumerate(scalars):
            a1, a2, n1, n2 = glv.decompose_limbs16(
                np.asarray(L.ints_to_limbs16(s), np.uint32))
            sc[b] = np.concatenate([a1, a2], axis=0)
            ng[b] = np.concatenate([n1, n2], axis=0)
        res = batch_msm_dp(MSM._expand_endo(ec.encode_points(pts)), sc,
                           c=C, mesh=self._mesh(),
                           neg_batch=ng, nbits=glv.glv_bits(), signed=True)
        got = ec.decode_points(np.asarray(res))
        for b in range(self.BATCH):
            assert got[b] == _affine(bn.g1_curve.msm(pts, scalars[b])), b


@needs8
@run_slow
def test_graft_entry_dryrun():
    import sys
    sys.path.insert(0, "/root/repo")
    import __graft_entry__ as ge
    fn, args = ge.entry()
    out = jax.jit(fn)(*args)
    assert out.shape == (3, 16)
    ge.dryrun_multichip(8)


class TestShardedNTT:
    """`parallel.sharded_ntt` on the 2x2 mesh, bytes equal to the one-device
    kernel's: an even log size (a square matrix) and an odd one (rr != cc)."""

    @pytest.mark.parametrize("logn", [6, 7])
    def test_matches_single_device_kernel(self, logn):
        from spectre_tpu.ops import field_ops as F, ntt as NTT
        from spectre_tpu.parallel.sharded_ntt import sharded_ntt
        from spectre_tpu.plonk.domain import Domain

        mesh = make_mesh(4)
        n = 1 << logn
        omega = Domain(logn).omega
        vals = [(i * 2654435761 + 17) % bn.R for i in range(n)]
        a = jnp.asarray(F.fr_ctx().encode_np(vals))
        want = np.asarray(NTT.ntt(a, omega))
        got = np.asarray(sharded_ntt(a, omega, mesh))
        assert np.array_equal(want, got)


class TestShardedMsmRouting:
    @pytest.mark.parametrize("mode", [
        "vanilla", pytest.param("glv", marks=run_slow), "glv+signed",
        pytest.param("fixed", marks=run_slow)])
    def test_backend_routes_large_msm_through_mesh(self, monkeypatch, mode):
        """TpuBackend.msm: >= 2^min_logn points + >1 device -> sharded_msm
        (tiny threshold here; the production default is 2^20). Every MSM
        mode must survive the mesh: the GLV scalar-prep stage runs before
        device_put, signed digits recode per shard, and `fixed` runs
        SHARDED since ISSUE 13 — the window table is built by the mesh
        with rows co-resident with their point shards, and must NOT
        degrade to glv+signed (pinned via the health counter)."""
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.native import host
        from spectre_tpu.utils.health import HEALTH

        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "2x2")
        monkeypatch.setenv("SPECTRE_SHARD_MSM_MIN_LOGN", "2")
        monkeypatch.setenv("SPECTRE_MSM_MODE", mode)
        # the mesh's own table would give these few points a window of 10
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", str(C))
        bk = B.TpuBackend()
        # not divisible by the data axis: padded to N rows, where `vanilla`
        # meets TestShardedMSM's 2x2 program
        n = N - 1
        assert bk._use_mesh(n, bk._shard_min_logn)
        pts, scs = _points(n), _scalars(n)
        degraded_before = HEALTH.get("msm_fixed_degraded")
        got = bk.msm(host.points_to_limbs(pts), host.ints_to_limbs(scs))
        assert got == _affine(bn.g1_curve.msm(pts, scs))
        if mode == "fixed":
            # the whole point of the sharded table: fixed stays fixed
            assert HEALTH.get("msm_fixed_degraded") == degraded_before


class TestBatchMsmManyOnMesh:
    @pytest.mark.parametrize("mode", [
        "vanilla", pytest.param("glv", marks=run_slow), "glv+signed",
        "fixed"])
    def test_msm_many_matches_oracle(self, monkeypatch, mode):
        """TpuBackend.msm_many on the >1-device batch DP path, the GLV
        modes with their scalar-prep stage threaded through (half-scalar +
        sign-mask batch rows against one replicated endomorphism-expanded
        base; `fixed` runs the glv+signed kernels here). At
        TestBatchMsmDP's shapes, so `vanilla` and the signed modes meet its
        two programs; unsigned `glv` needs one of its own."""
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.native import host

        monkeypatch.setenv("SPECTRE_MESH_SHAPE", "2x2")
        monkeypatch.setenv("SPECTRE_MSM_MODE", mode)
        monkeypatch.setenv("SPECTRE_MSM_WINDOW", str(C))
        pts = _points(N)
        scs = [_scalars(N) for _ in range(TestBatchMsmDP.BATCH)]
        got = B.TpuBackend().msm_many(host.points_to_limbs(pts),
                                      [host.ints_to_limbs(sc) for sc in scs])
        for b, sc in enumerate(scs):
            assert got[b] == _affine(bn.g1_curve.msm(pts, sc)), b


@needs8
@run_slow
class TestMeshProve:
    """A COMPLETE prove rides the mesh (sharded MSM + sharded NTT through
    the TpuBackend gates) and is byte-identical to the host prove — the
    difference between 'three kernels shard' and 'the prover is multi-chip'
    (SURVEY §2c(a)). Same k as dryrun_multichip phase 4 (shared compile
    cache)."""

    _fixture = None
    _host_proofs: dict = {}

    @classmethod
    def _get_fixture(cls):
        if cls._fixture is None:
            from spectre_tpu.test_utils import mesh_prove_fixture
            cls._fixture = mesh_prove_fixture(k=13)
        return cls._fixture

    @classmethod
    def _host_proof(cls, ntt_mode):
        # one CPU reference prove per NTT mode (the identity matrix below
        # re-proves on every mesh shape against the SAME reference bytes)
        if ntt_mode not in cls._host_proofs:
            from spectre_tpu.plonk import backend as B
            from spectre_tpu.plonk.prover import prove
            from spectre_tpu.test_utils import seeded_blinding_rng
            srs, pk, asg = cls._get_fixture()
            cls._host_proofs[ntt_mode] = prove(
                pk, srs, asg, B.CpuBackend(),
                blinding_rng=seeded_blinding_rng())
        return cls._host_proofs[ntt_mode]

    def test_full_prove_byte_equality_on_mesh(self, monkeypatch):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.prover import prove
        from spectre_tpu.plonk.verifier import verify
        from spectre_tpu.test_utils import seeded_blinding_rng

        monkeypatch.setenv("SPECTRE_SHARD_MSM_MIN_LOGN", "10")
        monkeypatch.setenv("SPECTRE_SHARD_NTT_MIN_LOGN", "10")
        srs, pk, asg = self._get_fixture()
        p_host = self._host_proof("default")
        tbk = B.TpuBackend()
        assert tbk._use_mesh(1 << 13, tbk._shard_ntt_min_logn)
        p_mesh = prove(pk, srs, asg, tbk,
                       blinding_rng=seeded_blinding_rng())
        assert p_mesh == p_host
        inst = [asg.instances[0]] if asg.instances else [[]]
        assert verify(pk.vk, srs, inst, p_mesh)

    @pytest.mark.parametrize("mesh_shape", ["1x1", "2x1", "4x2"])
    @pytest.mark.parametrize("msm_mode", ["glv+signed", "fixed"])
    @pytest.mark.parametrize("ntt_mode", ["radix2", "fourstep"])
    def test_identity_matrix(self, monkeypatch, mesh_shape, msm_mode,
                             ntt_mode):
        """ISSUE 13 acceptance: proof bytes byte-identical across
        1/2/8-device meshes for every MSM/NTT mode combo, with `fixed`
        running SHARDED (the health counter pins no silent degrade).
        1x1 means a one-device plan — the mesh gates disengage and the
        plain single-device kernels prove, which IS the single-device arm
        of the identity."""
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.prover import prove
        from spectre_tpu.test_utils import seeded_blinding_rng
        from spectre_tpu.utils.health import HEALTH

        monkeypatch.setenv("SPECTRE_SHARD_MSM_MIN_LOGN", "10")
        monkeypatch.setenv("SPECTRE_SHARD_NTT_MIN_LOGN", "10")
        monkeypatch.setenv("SPECTRE_MESH_SHAPE", mesh_shape)
        monkeypatch.setenv("SPECTRE_MSM_MODE", msm_mode)
        monkeypatch.setenv("SPECTRE_NTT_MODE", ntt_mode)
        srs, pk, asg = self._get_fixture()
        p_host = self._host_proof(ntt_mode)
        degraded_before = HEALTH.get("msm_fixed_degraded")
        p_mesh = prove(pk, srs, asg, B.TpuBackend(),
                       blinding_rng=seeded_blinding_rng())
        assert p_mesh == p_host, \
            f"proof bytes diverge on {mesh_shape} / {msm_mode} / {ntt_mode}"
        if msm_mode == "fixed":
            assert HEALTH.get("msm_fixed_degraded") == degraded_before, \
                "fixed mode silently degraded on the mesh"

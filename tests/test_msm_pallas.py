"""Pallas MSM kernel math: the in-kernel field/EC functions are pure jnp on
limb-row lists, so they are testable WITHOUT pallas_call (Mosaic needs real
TPU). Everything goes through jit — eager execution of the ~30k-op unrolled
kernels costs minutes per call. ONE small-shape test runs the actual
pallas_call in interpret mode (seconds-scale compile, the off-TPU dispatch
SPECTRE_MSM_IMPL=pallas rides) — see TestInterpretMode.

Oracle: ops/ec (already property-tested against the host curve). The full
SoA MSM parity run is RUN_SLOW (several compile shapes); device execution of
the actual pallas_call happens via bench.py on TPU."""

import os

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import ec, field_ops as F
from spectre_tpu.ops import msm_pallas as MP


def _pts(n, seed=3):
    g = bn.g1_curve
    return [g.mul(bn.G1_GEN, seed * k + 1) for k in range(n)]


_jit_padd = jax.jit(MP._k_padd)
_jit_mont_mul = jax.jit(MP._k_mont_mul)
_jit_add = jax.jit(MP._k_add)
_jit_sub = jax.jit(MP._k_sub)


@pytest.fixture(scope="module")
def batch():
    n = 8
    aos = ec.encode_points(_pts(2 * n))
    return aos[:n], aos[n:]


class TestLayout:
    def test_soa_roundtrip(self, batch):
        a, _ = batch
        back = MP.from_soa(MP.to_soa(a))
        assert np.array_equal(np.asarray(back), np.asarray(a))

    def test_inf_soa_matches_ec(self):
        want = np.asarray(ec.inf_point((4,)))
        got = np.asarray(MP.from_soa(MP.inf_soa(4)))
        assert np.array_equal(got, want)


class TestKernelMath:
    """_k_* functions on jnp rows vs the tested AoS ops."""

    def test_mont_mul(self, batch):
        a, b = batch
        ctx = F.fq_ctx()
        got = _jit_mont_mul(MP.to_soa(a)[:MP.NL], MP.to_soa(b)[:MP.NL])
        want = np.asarray(jnp.transpose(
            F.mont_mul(ctx, a[:, 0], b[:, 0]), (1, 0)))
        assert np.array_equal(np.asarray(got), want)

    def test_add_sub(self, batch):
        a, b = batch
        ctx = F.fq_ctx()
        x, y = MP.to_soa(a)[:MP.NL], MP.to_soa(b)[:MP.NL]
        want_add = np.asarray(jnp.transpose(F.add(ctx, a[:, 0], b[:, 0]), (1, 0)))
        want_sub = np.asarray(jnp.transpose(F.sub(ctx, a[:, 0], b[:, 0]), (1, 0)))
        assert np.array_equal(np.asarray(_jit_add(x, y)), want_add)
        assert np.array_equal(np.asarray(_jit_sub(x, y)), want_sub)

    def test_sub_zero_normalizes(self, batch):
        """p - 0 must normalize to 0-lane behavior (cond-sub path): a - 0 == a."""
        a, _ = batch
        x = MP.to_soa(a)[:MP.NL]
        zero = jnp.zeros_like(x)
        got = _jit_sub(x, zero)
        assert np.array_equal(np.asarray(got), np.asarray(x))

    def test_padd_vs_ec(self, batch):
        a, b = batch
        got = _jit_padd(MP.to_soa(a), MP.to_soa(b))
        want = np.asarray(MP.to_soa(ec.padd(a, b)))
        assert np.array_equal(np.asarray(got), want)

    def test_padd_doubling_and_infinity(self, batch):
        a, _ = batch
        inf = ec.inf_point((a.shape[0],))
        got = _jit_padd(MP.to_soa(a), MP.to_soa(a))
        want = np.asarray(MP.to_soa(ec.padd(a, a)))
        assert np.array_equal(np.asarray(got), want)
        got2 = MP.from_soa(_jit_padd(MP.to_soa(a), MP.to_soa(inf)))
        assert ec.decode_points(got2) == ec.decode_points(a)


class TestLegalBlock:
    def test_lane_multiple_dividing_pad(self):
        # largest multiple of LANE that divides n_pad, capped at `want`
        assert MP._legal_block(128, 2048) == 128
        assert MP._legal_block(256, 2048) == 256
        assert MP._legal_block(384, 256) == 128     # 256 doesn't divide 384
        assert MP._legal_block(4096, 2048) == 2048
        assert MP._legal_block(4096, 100) == 128    # floor is one lane tile
        for n_pad in (128, 384, 1152, 4096):
            b = MP._legal_block(n_pad, 2048)
            assert b % MP.LANE == 0 and n_pad % b == 0


class TestInterpretMode:
    """The REAL pallas_call in interpret mode (auto-selected off-TPU): one
    small shape — the kernel body is already covered by TestKernelMath;
    this pins the pallas_call plumbing (BlockSpecs, grid, the in-trace
    modulus column) against the same ec.padd oracle."""

    def test_interpret_dispatch_off_tpu(self):
        assert MP._interpret() is (jax.default_backend() != "tpu")

    def test_padd_soa_matches_ec(self, batch):
        a, b = batch
        got = MP.from_soa(MP.padd_soa(MP.to_soa(a), MP.to_soa(b)))
        assert np.array_equal(np.asarray(got), np.asarray(ec.padd(a, b)))

    def test_padd_soa_pads_partial_lane_batch(self, batch):
        # n=8 < LANE exercises the pad-to-128 + slice-back path
        a, b = batch
        out = MP.padd_soa(MP.to_soa(a), MP.to_soa(b))
        assert out.shape == (MP.ROWS, a.shape[0])


class TestBucketKernel:
    """The VMEM-resident bucket accumulation (this PR): the pure jnp body
    `_k_bucket_accumulate` is testable without pallas_call, same pattern as
    TestKernelMath; one small-shape test runs the REAL pallas_call pipeline
    in interpret mode."""

    def test_cneg_matches_ec(self, batch):
        a, _ = batch
        soa = MP.to_soa(a)
        mask = jnp.asarray([[True, False] * (a.shape[0] // 2)])
        got = jax.jit(MP._k_cneg)(mask, soa)
        want = MP.to_soa(ec.cneg(mask[0], a))
        assert np.array_equal(np.asarray(got), np.asarray(want))

    def test_cneg_keeps_infinity_at_infinity(self):
        # -(0:1:0) = (0:-1:0): a different representative of the SAME
        # point (Z = 0) — the complete padd treats both as identity
        inf = MP.inf_soa(4)
        got = jax.jit(MP._k_cneg)(jnp.ones((1, 4), bool), inf)
        assert ec.decode_points(MP.from_soa(got)) == [None] * 4
        assert np.array_equal(np.asarray(got[:MP.NL]),
                              np.asarray(inf[:MP.NL]))       # x untouched
        assert np.array_equal(np.asarray(got[2 * MP.NL:]),
                              np.asarray(inf[2 * MP.NL:]))   # z untouched

    @pytest.mark.slow
    def test_accumulate_matches_manual_buckets(self, batch):
        """One window, signed digits + GLV signs: the kernel body's bucket
        array must equal per-bucket ec sums of the (conditionally negated)
        points. slow marker: the nested fori_loop body costs a ~40s
        XLA-CPU compile; `make test-slow` (no marker filter) runs it."""
        a, _ = batch
        n = a.shape[0]
        nb = 4
        digs = jnp.asarray([[1, -2, 0, 2, 4, -1, 2, 3][:n]], jnp.int32)
        negs = jnp.asarray([[0, 1, 0, 0, 1, 0, 0, 1][:n]], jnp.uint32)
        buckets = jnp.broadcast_to(MP.inf_soa(1)[:, :1][None],
                                   (1, MP.ROWS, nb))
        got = jax.jit(MP._k_bucket_accumulate)(
            MP.to_soa(a)[None], digs, negs, buckets)
        eff = ec.cneg(np.asarray(
            (np.asarray(digs)[0] < 0) ^ (np.asarray(negs)[0] != 0)), a)
        for j in range(nb):
            want = ec.inf_point(())
            for i in range(n):
                if abs(int(digs[0, i])) == j + 1:
                    want = ec.padd(eff[i], want)
            assert ec.decode_points(
                MP.from_soa(got[0])[j][None]) == ec.decode_points(
                    jnp.asarray(want)[None])

    @pytest.mark.skipif(not os.environ.get("RUN_SLOW"),
                        reason="85 eager window aggregations (RUN_SLOW=1); "
                               "tier-1 parity lives in test_msm_modes")
    def test_bucket_pipeline_matches_host_msm(self):
        """The REAL pallas_call bucket pipeline (interpret mode) end to
        end: msm_soa (signed recode, VMEM-resident buckets, weighted
        aggregation) vs the host curve."""
        n = 8
        pts = _pts(n, seed=5)
        scalars = [(7919 * k + 13) % bn.R for k in range(n)]
        from spectre_tpu.ops import limbs as L
        soa = MP.to_soa(ec.encode_points(pts))
        sc = jnp.asarray(L.ints_to_limbs16(scalars))
        res = MP.msm_soa(soa, sc, c=3)
        got = ec.decode_points(jnp.asarray(res)[None])[0]
        want = bn.g1_curve.msm(pts, scalars)
        assert (int(got[0]), int(got[1])) == (int(want[0]), int(want[1]))

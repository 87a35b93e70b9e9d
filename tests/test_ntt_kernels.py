"""MXU-native NTT kernel (SPECTRE_NTT_KERNEL) and the fused quotient
vanishing-inverse (SPECTRE_QUOTIENT_FUSED_VINV).

The contract mirrors the NTT-mode suite: the DFT-matmul short-transform
body is the SAME transform as the butterfly stages in a different work
shape — byte-identical outputs, byte-identical proofs. The fused
vanishing-inverse likewise: same mont_mul, one fewer full-width pass, the
pass count pinned STRUCTURALLY (an op-count assertion, not a timing)."""

import random

import numpy as np
import pytest

import jax.numpy as jnp

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.ops import field_ops as F, limbs as L, ntt as NTT

R = bn.R

# (mode, kernel): the kernel knob only has effect inside fourstep's short
# row/column transforms; radix2 ignores it (resolved to "stages")
VARIANTS = [("radix2", "stages"), ("fourstep", "stages"),
            ("fourstep", "matmul")]


def _poly(n, seed=23):
    return [(i * 2654435761 + seed) % R for i in range(n)]


def _mont(vals):
    return jnp.asarray(F.fr_ctx().encode_np(vals))


class TestKernelByteIdentity:
    """{radix2, fourstep x stages, fourstep x matmul} x {ntt, intt,
    coset_lde_std}: identical BYTES, not merely equal values."""

    @pytest.mark.parametrize("k", [6, 10, 12])
    def test_ntt_bytes(self, k):
        omega = bn.fr_root_of_unity(k)
        a = _mont(_poly(1 << k))
        outs = [np.asarray(NTT.ntt(a, omega, mode=m, kernel=kn))
                for m, kn in VARIANTS]
        for got, (m, kn) in zip(outs[1:], VARIANTS[1:]):
            assert np.array_equal(outs[0], got), (k, m, kn)

    @pytest.mark.parametrize("k", [6, 10, 12])
    def test_intt_bytes(self, k):
        omega = bn.fr_root_of_unity(k)
        a = _mont(_poly(1 << k, seed=5))
        outs = [np.asarray(NTT.intt(a, omega, mode=m, kernel=kn))
                for m, kn in VARIANTS]
        for got, (m, kn) in zip(outs[1:], VARIANTS[1:]):
            assert np.array_equal(outs[0], got), (k, m, kn)

    @pytest.mark.parametrize("k", [6, 10, 12])
    def test_coset_lde_std_bytes(self, k):
        omega = bn.fr_root_of_unity(k)
        a_std = jnp.asarray(L.ints_to_limbs16(_poly(1 << k, seed=9)))
        outs = [np.asarray(NTT.coset_lde_std(a_std, omega, 7, mode=m,
                                             kernel=kn))
                for m, kn in VARIANTS]
        for got, (m, kn) in zip(outs[1:], VARIANTS[1:]):
            assert np.array_equal(outs[0], got), (k, m, kn)

    def test_matmul_matches_host_oracle(self):
        from spectre_tpu.native import host
        k = 6
        omega = bn.fr_root_of_unity(k)
        vals = _poly(1 << k, seed=31)
        want = host.limbs_to_ints(
            host.fr_ntt(np.array(host.ints_to_limbs(vals)), omega))
        res = NTT.ntt(_mont(vals), omega, mode="fourstep", kernel="matmul")
        assert F.fr_ctx().decode(res) == want


class TestKernelDispatch:
    def test_env_kernel_dispatch(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_NTT_KERNEL", "matmul")
        assert NTT.ntt_kernel() == "matmul"
        monkeypatch.setenv("SPECTRE_NTT_KERNEL", "bogus")
        with pytest.raises(ValueError):
            NTT.ntt_kernel()

    def test_radix2_ignores_kernel_knob(self):
        # the kernel names fourstep's short-transform body; radix2 resolves
        # to "stages" so trace-cache keys stay stable under the env knob
        assert NTT._resolve_kernel("matmul", "radix2") == "stages"
        assert NTT._resolve_kernel("matmul", "fourstep") == "matmul"
        assert NTT._resolve_kernel(None, "fourstep") == NTT.ntt_kernel()

    def test_length_cap_falls_back_to_stages(self, monkeypatch):
        # beyond _MATMUL_MAX_LOGN the exactness bound (int32 columns,
        # single-REDC u < 2p) no longer holds: _short_transform must route
        # to the butterfly stages, never the matmul body. Routing is the
        # whole contract here — the fallback IS _ntt_stages, whose output
        # the byte-identity matrix already pins — so assert the call
        # pattern, not (tautological) output bytes at the big length.
        calls = []
        orig = NTT._ntt_dft_matmul
        monkeypatch.setattr(
            NTT, "_ntt_dft_matmul",
            lambda a, logn, omega: calls.append(logn) or orig(a, logn, omega))
        small = _mont(_poly(1 << 4, seed=3))
        out = NTT._short_transform(small, 4, bn.fr_root_of_unity(4), "matmul")
        assert calls == [4]
        assert np.array_equal(
            np.asarray(out),
            np.asarray(NTT._ntt_stages(small, 4, bn.fr_root_of_unity(4))))
        # over the cap: stages must be chosen — recorder stands in for the
        # (expensive) transform so the routing check is compute-free
        stage_calls = []
        monkeypatch.setattr(
            NTT, "_ntt_stages",
            lambda x, logn, omega, scale=None:
                stage_calls.append(logn) or x)
        logn = NTT._MATMUL_MAX_LOGN + 1
        a = _mont(_poly(1 << logn, seed=3))
        back = NTT._short_transform(a, logn, bn.fr_root_of_unity(logn),
                                    "matmul")
        assert back is a and stage_calls == [logn]
        assert calls == [4]  # unchanged: the matmul body was never entered


def _tiny_circuit():
    """The tiny gate+lookup circuit at the shape every prove test shares
    (tests/_shapes.py)."""
    from _shapes import tiny_circuit, tiny_config
    from spectre_tpu.plonk.constraint_system import Assignment

    cfg = tiny_config()
    advice, lookup, fixed, selectors, copies, out = tiny_circuit(cfg)
    asg = Assignment(cfg, advice, lookup, fixed, selectors, [[out]], copies)
    return cfg, asg, fixed, selectors, copies, [[out]]


def _seeded():
    r = random.Random(0x177E57)
    return lambda: r.randrange(R)


class TestKernelProofBytes:
    """The kernel-knob + fused-vinv correctness gate, mirroring
    TestNttModeProofBytes: stages and matmul must yield BYTE-IDENTICAL
    proofs through the device backend under seeded blinding, and switching
    off SPECTRE_QUOTIENT_FUSED_VINV must change the mul-pass COUNT (by
    exactly one) but never a proof byte. One shared pk: keygen/prove NTT
    equality across kernels is already pinned value-level by the
    byte-identity matrix above, so the expensive keygen runs once.

    slow-marked: ~4 min of prove wall-clock on the 1-core box — runs in
    `make test-slow` (no marker filter), stays out of the 870s tier-1 window
    like test_integrity's heavy drills."""

    @pytest.mark.slow
    def test_proof_bytes_across_kernels_and_fused_vinv(self, monkeypatch):
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk import quotient_device as QD
        from spectre_tpu.plonk.keygen import keygen
        from spectre_tpu.plonk.prover import prove
        from spectre_tpu.plonk.srs import SRS
        from spectre_tpu.plonk.verifier import verify

        cfg, asg, fixed, selectors, copies, instance = _tiny_circuit()
        srs = SRS.unsafe_setup(cfg.k)
        bk = B.get_backend("tpu")

        counts = {"mul": 0}
        orig_helpers = QD._helpers

        def counting_helpers():
            h = dict(orig_helpers())
            real = h["mul"]

            def mul(a, b):
                counts["mul"] += 1
                return real(a, b)

            h["mul"] = mul
            return h

        monkeypatch.setattr(QD, "_helpers", counting_helpers)
        # the explicit path's lazy vinv tensor must rebuild per run, not
        # leak between the two env settings
        monkeypatch.setattr(QD, "_static_cache", {})

        monkeypatch.setenv("SPECTRE_NTT_MODE", "fourstep")
        pk = keygen(srs, cfg, fixed, selectors, copies, bk)
        proofs, muls = {}, {}
        for kern, fused in (("stages", "1"), ("matmul", "1"),
                            ("stages", "0")):
            monkeypatch.setenv("SPECTRE_NTT_KERNEL", kern)
            monkeypatch.setenv("SPECTRE_QUOTIENT_FUSED_VINV", fused)
            counts["mul"] = 0
            proofs[kern, fused] = prove(pk, srs, asg, bk,
                                        blinding_rng=_seeded())
            muls[kern, fused] = counts["mul"]
            assert verify(pk.vk, srs, instance, proofs[kern, fused]), \
                (kern, fused)
        assert proofs["stages", "1"] == proofs["matmul", "1"], \
            "SPECTRE_NTT_KERNEL changed proof bytes (kernels must be " \
            "identical)"
        assert proofs["stages", "1"] == proofs["stages", "0"], \
            "fused vanishing-inverse changed proof bytes"
        # the structural pin: folding the inverse into the iNTT's stage-0
        # table removes EXACTLY ONE full-width elementwise mont_mul
        # dispatch per quotient
        assert muls["stages", "0"] == muls["stages", "1"] + 1, muls


class TestFusedVinvQuotient:
    """SPECTRE_QUOTIENT_FUSED_VINV: the vanishing-inverse folded into
    stage 0 of the inverse coset NTT vs the explicit [4n, 16] pre-multiply,
    checked at the kernel level (the proof-level gate rides
    TestKernelProofBytes)."""

    def test_vinv_table_matches_explicit(self):
        from spectre_tpu.plonk.domain import COSET_GEN, Domain
        dom = Domain(4)
        vals = dom.vanishing_inv_period_vals()
        # the period tuple IS the extended-domain inverse, tiled
        from spectre_tpu.plonk import backend as B
        want = dom.vanishing_inv_on_extended()
        tiled = [vals[i % len(vals)] for i in range(dom.n_ext)]
        assert np.array_equal(B.to_arr(tiled), want)
        # fused entry == explicit multiply-then-transform, byte-for-byte
        a = _mont(_poly(dom.n_ext, seed=41))
        vtab = jnp.asarray(F.fr_ctx().encode(
            [vals[i % len(vals)] for i in range(dom.n_ext)]))
        explicit = NTT.coset_intt_std(
            F.mont_mul(F.fr_ctx(), a, vtab), dom.omega_ext, COSET_GEN)
        fused = NTT.coset_intt_std_vinv(a, dom.omega_ext, COSET_GEN, vals)
        assert np.array_equal(np.asarray(explicit), np.asarray(fused))

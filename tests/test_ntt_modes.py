"""NTT pipeline modes (ISSUE 4): radix2 vs fourstep vs host oracle, batched
vs per-column loops, fused coset-LDE vs scale-then-NTT, the budgeted
twiddle-table LRU, and the proof-byte gate.

The contract every mode must honor (mirroring the MSM modes): identical
bytes out — radix2 and fourstep are the SAME transform in a different work
shape, and the batched kernels are the per-column kernels on a stack."""

import numpy as np
import pytest

import jax.numpy as jnp

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.native import host
from spectre_tpu.ops import field_ops as F, limbs as L, ntt as NTT

R = bn.R


def _poly(n, seed=17):
    return [(i * 2654435761 + seed) % R for i in range(n)]


def _mont(vals):
    return jnp.asarray(F.fr_ctx().encode_np(vals))


class TestModeEquality:
    @pytest.mark.parametrize("k", [2, 3, 5, 7, 9])
    def test_modes_match_host_oracle(self, k):
        omega = bn.fr_root_of_unity(k)
        vals = _poly(1 << k)
        want = host.limbs_to_ints(
            host.fr_ntt(np.array(host.ints_to_limbs(vals)), omega))
        a = _mont(vals)
        ctx = F.fr_ctx()
        out = {}
        for mode in NTT.NTT_MODES:
            res = NTT.ntt(a, omega, mode=mode)
            assert ctx.decode(res) == want, (mode, k)
            out[mode] = np.asarray(res)
        # byte-identical across modes, not merely value-equal
        assert np.array_equal(out["radix2"], out["fourstep"]), k

    def test_env_mode_dispatch(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_NTT_MODE", "fourstep")
        assert NTT.ntt_mode() == "fourstep"
        monkeypatch.setenv("SPECTRE_NTT_MODE", "bogus")
        with pytest.raises(ValueError):
            NTT.ntt_mode()

    def test_tiny_sizes_fall_back_to_radix2(self):
        # logn < 2 has no row/column split; fourstep must still answer
        omega = bn.fr_root_of_unity(1)
        a = _mont(_poly(2))
        assert np.array_equal(np.asarray(NTT.ntt(a, omega, mode="fourstep")),
                              np.asarray(NTT.ntt(a, omega, mode="radix2")))

    @pytest.mark.parametrize("mode", NTT.NTT_MODES)
    def test_intt_roundtrip(self, mode):
        k = 6
        omega = bn.fr_root_of_unity(k)
        vals = _poly(1 << k)
        a = _mont(vals)
        back = NTT.intt(NTT.ntt(a, omega, mode=mode), omega, mode=mode)
        assert F.fr_ctx().decode(back) == vals


class TestBatched:
    @pytest.mark.parametrize("mode", NTT.NTT_MODES)
    def test_ntt_many_matches_loop(self, mode):
        k = 5
        omega = bn.fr_root_of_unity(k)
        cols = [_poly(1 << k, seed=s) for s in (1, 2, 3)]
        stack = jnp.stack([_mont(c) for c in cols])
        many = np.asarray(NTT.ntt_many(stack, omega, mode=mode))
        for i, c in enumerate(cols):
            assert np.array_equal(
                many[i], np.asarray(NTT.ntt(_mont(c), omega, mode=mode))), i

    def test_intt_many_matches_loop(self):
        k = 5
        omega = bn.fr_root_of_unity(k)
        cols = [_poly(1 << k, seed=s) for s in (4, 5)]
        stack = jnp.stack([_mont(c) for c in cols])
        many = np.asarray(NTT.intt_many(stack, omega))
        for i, c in enumerate(cols):
            assert np.array_equal(many[i],
                                  np.asarray(NTT.intt(_mont(c), omega))), i

    def test_backend_ntt_many_matches_singles(self):
        from spectre_tpu.plonk import backend as B
        bk = B.get_backend("tpu")
        n = 1 << 5
        omega = bn.fr_root_of_unity(5)
        arrs = [B.to_arr(_poly(n, seed=s)) for s in (7, 8, 9)]
        many = bk.ntt_many(arrs, omega)
        inv_many = bk.intt_many(arrs, omega)
        for a, m, im in zip(arrs, many, inv_many):
            assert np.array_equal(m, bk.ntt(a, omega))
            assert np.array_equal(im, bk.intt(a, omega))
        # CPU backend agrees (the native oracle)
        cpu = B.get_backend("cpu")
        for a, m in zip(arrs, many):
            assert np.array_equal(m, cpu.ntt(a, omega))


class TestFusedCosetLde:
    @pytest.mark.parametrize("mode", NTT.NTT_MODES)
    def test_fused_equals_scale_then_ntt(self, mode):
        k, g = 6, 7
        omega = bn.fr_root_of_unity(k)
        a = _mont(_poly(1 << k))
        fused = np.asarray(NTT.coset_ntt(a, omega, g, mode=mode))
        unfused = np.asarray(
            NTT.ntt(NTT.coset_scale(a, g), omega, mode=mode))
        assert np.array_equal(fused, unfused)

    @pytest.mark.parametrize("mode", NTT.NTT_MODES)
    def test_std_boundary_fusions(self, mode):
        """coset_lde_std folds std→mont + scale into stage 0;
        coset_intt_std folds 1/n + g^{-i} + mont→std into one table."""
        k, g = 5, 7
        omega = bn.fr_root_of_unity(k)
        vals = _poly(1 << k)
        a_std = jnp.asarray(L.ints_to_limbs16(vals))
        fwd = NTT.coset_lde_std(a_std, omega, g, mode=mode)
        assert np.array_equal(
            np.asarray(fwd),
            np.asarray(NTT.coset_ntt(_mont(vals), omega, g, mode=mode)))
        back = NTT.coset_intt_std(fwd, omega, g, mode=mode)
        assert L.limbs16_to_ints(np.asarray(back)) == vals

    def test_inverse_roundtrip_batched(self):
        k, g = 5, 7
        omega = bn.fr_root_of_unity(k)
        cols = [_poly(1 << k, seed=s) for s in (11, 12)]
        stack = jnp.stack([_mont(c) for c in cols])
        ext = NTT.coset_ntt_many(stack, omega, g)
        back = NTT.coset_intt_many(ext, omega, g)
        ctx = F.fr_ctx()
        for i, c in enumerate(cols):
            assert ctx.decode(back[i]) == c

    def test_backend_coset_lde_many_matches_domain(self):
        """The device batched fused path reproduces the host
        coeff_to_extended (the quotient's correctness anchor)."""
        from spectre_tpu.plonk import backend as B
        from spectre_tpu.plonk.domain import Domain
        dom = Domain(5)
        cpu, tpu = B.get_backend("cpu"), B.get_backend("tpu")
        coeffs = [B.to_arr(_poly(dom.n, seed=s)) for s in (3, 4, 5)]
        want = [dom.coeff_to_extended(c, cpu) for c in coeffs]
        got = dom.coset_lde_many(coeffs, tpu)
        for w, g_ in zip(want, got):
            assert np.array_equal(w, g_)


class TestTwiddleTableLRU:
    def test_budget_eviction_and_recompute(self, monkeypatch):
        lru = NTT._TableLRU(1 << 20, label="test ntt table",
                            budget_var="SPECTRE_NTT_TABLE_MB")
        monkeypatch.setattr(NTT, "_TABLES", lru)
        omega = bn.fr_root_of_unity(12)
        t1 = NTT._stage_twiddles(12, omega)          # ~512KB of stages
        b0 = lru.builds
        assert NTT._stage_twiddles(12, omega) is t1  # hit
        assert lru.hits >= 1 and lru.builds == b0
        # a second table family under a 1MB budget forces eviction
        NTT._power_table(13, 7)                      # 512KB
        NTT._power_table(13, 5)                      # 512KB -> evicts
        assert lru.evictions >= 1
        # evicted entries recompute correctly (budget costs time, never
        # correctness)
        t1b = NTT._stage_twiddles(12, omega)
        assert all(np.array_equal(x, y) for x, y in zip(t1, t1b))

    def test_oversize_table_passes_through_uncached(self, monkeypatch):
        lru = NTT._TableLRU(1024, label="tiny", budget_var="X")
        monkeypatch.setattr(NTT, "_TABLES", lru)
        tab = NTT._power_table(10, 7)                # 64KB > 1KB budget
        assert tab.shape == (1 << 10, 16)
        assert lru._bytes == 0                       # nothing retained
        b0 = lru.builds
        NTT._power_table(10, 7)                      # rebuilds every time
        assert lru.builds == b0 + 1

    def test_budget_env_override(self, monkeypatch):
        monkeypatch.setenv("SPECTRE_NTT_TABLE_MB", "3")
        assert NTT._table_budget_bytes() == 3 << 20

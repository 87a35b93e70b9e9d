"""The SRS's Lagrange base (ISSUE 38): L_i(tau) G from tau and from the
powers, its sibling file, the prefix case, and `kzg.commit_lagrange_many`
against the coefficient commit, blinding rows and the additions' edge cases
included. Host library only (`CpuBackend`): no device program."""

import hashlib
import os
import random

import numpy as np
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.native import host
from spectre_tpu.plonk import backend as B, kzg
from spectre_tpu.plonk import srs as srs_mod
from spectre_tpu.plonk.domain import get_domain
from spectre_tpu.plonk.srs import SRS
from spectre_tpu.utils.artifacts import ArtifactCorrupt

R = bn.R
KS = (4, 5, 6)


def _tau(seed: bytes = b"spectre-tpu-test-srs") -> int:
    return int.from_bytes(hashlib.sha256(seed).digest() * 2, "big") % R


@pytest.fixture(scope="module")
def setups():
    return {k: SRS.unsafe_setup(k) for k in KS}


def _random_column(k: int, seed: int) -> np.ndarray:
    rng = random.Random(seed)
    return B.to_arr([rng.randrange(R) for _ in range(1 << k)])


def _coefficient_commit(srs, vals):
    cpu = B.get_backend("cpu")
    return kzg.commit(srs, get_domain(srs.k).lagrange_to_coeff(vals, cpu), cpu)


@pytest.fixture()
def no_fft(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the group FFT ran")
    monkeypatch.setattr(host, "g1_fft", refuse)


class TestLagrangeBase:
    @pytest.mark.parametrize("k", KS)
    def test_from_tau_equals_the_group_fft_of_the_powers(self, setups, k):
        srs = setups[k]
        cut = SRS(k, srs.g1_powers, srs.g2_gen, srs.g2_tau)
        assert np.array_equal(cut.g1_lagrange, srs.g1_lagrange)
        assert cut.g1_lagrange is cut.g1_lagrange      # one array, kept

    @pytest.mark.parametrize("k", KS)
    def test_values_commit_to_the_coefficients_point(self, setups, k):
        srs, cpu = setups[k], B.get_backend("cpu")
        vals = _random_column(k, k)
        want = _coefficient_commit(srs, vals)
        assert cpu.msm(srs.g1_lagrange, vals) == want
        assert kzg.commit_lagrange(srs, vals, cpu) == want
        assert kzg.commit_lagrange_many(srs, [vals, vals], cpu) == [want] * 2

    def test_scalars_are_the_lagrange_polynomials_at_tau(self):
        k, tau = 4, _tau()
        n, omega = 1 << k, get_domain(k).omega
        got = host.limbs_to_ints(srs_mod.lagrange_scalars(k, tau))
        for i in (0, 1, n - 1):
            want = 1
            for j in range(n):
                if j != i:
                    want = want * (tau - pow(omega, j, R)) % R * pow(
                        pow(omega, i, R) - pow(omega, j, R), -1, R) % R
            assert got[i] == want
        assert sum(got) % R == 1

    def test_fixed_base_mul_is_a_scalar_multiplication_a_row(self):
        gen = (int(bn.G1_GEN[0]), int(bn.G1_GEN[1]))
        scalars = [0, 1, 2, R - 1, _tau()]
        got = host.limbs_to_points(
            host.g1_fixed_base_mul(gen, host.ints_to_limbs(scalars)))
        want = [bn.g1_curve.mul(bn.G1_GEN, s) for s in scalars]
        assert got == [None if p is None else (int(p[0]), int(p[1]))
                       for p in want]

    def test_the_digests_name_two_bases(self, setups):
        srs = setups[4]
        assert srs.lagrange_digest() != srs.digest()
        assert srs.lagrange_digest() == SRS(
            4, srs.g1_powers, srs.g2_gen, srs.g2_tau).lagrange_digest()
        assert srs.lagrange_digest() != setups[5].lagrange_digest()


class TestLagrangeFile:
    def test_round_trip_reads_the_sibling(self, setups, tmp_path, no_fft):
        srs = setups[5]
        path = str(tmp_path / "kzg_bn254_5.srs")
        srs.write(path)
        assert os.path.exists(path + ".lagrange")
        back = SRS.read(path)
        assert np.array_equal(back.g1_powers, srs.g1_powers)
        assert np.array_equal(back.g1_lagrange, srs.g1_lagrange)
        assert back.g1_lagrange.dtype == np.uint64

    def test_a_file_without_tau_gets_its_base_once(self, setups, tmp_path,
                                                  monkeypatch):
        """A ceremony's file has powers and no sibling: the first use works
        the base out of them and keeps it beside the file; the next process
        reads it."""
        srs = setups[5]
        path = str(tmp_path / "kzg_bn254_5.srs")
        SRS(5, srs.g1_powers, srs.g2_gen, srs.g2_tau).write(path)
        assert not os.path.exists(path + ".lagrange")
        first = SRS.read(path)
        assert np.array_equal(first.g1_lagrange, srs.g1_lagrange)
        assert os.path.exists(path + ".lagrange")
        monkeypatch.setattr(host, "g1_fft", None)      # not called again
        assert np.array_equal(SRS.read(path).g1_lagrange, srs.g1_lagrange)

    def test_a_prefix_of_a_larger_file_gets_a_base_of_its_own(
            self, setups, tmp_path):
        big = setups[6]
        big.write(str(tmp_path / "kzg_bn254_6.srs"))
        small = SRS.load_or_setup(5, str(tmp_path))
        assert np.array_equal(small.g1_powers, setups[5].g1_powers)
        assert np.array_equal(small.g1_lagrange, setups[5].g1_lagrange)
        assert not np.array_equal(small.g1_lagrange, big.g1_lagrange[:32])
        # kept beside the file it wrote, and read from there the next time
        assert os.path.exists(str(tmp_path / "kzg_bn254_5.srs.lagrange"))
        again = SRS.load_or_setup(5, str(tmp_path))
        assert np.array_equal(again.g1_lagrange, setups[5].g1_lagrange)
        # `truncate` is the same cut, one object a k
        assert big.truncate(5) is big.truncate(5)
        assert np.array_equal(big.truncate(5).g1_lagrange,
                              setups[5].g1_lagrange)

    def test_another_size_s_sibling_is_refused(self, setups, tmp_path):
        path = str(tmp_path / "kzg_bn254_4.srs")
        setups[4].write(path)
        setups[5].write(str(tmp_path / "other.srs"))
        for suffix in (".lagrange", ".lagrange.sha256"):
            os.replace(str(tmp_path / "other.srs") + suffix, path + suffix)
        with pytest.raises(ValueError, match="not the Lagrange base of k=4"):
            SRS.read(path).g1_lagrange

    def test_a_flipped_bit_in_the_sibling_is_refused(self, setups, tmp_path):
        path = str(tmp_path / "kzg_bn254_4.srs")
        setups[4].write(path)
        with open(path + ".lagrange", "r+b") as f:
            f.seek(40)
            byte = f.read(1)
            f.seek(40)
            f.write(bytes([byte[0] ^ 1]))
        with pytest.raises(ArtifactCorrupt):
            SRS.read(path).g1_lagrange


class TestBlindingRowsApart:
    """`commit_lagrange_many(..., usable=u)`: the rows from u on go to the
    host, the rest to the backend, and the two points are added: the same
    point as the whole column's, whatever the two halves are."""

    K = 5
    U = 32 - 6

    def _scalars(self):
        return host.limbs_to_ints(srs_mod.lagrange_scalars(self.K, _tau()))

    def _column(self, case: str):
        n, u, lag = 32, self.U, self._scalars()
        vals = [0] * n
        rng = random.Random(7)
        if case == "random":
            vals = [rng.randrange(2) for _ in range(u)] \
                + [rng.randrange(R) for _ in range(n - u)]
        elif case == "no_head":          # identity + point
            vals[u + 1] = rng.randrange(R)
        elif case == "no_tail":          # point + identity
            vals[3] = 1
        elif case == "nothing":          # identity + identity
            pass
        elif case in ("equal_points", "opposite_points"):
            # head = a L_0(tau) G; the tail row's value makes the tail the
            # same point (the addition is a doubling) or its negative
            a = rng.randrange(R)
            t = a * lag[0] % R * pow(lag[u], -1, R) % R
            vals[0], vals[u] = a, t if case == "equal_points" else R - t
        else:
            raise ValueError(case)
        return B.to_arr(vals)

    @pytest.mark.parametrize("case", ["random", "no_head", "no_tail",
                                      "nothing", "equal_points",
                                      "opposite_points"])
    def test_equals_the_whole_column(self, setups, case):
        srs, cpu = setups[self.K], B.get_backend("cpu")
        col = self._column(case)
        want = _coefficient_commit(srs, col)
        seen = []

        class Spy(B.CpuBackend):
            def msm_many(self, points, scalars_list, base_key=None,
                         basis="powers"):
                seen.append((points, scalars_list, base_key, basis))
                return super().msm_many(points, scalars_list)

        got = kzg.commit_lagrange_many(srs, [col, col], Spy(), usable=self.U)
        assert got == [want, want]
        assert (want is None) == (case in ("nothing", "opposite_points"))
        # what the backend saw: the Lagrange base under its own key, and no
        # blinding row
        (points, heads, key, basis), = seen
        assert points is srs.g1_lagrange and basis == "lagrange"
        assert key == srs.lagrange_digest()
        assert all(not h[self.U:].any() for h in heads)
        assert np.array_equal(heads[0][:self.U], col[:self.U])
        assert col[self.U:].any() == (case not in ("no_tail", "nothing"))
        assert kzg.commit_lagrange_many(srs, [col], cpu) == [want]

    def test_a_larger_srs_commits_through_its_cut(self, setups):
        col = _random_column(4, 11)
        want = _coefficient_commit(setups[4], col)
        assert kzg.commit_lagrange(setups[6], col, B.get_backend("cpu"),
                                   usable=10) == want


class TestProveBytes:
    def test_values_and_coefficients_give_the_same_proof(
            self, tiny, tiny_cpu_proof, monkeypatch):
        """The prover as it was before ISSUE 38, every column transformed
        and its coefficients committed against the powers, gives the bytes
        it gives now (`CpuBackend`; the device backend's side is
        tests/test_device_prove.py)."""
        from spectre_tpu.plonk.prover import prove

        from _shapes import TINY_SEED, seeded_blinding

        calls = []

        def by_coefficients(srs, evals_list, bk=None, usable=None):
            calls.append((len(evals_list), usable))
            return kzg.commit_many(
                srs, get_domain(srs.k).lagrange_to_coeff_many(evals_list, bk),
                bk)

        monkeypatch.setattr(kzg, "commit_lagrange_many", by_coefficients)
        proof = prove(tiny.pk, tiny.srs, tiny.asg, B.get_backend("cpu"),
                      blinding_rng=seeded_blinding(TINY_SEED))
        assert proof == tiny_cpu_proof
        # advice + lookup advice, then the two permuted columns, with the
        # usable rows named
        u = tiny.cfg.usable_rows
        assert calls == [(2, u), (2, u)] and u < tiny.cfg.n

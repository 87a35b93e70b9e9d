"""KZG commitments + BDFG20 (SHPLONK) multiopen.

Reference parity: halo2's KZGCommitmentScheme + snark-verifier's SHPLONK
multi-open (SURVEY.md §2b N4). Prover-side design is TPU-shaped: every
quotient ((p - r)/Z_S, L/(X - u)) is computed POINTWISE on the evaluation
domain (the divisor never vanishes on the domain because the open points are
random), so the whole multiopen is elementwise ops + one iNTT + one MSM per
witness commitment — no sequential synthetic division anywhere.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from ..fields import bn254
from ..native import host
from ..observability.tracing import annotate, span
from . import backend as B
from .domain import Domain
from .srs import SRS

R = bn254.R


def commit(srs: SRS, coeffs: np.ndarray, bk=None):
    """Commit to coefficient-form poly: MSM over tau powers. The SRS digest
    rides along as the fixed-base table key (SPECTRE_MSM_MODE=fixed reuses
    one precomputed window table per SRS across every commitment)."""
    bk = bk or B.get_backend()
    assert coeffs.shape[0] <= srs.n, "poly larger than SRS"
    return bk.msm(srs.g1_powers, coeffs, base_key=srs.digest())


def commit_many(srs: SRS, coeffs_list: list, bk=None) -> list:
    """Commit to several coefficient-form polys in one backend call
    (device base cached + batch axis shardable — SURVEY §2c(b))."""
    bk = bk or B.get_backend()
    for c in coeffs_list:
        assert c.shape[0] <= srs.n, "poly larger than SRS"
    return bk.msm_many(srs.g1_powers, coeffs_list, base_key=srs.digest())


def commit_lagrange(srs: SRS, domain: Domain, evals: np.ndarray, bk=None):
    """Commit to lagrange-form poly (iNTT then power-basis MSM)."""
    bk = bk or B.get_backend()
    return commit(srs, domain.lagrange_to_coeff(evals, bk), bk)


@dataclass
class OpenEntry:
    """One committed polynomial opened at a set of points."""

    coeffs: np.ndarray          # [n, 4] coefficient form (prover side)
    commitment: object          # affine point (verifier side)
    points: tuple               # the query points (ints)
    evals: tuple                # claimed evaluations at those points


def _interp(points, evals) -> list[int]:
    """Lagrange interpolation -> coefficient list (degree < len(points))."""
    m = len(points)
    coeffs = [0] * m
    for j in range(m):
        # basis poly prod_{k!=j} (X - x_k) / (x_j - x_k)
        denom = 1
        basis = [1]
        for k2 in range(m):
            if k2 == j:
                continue
            denom = denom * ((points[j] - points[k2]) % R) % R
            # basis *= (X - x_k)
            nb = [0] * (len(basis) + 1)
            for d, c in enumerate(basis):
                nb[d + 1] = (nb[d + 1] + c) % R
                nb[d] = (nb[d] - c * points[k2]) % R
            basis = nb
        scale = evals[j] * pow(denom, -1, R) % R
        for d, c in enumerate(basis):
            coeffs[d] = (coeffs[d] + c * scale) % R
    return coeffs


def _z_eval(points, x: int) -> int:
    out = 1
    for s in points:
        out = out * ((x - s) % R) % R
    return out


def _domain_linear_factors(domain: Domain, points, bk) -> np.ndarray:
    """[n,4] evals of Z_S(omega^i) = prod (omega^i - s)."""
    omegas = bk.powers(domain.omega, domain.n)
    acc = None
    for s in points:
        term = bk.sub(omegas, B.to_arr([s] * domain.n))
        acc = term if acc is None else bk.mul(acc, term)
    return acc


def _eval_small_poly_on_domain(domain: Domain, coeffs: list[int], bk) -> np.ndarray:
    """Evaluate a degree<=3 poly on the whole domain, vectorized."""
    omegas = bk.powers(domain.omega, domain.n)
    acc = B.to_arr([coeffs[-1]] * domain.n)
    for c in reversed(coeffs[:-1]):
        acc = bk.add(bk.mul(acc, omegas), B.to_arr([c] * domain.n))
    return acc


def shplonk_open(srs: SRS, domain: Domain, entries: list[OpenEntry], transcript, bk=None):
    """Prover: BDFG20 two-commitment multiopen. Evals must already be absorbed
    into the transcript by the caller; this writes W1, W2.

    Most of it is host arithmetic between a few backend calls; its three
    loops are spans of their own (`multiopen/h_poly`, with each entry's
    low-degree remainder on the domain as `multiopen/h_poly/remainder`;
    `multiopen/linearisation`; `multiopen/w2_division`), with the backend's
    calls as their children."""
    bk = bk or B.get_backend()
    v = transcript.challenge()

    # group by point set (identical sets share one Z_S)
    n = domain.n
    all_points = []
    for e in entries:
        for p in e.points:
            if p not in all_points:
                all_points.append(p)

    with span("multiopen/h_poly", entries=len(entries)):
        h_evals = B.zeros(n)
        vk = 1
        lagrange_cache = {}
        zinv_cache = {}
        for e in entries:
            key = e.points
            if key not in zinv_cache:
                zinv_cache[key] = bk.inv(
                    _domain_linear_factors(domain, e.points, bk))
            if e.coeffs.shape[0] < n:
                padded = np.zeros((n, 4), dtype=np.uint64)
                padded[:e.coeffs.shape[0]] = e.coeffs
            else:
                padded = e.coeffs
            p_evals = domain.coeff_to_lagrange(padded, bk)
            with span("multiopen/h_poly/remainder"):
                r_coeffs = _interp(e.points, e.evals)
                r_evals = _eval_small_poly_on_domain(domain, r_coeffs, bk)
            term = bk.mul(bk.sub(p_evals, r_evals), zinv_cache[key])
            h_evals = bk.add(h_evals, bk.scale(term, vk))
            lagrange_cache[id(e)] = (p_evals, r_coeffs)
            vk = vk * v % R

        h_coeffs = domain.lagrange_to_coeff(h_evals, bk)
    w1 = commit(srs, h_coeffs, bk)
    transcript.write_point(w1)
    u = transcript.challenge()

    # L(X) = sum v^k Z_{T \ S_k}(u) (p_k(X) - r_k(u)) - Z_T(u) h(X)
    with span("multiopen/linearisation"):
        l_evals = B.zeros(n)
        vk = 1
        for e in entries:
            p_evals, r_coeffs = lagrange_cache[id(e)]
            z_rest = _z_eval([p for p in all_points if p not in e.points], u)
            r_u = 0
            for c in reversed(r_coeffs):
                r_u = (r_u * u + c) % R
            term = bk.sub(p_evals, B.to_arr([r_u] * n))
            l_evals = bk.add(l_evals, bk.scale(term, vk * z_rest % R))
            vk = vk * v % R
        z_t_u = _z_eval(all_points, u)
        l_evals = bk.sub(l_evals, bk.scale(domain.coeff_to_lagrange(
            _pad(h_coeffs, n), bk), z_t_u))

    # W2 = commit(L / (X - u)) via pointwise division on the domain
    with span("multiopen/w2_division"):
        omegas = bk.powers(domain.omega, n)
        denom_inv = bk.inv(bk.sub(omegas, B.to_arr([u] * n)))
        w2_evals = bk.mul(l_evals, denom_inv)
        w2_coeffs = domain.lagrange_to_coeff(w2_evals, bk)
    w2 = commit(srs, w2_coeffs, bk)
    transcript.write_point(w2)


def _pad(coeffs, n):
    if coeffs.shape[0] >= n:
        return coeffs
    out = np.zeros((n, 4), dtype=np.uint64)
    out[:coeffs.shape[0]] = coeffs
    return out


def shplonk_accumulate(srs: SRS, entries: list[OpenEntry], transcript):
    """Verifier scalar/MSM work WITHOUT the pairing: returns the deferred
    check (w2, F + u*W2) satisfying e(F + u*W2, [1]_2) == e(W2, [tau]_2),
    affine points with `Fq` coordinates (None = the identity).
    One definition serves shplonk_verify AND the aggregation layer's native
    accumulator oracle (`plonk/in_circuit.py`).

    The combination is ONE multi-scalar multiplication in the native host
    library (span `verify/accumulate`, its `points` the pairs that went in).
    It never goes through `B.get_backend()`: this is the check that guards a
    served proof against corruption on the device, and a verifier that
    committed through `TpuBackend` would have the chip vouch for itself.
    Points read from the proof come through `transcript.read_point`, which
    has checked them to be on the curve before they reach the MSM."""
    v = transcript.challenge()
    w1 = transcript.read_point()
    u = transcript.challenge()
    w2 = transcript.read_point()

    with span("verify/accumulate"):
        all_points = []
        for e in entries:
            for p in e.points:
                if p not in all_points:
                    all_points.append(p)

        # F + u W2, with
        # F = sum v^k Z_rest(u) C_k  -  [sum v^k Z_rest(u) r_k(u)] G  -  Z_T(u) W1
        points, scalars = [], []
        e_scalar = 0
        vk = 1
        for e in entries:
            z_rest = _z_eval([p for p in all_points if p not in e.points], u)
            r_coeffs = _interp(e.points, e.evals)
            r_u = 0
            for c in reversed(r_coeffs):
                r_u = (r_u * u + c) % R
            w = vk * z_rest % R
            points.append(e.commitment)
            scalars.append(w)
            e_scalar = (e_scalar + w * r_u) % R
            vk = vk * v % R
        points += [bn254.G1_GEN, w1, w2]
        scalars += [-e_scalar % R, -_z_eval(all_points, u) % R, u % R]
        annotate(points=len(points))
        one_side = host.g1_msm(host.points_to_limbs(points),
                               host.ints_to_limbs(scalars))
    if one_side is not None:
        one_side = (bn254.Fq(one_side[0]), bn254.Fq(one_side[1]))

    # deferred: e(F + u W2, [1]_2) == e(W2, [tau]_2)
    return w2, one_side


def shplonk_verify(srs: SRS, entries: list[OpenEntry], transcript) -> bool:
    """Verifier: reads W1, W2; one pairing check."""
    tau_side, one_side = shplonk_accumulate(srs, entries, transcript)
    return bn254.pairing_check([
        (one_side, srs.g2_gen),
        (bn254.g1_curve.neg(tau_side), srs.g2_tau),
    ])

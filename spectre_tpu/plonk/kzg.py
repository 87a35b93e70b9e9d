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
    one precomputed window table per SRS across every commitment). A
    coefficient is a full-width scalar whatever the column held: what the
    prover has as values it commits with `commit_lagrange_many`."""
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


def commit_lagrange(srs: SRS, evals: np.ndarray, bk=None, usable=None):
    """`commit_lagrange_many` of one column."""
    return commit_lagrange_many(srs, [evals], bk, usable)[0]


def commit_lagrange_many(srs: SRS, evals_list: list, bk=None,
                         usable: int | None = None) -> list:
    """Commit to polynomials given by their VALUES on the domain ([n, 4]
    each, n the domain's size): one MSM of the values against the Lagrange
    base L_i(tau) G (halo2's `commit_lagrange`), the point the coefficients
    commit to against the powers, with no transform on the way. The scalars
    are then the cells themselves, and the one-device backend runs only the
    windows a column's largest cell reaches (`TpuBackend._msm_chunks`): one
    for a column of bits where a coefficient pays all of them.

    usable: the rows from `usable` on are blinding rows, full-width scalars
    in every column. They are kept from the backend (zeroed in what it gets,
    so a column of bits stays one) and committed here, n - usable points a
    column in the native host library while the backend's call runs, then
    added to its point."""
    bk = bk or B.get_backend()
    n = evals_list[0].shape[0]
    assert all(e.shape[0] == n for e in evals_list) and n <= srs.n \
        and n & (n - 1) == 0, "values are a whole domain's"
    srs = srs.truncate(n.bit_length() - 1)
    base, key = srs.g1_lagrange, srs.lagrange_digest()
    if usable is None or usable >= n:
        return bk.msm_many(base, evals_list, base_key=key, basis="lagrange")
    heads = []
    for e in evals_list:
        head = e.copy()
        head[usable:] = 0
        heads.append(head)
    from concurrent.futures import ThreadPoolExecutor
    with ThreadPoolExecutor(max_workers=1) as ex:
        tails = ex.submit(lambda: [host.g1_msm(base[usable:], e[usable:])
                                   for e in evals_list])
        points = bk.msm_many(base, heads, base_key=key, basis="lagrange")
    summed = host.g1_add_affine_batch(host.points_to_limbs(points),
                                      host.points_to_limbs(tails.result()))
    return host.limbs_to_points(summed)


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


def _domain_linear_factors(omegas: np.ndarray, points, bk) -> np.ndarray:
    """[n,4] evals of Z_S(omega^i) = prod (omega^i - s), from the domain's
    points `omegas` = omega^i."""
    acc = None
    for s in points:
        term = bk.add_scalar(omegas, -s % R)
        acc = term if acc is None else bk.mul(acc, term)
    return acc


def _eval_small_poly_on_domain(omegas: np.ndarray, coeffs: list[int], bk) -> np.ndarray:
    """Evaluate a poly of a few coefficients (as many as its set has points:
    one to five) at every omega^i of `omegas`, by Horner over the column."""
    acc = B.const_arr(coeffs[-1], omegas.shape[0])
    for c in reversed(coeffs[:-1]):
        acc = bk.add_scalar(bk.mul(acc, omegas), c)
    return acc


def _horner(coeffs: list[int], x: int) -> int:
    out = 0
    for c in reversed(coeffs):
        out = (out * x + c) % R
    return out


def shplonk_open(srs: SRS, domain: Domain, entries: list[OpenEntry], transcript, bk=None):
    """Prover: BDFG20 two-commitment multiopen. Evals must already be absorbed
    into the transcript by the caller; this writes W1, W2.

    h = sum_k v^k (p_k - r_k) / Z_{S_k} is linear in the p_k, and the entries
    share a few point sets S (eight in a committee prove of 231 entries), so
    the entries are first combined a set, P_S = sum_{k in S} v^k p_k in
    coefficient form and R_S = sum v^k r_k as a handful of ints (span
    `multiopen/h_poly/combine`), and everything that touches the domain
    happens once a SET: one NTT, one remainder put on the domain
    (`multiopen/h_poly/remainder`), one division by Z_S. The linearisation
    L = sum_S Z_{T - S}(u) (P_S - R_S(u)) - Z_T(u) h is taken from the same
    per-set evaluations and from h's, which are still at hand
    (`multiopen/linearisation`: no transform), and divided by X - u on the
    domain (`multiopen/w2_division`). Exact field arithmetic throughout, so
    W1 and W2 are those of the entry-at-a-time form (kept as the oracle in
    tests/test_plonk.py). `multiopen/h_poly` carries `entries` and `sets`;
    the backend's calls are children of these spans."""
    bk = bk or B.get_backend()
    v = transcript.challenge()

    n = domain.n
    all_points = []
    for e in entries:
        for p in e.points:
            if p not in all_points:
                all_points.append(p)

    with span("multiopen/h_poly", entries=len(entries)):
        with span("multiopen/h_poly/combine"):
            p_sets: dict = {}   # point set -> P_S, [n,4] coefficients
            r_sets: dict = {}   # point set -> R_S, as many ints as points
            zero = B.zeros(n)
            vk = 1
            for e in entries:
                p_sets[e.points] = bk.axpy(_pad(e.coeffs, n), vk,
                                           p_sets.get(e.points, zero))
                r_sets[e.points] = [
                    (a + c * vk) % R for a, c in zip(
                        r_sets.get(e.points, [0] * len(e.points)),
                        _interp(e.points, e.evals))]
                vk = vk * v % R
        annotate(sets=len(p_sets))

        omegas = bk.powers(domain.omega, n)
        h_evals = B.zeros(n)
        p_evals = {}
        for points, p_s in p_sets.items():
            zinv = bk.inv(_domain_linear_factors(omegas, points, bk))
            p_evals[points] = domain.coeff_to_lagrange(p_s, bk)
            with span("multiopen/h_poly/remainder"):
                r_evals = _eval_small_poly_on_domain(omegas, r_sets[points], bk)
            h_evals = bk.add(h_evals, bk.mul(
                bk.sub(p_evals[points], r_evals), zinv))

        h_coeffs = domain.lagrange_to_coeff(h_evals, bk)
    w1 = commit(srs, h_coeffs, bk)
    transcript.write_point(w1)
    u = transcript.challenge()

    # L(X) = sum_S Z_{T - S}(u) (P_S(X) - R_S(u)) - Z_T(u) h(X), on the domain
    with span("multiopen/linearisation"):
        l_evals = bk.scale(h_evals, -_z_eval(all_points, u) % R)
        const = 0
        for points, p_s in p_evals.items():
            z_rest = _z_eval([p for p in all_points if p not in points], u)
            l_evals = bk.axpy(p_s, z_rest, l_evals)
            const = (const + z_rest * _horner(r_sets[points], u)) % R
        l_evals = bk.add_scalar(l_evals, -const % R)

    # W2 = commit(L / (X - u)) via pointwise division on the domain
    with span("multiopen/w2_division"):
        denom_inv = bk.inv(bk.add_scalar(omegas, -u % R))
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
            r_u = _horner(_interp(e.points, e.evals), u)
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

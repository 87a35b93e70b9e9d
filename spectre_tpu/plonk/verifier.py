"""The verifier: transcript replay, identity check at x, SHPLONK pairing check.

Reference parity: halo2's verify_proof / snark-verifier PlonkVerifier
(SURVEY.md L0). Pure host math, and never the configured backend: the
service runs it on every proof before serving it, to catch what the device
got wrong. Four spans say where its time goes: `verify/replay` (transcript,
commitments, evaluations), `verify/identity` (Lagrange evaluations, the
constraint set at x), `verify/accumulate` (one native MSM over every opened
commitment, `plonk/kzg.py`) and `verify/pairing` (two Miller loops, one
final exponentiation: Python field arithmetic, most of what is left). The
same `all_expressions` definition the prover used guarantees the identity
is checked against exactly the constraint set that was proven.
"""

from __future__ import annotations

from ..fields import bn254
from ..observability.tracing import span
from . import kzg
from .expressions import ScalarCtx, all_expressions
from .keygen import VerifyingKey
from .srs import SRS
from .transcript import Blake2bTranscript

R = bn254.R


def verify(vk: VerifyingKey, srs: SRS, instances: list, proof: bytes,
           transcript_cls=Blake2bTranscript) -> bool:
    acc = verify_deferred(vk, srs, instances, proof, transcript_cls)
    if acc is None:
        return False
    tau_side, one_side = acc
    g1 = bn254.g1_curve
    with span("verify/pairing"):
        return bn254.pairing_check([
            (one_side, srs.g2_gen),
            (g1.neg(tau_side), srs.g2_tau),
        ])


def verify_deferred(vk: VerifyingKey, srs: SRS, instances: list, proof: bytes,
                    transcript_cls=Blake2bTranscript):
    """Everything but the pairing: transcript replay, identity at x, SHPLONK
    combination. Returns the deferred check (tau_side, one_side) with
    e(tau_side, [tau]_2) == e(one_side, [1]_2), or None if the polynomial
    identity fails OR the proof bytes are malformed (short, non-canonical,
    trailing garbage) — untrusted bytes must yield a boolean reject, not an
    exception. The aggregation layer's native accumulator oracle and
    `verify` share this single definition."""
    try:
        return _verify_deferred_inner(vk, srs, instances, proof, transcript_cls)
    except (AssertionError, ValueError):
        return None


def _verify_deferred_inner(vk: VerifyingKey, srs: SRS, instances: list,
                           proof: bytes, transcript_cls):
    cfg = vk.config
    dom = vk.domain
    n, u = cfg.n, cfg.usable_rows
    tr = transcript_cls(proof)

    with span("verify/replay"):
        tr._absorb_bytes(vk.digest())
        for col in instances:
            assert len(col) <= u, "too many public inputs"
            for v in col:
                tr.common_scalar(int(v) % R)

        keys, pre_bg, pre_y, pre_x = vk.commitment_plan()
        commits = {}
        for key in keys[:pre_bg]:
            commits[key] = tr.read_point()
        beta = tr.challenge()
        gamma = tr.challenge()
        for key in keys[pre_bg:pre_y]:
            commits[key] = tr.read_point()
        y = tr.challenge()
        for key in keys[pre_y:pre_x]:
            commits[key] = tr.read_point()
        x = tr.challenge()

        plan = vk.query_plan()
        evals = {}
        for key, rot in plan:
            evals[(key, rot)] = tr.read_scalar()

    with span("verify/identity"):
        # --- instance evaluations (computed, not read: public input binding) ---
        for j in range(cfg.num_instance):
            rows = list(range(len(instances[j])))
            lag = dom.lagrange_evals(x, rows)
            evals[(("inst", j), 0)] = sum(
                int(v) * lag[i] for i, v in enumerate(instances[j])) % R

        # --- gate/permutation/lookup identity at x ---
        lag_special = dom.lagrange_evals(x, [0, cfg.last_row] + list(range(u + 1, n)))
        l0 = lag_special[0]
        llast = lag_special[cfg.last_row]
        lblind = sum(lag_special[i] for i in range(u + 1, n)) % R

        ctx = ScalarCtx(cfg, evals, l0, llast, lblind, x)
        exprs = all_expressions(cfg, ctx, beta, gamma)
        acc = 0
        for e in exprs:
            acc = (acc * y + e) % R
        vanishing = dom.evaluate_vanishing(x)
        xn = pow(x, n, R)
        h_at_x = (evals[(("h", 0), 0)] + xn * evals[(("h", 1), 0)]
                  + xn * xn % R * evals[(("h", 2), 0)]) % R
        if acc != h_at_x * vanishing % R:
            return None

    # --- SHPLONK ---
    fixed_commits = vk.fixed_commitment_map()

    by_key: dict = {}
    for key, rot in plan:
        by_key.setdefault(key, []).append(rot)
    entries = []
    for key, rots in by_key.items():
        pts = tuple(vk.rotation_point(x, r) for r in rots)
        evs = tuple(evals[(key, r)] for r in rots)
        # a commitment may legitimately be None (infinity = zero polynomial,
        # e.g. an all-zero fixed column), so membership — not truthiness —
        # decides where it comes from
        com = commits[key] if key in commits else fixed_commits[key]
        entries.append(kzg.OpenEntry(None, com, pts, evs))
    tau_side, one_side = kzg.shplonk_accumulate(srs, entries, tr)
    tr.assert_consumed()
    return tau_side, one_side

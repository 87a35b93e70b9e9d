"""The opening's two batched native calls against the forms they replaced.

`host.fp_eval_many` (the prover's `prove/evals`: each polynomial at all of
its points in one pass) against Horner over Python ints (`kzg._horner`) and
against one `host.fp_horner` a point; `host.fp_combine_sets` (`shplonk_open`'s
combine: every point set's P_S = sum v^k p_k in one call) and
`host.fp_axpy_batch` against the two-step Montgomery axpy they used to be,
to_mont on the way in and from_mont on the way out, written here over Python
ints. Both calls rest on mont(a, s*R) = a*s; the results are the same field
elements, so the same bytes."""

import random

import numpy as np
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.native import host
from spectre_tpu.plonk import kzg

R = bn.R
MONT_R = (1 << 256) % R
MONT_R_INV = pow(MONT_R, -1, R)


def _mont(a: int, b: int) -> int:
    """A Montgomery product: a * b / 2^256 mod R."""
    return a * b * MONT_R_INV % R


def _axpy_two_step(a: int, s: int, b: int) -> int:
    """The old native axpy, product by product: to_mont(a), to_mont(s),
    their product, from_mont, then + b."""
    to_mont = lambda x: _mont(x, MONT_R * MONT_R % R)  # noqa: E731
    return (_mont(_mont(to_mont(a), to_mont(s)), 1) + b) % R


# (polynomial lengths, points a polynomial, how coefficients and points are
# drawn): one call a case, every polynomial of the case in it
EVAL_CASES = {
    "n_2_14": ([1 << 14, 1 << 14], [5, 1], "random"),
    "n_2_6": ([1 << 6] * 4, [1, 2, 3, 5], "random"),
    "odd_length": ([37, 1, 3, 5], [2, 1, 5, 4], "random"),
    "one_point": ([1 << 6] * 8, [1] * 8, "random"),
    "five_points": ([1 << 6] * 3, [5] * 3, "random"),
    "all_zero": ([1 << 6, 0, 9], [5, 1, 2], "zero"),
    "coeffs_r_minus_1": ([1 << 6, 33], [5, 1], "r_minus_1"),
    "point_zero": ([1 << 6, 7], [3, 1], "point_zero"),
}


def _eval_case(case):
    lens, npts, draw = EVAL_CASES[case]
    rng = random.Random(case)
    polys, points = [], []
    for n, m in zip(lens, npts):
        coeff = {"zero": lambda: 0, "r_minus_1": lambda: R - 1}.get(
            draw, lambda: rng.randrange(R))
        polys.append([coeff() for _ in range(n)])
        pts = [rng.randrange(R) for _ in range(m)]
        if draw == "point_zero":
            pts[0] = 0
        if draw == "r_minus_1":
            pts[-1] = R - 1
        points.append(pts)
    return polys, points


@pytest.mark.parametrize("nthreads", [1, 8])
@pytest.mark.parametrize("case", list(EVAL_CASES))
def test_eval_many_equals_horner(case, nthreads):
    polys, points = _eval_case(case)
    arrays = [host.ints_to_limbs(p) for p in polys]
    got = host.fp_eval_many(host.FR, arrays, points, nthreads=nthreads)
    assert got == [[kzg._horner(p, x) for x in pts]
                   for p, pts in zip(polys, points)]
    assert got == [[host.fp_horner(host.FR, a, x) for x in pts]
                   for a, pts in zip(arrays, points)]


def test_eval_many_of_nothing():
    assert host.fp_eval_many(host.FR, [], []) == []


# (entry lengths, the set of each entry, rows of a set)
COMBINE_CASES = {
    "one_entry": ([64], [0], 64),
    "forty_entries_one_set": ([64] * 40, [0] * 40, 64),
    "forty_entries_eight_sets": ([64] * 40, [k % 8 for k in range(40)], 64),
    "short_polys": ([64, 32, 1, 37, 0, 64], [0, 1, 0, 1, 2, 2], 64),
    "n_2_10_short_first": ([1, 1 << 10, 512], [0, 0, 1], 1 << 10),
}


@pytest.mark.parametrize("nthreads", [1, 8])
@pytest.mark.parametrize("case", list(COMBINE_CASES))
def test_combine_sets_equals_axpy_chain(case, nthreads):
    lens, sets, n = COMBINE_CASES[case]
    rng = random.Random(case)
    polys = [[rng.randrange(R) for _ in range(m)] for m in lens]
    polys[0][0] = R - 1          # every case's first entry has a row
    v = rng.randrange(R)
    weights = [pow(v, k, R) for k in range(len(polys))]
    got = host.fp_combine_sets(host.FR, [host.ints_to_limbs(p) for p in polys],
                               weights, sets, n, nthreads=nthreads)
    assert got.shape == (max(sets) + 1, n, 4)
    want = [[0] * n for _ in range(max(sets) + 1)]
    for p, w, s in zip(polys, weights, sets):
        padded = p + [0] * (n - len(p))
        want[s] = [_axpy_two_step(a, w, b) for a, b in zip(padded, want[s])]
    assert [host.limbs_to_ints(rows) for rows in got] == want


@pytest.mark.parametrize("case", [
    "entry_longer_than_a_set", "a_scalar_short", "a_negative_set",
    "eval_points_short", "not_limbs"])
def test_the_calls_refuse_what_they_cannot_read(case):
    """A shape the native code would read past is refused before the call."""
    p = host.ints_to_limbs([1, 2, 3])
    with pytest.raises(ValueError):
        if case == "entry_longer_than_a_set":
            host.fp_combine_sets(host.FR, [p], [1], [0], 2)
        elif case == "a_scalar_short":
            host.fp_combine_sets(host.FR, [p, p], [1], [0, 0], 4)
        elif case == "a_negative_set":
            host.fp_combine_sets(host.FR, [p], [1], [-1], 4)
        elif case == "eval_points_short":
            host.fp_eval_many(host.FR, [p, p], [[1]])
        else:
            host.fp_eval_many(host.FR, [np.zeros((3, 2), np.uint64)], [[1]])


@pytest.mark.parametrize("s", [0, 1, 2, R - 1, "random"])
def test_axpy_equals_two_step(s):
    rng = random.Random(str(s))
    s = rng.randrange(R) if s == "random" else s
    a = [R - 1, 0, 1] + [rng.randrange(R) for _ in range(61)]
    b = [R - 1, R - 1, 0] + [rng.randrange(R) for _ in range(61)]
    got = host.fp_axpy_batch(host.FR, host.ints_to_limbs(a), s,
                             host.ints_to_limbs(b))
    assert host.limbs_to_ints(got) == [_axpy_two_step(x, s, y)
                                       for x, y in zip(a, b)]


def test_a_library_without_the_calls_is_stale(tmp_path):
    """A library built from older sources lacks the calls; the loader looks
    for every name it binds, not at the file's time alone."""
    old = tmp_path / "libold.so"
    old.write_bytes(b"\0".join(n.encode() for n in host._SIGNATURES
                               if n not in ("fp_eval_many", "fp_combine_sets")))
    assert not host._exports_all(str(old))
    assert host._exports_all(host._SO)


def test_the_opening_spans_carry_their_counts(tiny, tiny_cpu_proof):
    """`evals/horner` has `queries`, `polys` and `threads`, and
    `multiopen/h_poly/combine` `threads`; the seeded proof is the shared
    tiny proof's bytes."""
    from _shapes import TINY_SEED, seeded_blinding
    from spectre_tpu.observability import tracing
    from spectre_tpu.plonk import backend as B
    from spectre_tpu.plonk.prover import prove

    with tracing.trace("open") as tr:
        proof = prove(tiny.pk, tiny.srs, tiny.asg, B.get_backend("cpu"),
                      blinding_rng=seeded_blinding(TINY_SEED))
    assert proof == tiny_cpu_proof
    spans, todo = {}, [tr.root]
    while todo:
        s = todo.pop()
        spans[s.name] = s
        todo += s.children
    plan = tiny.pk.vk.query_plan()
    horner = spans["evals/horner"].meta
    assert horner["queries"] == len(plan)
    assert horner["polys"] == len({key for key, _ in plan})
    assert horner["threads"] == host.threads() >= 1
    assert spans["multiopen/h_poly/combine"].meta["threads"] == host.threads()

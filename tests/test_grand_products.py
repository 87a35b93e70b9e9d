"""The grand products in limbs (`plonk/prover.py:perm_grand_products`,
`lookup_grand_product`) against the loop they replaced, kept here as the
oracle: each column marshalled from Python ints, the ratio and the prefix
product read back into ints, z built and blinded in ints.

Every case draws its blinding from one seeded source: the z bytes are equal
and so is the order of the draws; a satisfied argument telescopes to 1 and
a broken one still raises."""

import random
import types

import numpy as np
import pytest

from spectre_tpu.fields import bn254 as bn
from spectre_tpu.plonk import backend as B
from spectre_tpu.plonk.constraint_system import PERM_CHUNK, permute_lookup
from spectre_tpu.plonk.domain import DELTA, Domain
from spectre_tpu.plonk.prover import (_blind_tail, lookup_grand_product,
                                      perm_grand_products)

R = bn.R
K = 6
N = 1 << K


def oracle_perm(bk, n, u, columns, sigmas, omega_pows, beta, gamma, rand):
    prev_end = 1
    zs = []
    for ch in range(-(-len(columns) // PERM_CHUNK)):
        num = B.to_arr([1] * n)
        den = B.to_arr([1] * n)
        for gidx in range(ch * PERM_CHUNK,
                          min((ch + 1) * PERM_CHUNK, len(columns))):
            v_arr = B.to_arr(columns[gidx])
            dj = pow(DELTA, gidx, R)
            id_term = bk.add_scalar(
                bk.add(v_arr, bk.scale(omega_pows, beta * dj % R)), gamma)
            sig_term = bk.add_scalar(
                bk.add(v_arr, bk.scale(B.to_arr(sigmas[gidx]), beta)), gamma)
            num = bk.mul(num, id_term)
            den = bk.mul(den, sig_term)
        ratio_ints = B.arr_to_ints(bk.mul(num, bk.inv(den)))
        for i in range(u, n):
            ratio_ints[i] = 1
        prefix_ints = B.arr_to_ints(bk.prefix_prod(B.to_arr(ratio_ints)))
        z = [prev_end] + [prev_end * p % R for p in prefix_ints[:-1]]
        prev_end = prev_end * prefix_ints[u - 1] % R
        for i in range(u + 1, n):
            z[i] = rand()
        zs.append(z)
    assert prev_end == 1, \
        "permutation product != 1 (copy constraints unsatisfiable)"
    return zs


def oracle_lookup(bk, n, u, a_v, pa_v, pt_v, t_v, beta, gamma, rand):
    num = bk.mul(bk.add_scalar(B.to_arr(a_v), beta),
                 bk.add_scalar(B.to_arr(t_v), gamma))
    den = bk.mul(bk.add_scalar(B.to_arr(pa_v), beta),
                 bk.add_scalar(B.to_arr(pt_v), gamma))
    ratio = B.arr_to_ints(bk.mul(num, bk.inv(den)))
    for i in range(u, n):
        ratio[i] = 1
    prefix = B.arr_to_ints(bk.prefix_prod(B.to_arr(ratio)))
    z = [1] + prefix[:-1]
    assert prefix[u - 1] == 1, "lookup product != 1"
    for i in range(u + 1, n):
        z[i] = rand()
    return z


def logged_rand(seed):
    r = random.Random(seed)
    drawn = []

    def rand():
        drawn.append(r.randrange(R))
        return drawn[-1]
    return rand, drawn


def copy_circuit(ncols, u, seed):
    """Columns of random values and a sigma of 2-cycles: pairs of active
    cells swapped, each pair holding one value. Returns (columns, sigmas,
    pairs)."""
    r = random.Random(seed)
    dom = Domain(K)
    ident = [[pow(DELTA, g, R) * pow(dom.omega, i, R) % R for i in range(N)]
             for g in range(ncols)]
    columns = [[r.randrange(R) for _ in range(N)] for _ in range(ncols)]
    sigmas = [list(col) for col in ident]
    cells = [(g, i) for g in range(ncols) for i in range(u)]
    r.shuffle(cells)
    pairs = list(zip(cells[0::2], cells[1::2]))[:max(1, len(cells) // 4)]
    for (ga, ia), (gb, ib) in pairs:
        columns[gb][ib] = columns[ga][ia]
        sigmas[ga][ia], sigmas[gb][ib] = ident[gb][ib], ident[ga][ia]
    return columns, sigmas, pairs


def lookup_column(u, seed):
    """One lookup argument over the 4-bit table, the advice's tail blinded
    and the permuted columns' zero, as the prover commits them."""
    r = random.Random(seed)
    cfg = types.SimpleNamespace(n=N, usable_rows=u)
    table = list(range(16)) + [0] * (N - 16)
    a = [r.randrange(16) for _ in range(u)] + [r.randrange(R)
                                                for _ in range(N - u)]
    pa, pt = permute_lookup(cfg, a, table)
    return a, pa, pt, table


CASES = {
    # name: (permutation columns or None for a lookup column, u)
    "36_columns": (36, N - 10),
    "odd_columns": (7, N - 10),
    "one_column": (1, N - 10),
    "lookup_column": (None, N - 10),
    "u_near_n": (5, N - 2),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_limbs_match_the_int_loop(case):
    ncols, u = CASES[case]
    bk = B.CpuBackend()
    beta, gamma = (random.Random(case).randrange(R) for _ in range(2))
    seed = sum(map(ord, case))

    if ncols is None:
        a, pa, pt, table = lookup_column(u, seed)
        rand, want_draws = logged_rand(seed)
        want = oracle_lookup(bk, N, u, a, pa, pt, table, beta, gamma, rand)
        rand, got_draws = logged_rand(seed)
        got = _blind_tail(lookup_grand_product(
            bk, N, u, *map(B.to_arr, (a, pa, pt, table)), beta, gamma),
            u, rand)
        assert got.tobytes() == B.to_arr(want).tobytes()
        assert got_draws == want_draws and len(got_draws) == N - u - 1
        bad = list(a)
        bad[0] = 99    # outside the table
        with pytest.raises(AssertionError, match="lookup product != 1"):
            lookup_grand_product(bk, N, u, *map(B.to_arr, (bad, pa, pt, table)),
                                 beta, gamma)
        return

    columns, sigmas, pairs = copy_circuit(ncols, u, seed)
    omega_pows = bk.powers(Domain(K).omega, N)
    rand, want_draws = logged_rand(seed)
    want = oracle_perm(bk, N, u, columns, sigmas, omega_pows, beta, gamma,
                       rand)
    rand, got_draws = logged_rand(seed)
    got = perm_grand_products(bk, u, [B.to_arr(c) for c in columns],
                              [B.to_arr(s) for s in sigmas], omega_pows,
                              beta, gamma, rand)
    assert len(got) == len(want) == -(-ncols // PERM_CHUNK)
    for z, zw in zip(got, want):
        assert z.dtype == np.uint64 and z.shape == (N, 4)
        assert z.tobytes() == B.to_arr(zw).tobytes()
    # chunk by chunk, row by row: each z's tail is the next draws in turn
    assert got_draws == want_draws
    assert len(got_draws) == len(got) * (N - u - 1)
    tails = [v for z in got for v in B.arr_to_ints(z[u + 1:])]
    assert tails == got_draws

    # a copied cell that differs from its pair breaks the product
    (ga, ia), _ = pairs[0]
    columns[ga][ia] = (columns[ga][ia] + 1) % R
    with pytest.raises(AssertionError, match="permutation product != 1"):
        perm_grand_products(bk, u, [B.to_arr(c) for c in columns],
                            [B.to_arr(s) for s in sigmas], omega_pows,
                            beta, gamma, logged_rand(seed)[0])

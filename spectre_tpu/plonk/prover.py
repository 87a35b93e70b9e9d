"""The prover: witness commitments -> grand products -> quotient -> multiopen.

Reference parity: halo2's create_proof (`gen_snark_shplonk` path,
`util/circuit.rs:163-180`, SURVEY.md §3.2 step 3 — "this is where the TPU
backend plugs in"). All bulk math goes through the backend (MSM commitments,
NTTs, pointwise quotient evaluation); transcript and control flow stay on host.
"""

from __future__ import annotations

import secrets
import threading
import weakref

import numpy as np

from ..fields import bn254
from ..native import host
from ..observability.tracing import annotate, span
from ..utils.profiling import phase
from . import backend as B, kzg
from .constraint_system import (Assignment, NUM_H_CHUNKS, PERM_CHUNK,
                                permute_lookup)
from .domain import DELTA, Domain
from .expressions import all_expressions, perm_column_keys
from .keygen import (KEY_KINDS, ProvingKey, ROT_LAST, ext_cache_budget_bytes,
                     meminfo_total_bytes)
from .srs import SRS
from .transcript import Blake2bTranscript

R = bn254.R


class _ArrayCtx:
    """Prover-side expression context over extended-domain arrays."""

    def __init__(self, cfg, dom: Domain, bk, ext):
        self._cfg = cfg
        self._dom = dom
        self._bk = bk
        self._ext = ext        # key -> extended array (mapping or callable cache)
        # X on the extended coset: g * omega_ext^i (powers domain-cached)
        from .domain import COSET_GEN
        xs = dom._coset_powers(dom.omega_ext, bk)
        self.x_col = bk.scale(xs, COSET_GEN)
        self.l0 = None      # filled by prover
        self.llast = None
        self.lblind = None

    def var(self, key, rot):
        # _ext is a mapping (device path) or a callable cache (_quotient_host)
        arr = self._ext(key) if callable(self._ext) else self._ext[key]
        if rot == 0:
            return arr
        if rot == ROT_LAST:
            return self._dom.rotate_extended(arr, self._cfg.last_row)
        return self._dom.rotate_extended(arr, rot)

    def mul(self, a, b):
        return self._bk.mul(a, b)

    def add(self, a, b):
        return self._bk.add(a, b)

    def sub(self, a, b):
        return self._bk.sub(a, b)

    def scale(self, a, s):
        return self._bk.scale(a, s % R)

    def add_const(self, a, s):
        return self._bk.add_scalar(a, s)

    def const(self, s):
        return B.const_arr(s, self._dom.n_ext)


# The proving key's value columns (fixed, sigma, table) as [n, 4] limb
# arrays, marshalled once a key: id(pk) -> (a weak reference to the key,
# {(kind, j): array}). Held here and not on the key, so a pickled key holds
# what it always held; bounded as `quotient_device._static_cache` is.
_key_limbs: dict = {}
_key_limbs_lock = threading.Lock()          # two jobs may prove one key

_ONE = host.ints_to_limbs([1])[0]          # the limb row of 1


def _key_columns(pk) -> dict:
    with _key_limbs_lock:
        hit = _key_limbs.get(id(pk))
        if hit is None or hit[0]() is not pk:
            if len(_key_limbs) > 4:
                _key_limbs.clear()
            hit = _key_limbs[id(pk)] = (weakref.ref(pk), {})
        return hit[1]


def release_key_columns(pk):
    """Drop the key's limb arrays (`ProvingKey.release_ext_cache`)."""
    _key_limbs.pop(id(pk), None)


def _running_product(bk, num, den, u: int):
    """prefix[i] = prod_{r <= i} num[r] / den[r], every row from u on
    counted as 1 (the blinding rows are inactive)."""
    ratio = bk.mul(num, bk.inv(den))
    ratio[u:] = _ONE
    return bk.prefix_prod(ratio)


def _blind_tail(z, u: int, rand):
    """Rows u+1..n-1 of z from `rand`, row by row. Every constraint touching
    z is inactive there (act excludes them, llast hits row u, ROT_LAST reads
    row u), but z is opened at x and omega*x: deterministic tail rows would
    leak witness information halo2 hides."""
    n = z.shape[0]
    if u + 1 < n:
        z[u + 1:] = host.ints_to_limbs([rand() for _ in range(u + 1, n)])
    return z


def perm_grand_products(bk, u: int, columns: list, sigmas: list, omega_pows,
                        beta: int, gamma: int, rand) -> list:
    """The permutation's running products z, one a chunk of PERM_CHUNK
    columns, each chunk starting where the last ended; for a satisfied
    permutation the last ends at 1 (asserted). columns[g], sigmas[g]: the
    g-th permutation column's values and its sigma, [n, 4] limb arrays;
    every z is one too, its tail blinded from `rand` chunk by chunk."""
    zs = []
    prev_end = 1
    for base in range(0, len(columns), PERM_CHUNK):
        num = den = None
        for g in range(base, min(base + PERM_CHUNK, len(columns))):
            v = columns[g]
            id_term = bk.add_scalar(
                bk.axpy(omega_pows, beta * pow(DELTA, g, R) % R, v), gamma)
            sig_term = bk.add_scalar(bk.axpy(sigmas[g], beta, v), gamma)
            num = id_term if num is None else bk.mul(num, id_term)
            den = sig_term if den is None else bk.mul(den, sig_term)
        prefix = _running_product(bk, num, den, u)
        z = np.empty_like(prefix)
        z[0] = host.ints_to_limbs([prev_end])[0]
        z[1:] = bk.scale(prefix[:-1], prev_end)
        prev_end = prev_end * host.limbs_to_ints(prefix[u - 1:u])[0] % R
        zs.append(_blind_tail(z, u, rand))
    assert prev_end == 1, \
        "permutation product != 1 (copy constraints unsatisfiable)"
    return zs


def lookup_grand_product(bk, n: int, u: int, a_v, pa_v, pt_v, t_v,
                         beta: int, gamma: int):
    """Running product z for one lookup column, an [n, 4] limb array from
    the columns' arrays; telescopes to 1 at row u for honest witnesses
    (asserted — the l_last boundary constraint enforces it in-proof)."""
    num = bk.mul(bk.add_scalar(a_v, beta), bk.add_scalar(t_v, gamma))
    den = bk.mul(bk.add_scalar(pa_v, beta), bk.add_scalar(pt_v, gamma))
    prefix = _running_product(bk, num, den, u)
    assert np.array_equal(prefix[u - 1], _ONE), "lookup product != 1"
    z = np.empty_like(prefix)
    z[0] = _ONE
    z[1:] = prefix[:-1]
    return z


def prove(pk: ProvingKey, srs: SRS, assignment: Assignment,
          bk=None, transcript=None, blinding_rng=None) -> bytes:
    """blinding_rng: optional zero-arg callable returning a uniform element
    of [0, R) for the ZK blinding rows/tails. Default is `secrets` (fresh
    system randomness). Passing a seeded generator makes the proof a pure
    function of (pk, witness, transcript) — the backend byte-equality tests
    (VERDICT r3 item 4) prove the SAME bytes come out of CpuBackend and
    TpuBackend; never seed it in production."""
    bk = bk or B.get_backend()
    rand = blinding_rng or (lambda: secrets.randbelow(R))
    cfg = pk.vk.config
    dom = pk.vk.domain
    n, u = cfg.n, cfg.usable_rows
    tr = transcript or Blake2bTranscript()

    # --- bind statement: vk digest + instances ---
    tr._absorb_bytes(pk.vk.digest())
    for col in assignment.instances:
        for v in col:
            tr.common_scalar(int(v) % R)

    # --- 1. blind + commit advice and lookup-advice columns ---
    def blind(vals):
        out = [int(v) % R for v in vals]
        for i in range(u, n):
            out[i] = rand()
        return out

    # spans below a phase use `span`, not `phase` (utils/profiling.py), and
    # none is named `prove/...`: those are the twelve phases
    with span("job/blind"):
        adv_vals = [blind(v) for v in assignment.advice]
        ladv_vals = [blind(v) for v in assignment.lookup_advice]
        shb_vals = [blind(assignment.sha_bit[j].tolist())
                    for j in range(cfg.num_sha_bit)]
        shw_vals = [blind(assignment.sha_word[j].tolist())
                    for j in range(cfg.num_sha_word)]
        inst_arrs = [B.to_arr(assignment.instance_column(j))
                     for j in range(cfg.num_instance)]

    polys: dict = {}      # key -> coefficient form
    # key -> [n, 4] lagrange values, as the commit marshalled them, of the
    # columns the grand products read
    value_arrs: dict = {}
    keep = set(perm_column_keys(cfg)) | {
        (kind, j) for kind in ("pA", "pT")
        for j in range(cfg.num_lookup_advice)}

    def limbs(vals):
        return vals if isinstance(vals, np.ndarray) else B.to_arr(vals)

    COMMIT_CHUNK = 16   # bounds resident coefficient arrays (k=20: 512MB)

    def commit_cols_batched(item_list, as_values=False):
        """Pipelined + batched commits (SURVEY §2c axes (b)+(c)): host limb
        marshalling of the NEXT chunk overlaps the backend NTT+MSM of the
        current one on worker threads (ctypes/JAX release the GIL), each
        chunk's iNTTs run as ONE batched `lagrange_to_coeff_many` call
        (ISSUE 4: a single [B, n, 16] device kernel instead of B per-column
        dispatches), and each chunk's MSMs go through one `commit_many`
        call (device base cached; batch axis sharded on a mesh). Transcript
        order is unchanged — points are absorbed strictly in sequence.

        as_values: the chunk's VALUES are committed against the Lagrange
        base (`kzg.commit_lagrange_many`, the blinding rows from `u` on
        apart), ahead of the transform, which then only feeds `polys`: the
        same points, and a column of bits pays one window of the MSM where
        its coefficients pay all of them. For the witness's own columns,
        whose cells are mostly narrow; a grand product's are full width
        and stay on the coefficient path.

        An item's values are an int list, marshalled here, or an [n, 4]
        limb array, taken as it is."""
        from concurrent.futures import ThreadPoolExecutor

        if not item_list:
            return
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = {i: ex.submit(limbs, item_list[i][1])
                    for i in range(min(COMMIT_CHUNK, len(item_list)))}
            for base in range(0, len(item_list), COMMIT_CHUNK):
                chunk = item_list[base:base + COMMIT_CHUNK]
                for j in range(base + COMMIT_CHUNK,
                               min(base + 2 * COMMIT_CHUNK, len(item_list))):
                    if j not in futs:
                        futs[j] = ex.submit(limbs, item_list[j][1])
                with span("commit/marshal"):
                    arrs = [futs.pop(base + off).result()
                            for off in range(len(chunk))]
                if as_values:
                    points = kzg.commit_lagrange_many(srs, arrs, bk, usable=u)
                coeffs = dom.lagrange_to_coeff_many(arrs, bk)
                for (key, _), arr, c in zip(chunk, arrs, coeffs):
                    if key in keep:
                        value_arrs[key] = arr
                    polys[key] = c
                if not as_values:
                    points = kzg.commit_many(srs, coeffs, bk)
                for pt in points:
                    tr.write_point(pt)

    with phase("prove/commit_advice"):
        items = ([(("adv", j), v) for j, v in enumerate(adv_vals)]
                 + [(("ladv", j), v) for j, v in enumerate(ladv_vals)]
                 + [(("shb", j), v) for j, v in enumerate(shb_vals)]
                 + [(("shw", j), v) for j, v in enumerate(shw_vals)])
        commit_cols_batched(items, as_values=True)

    # --- 2. lookup permuted columns ---
    with phase("prove/lookup_permute"):
        lk_items = []
        for j in range(cfg.num_lookup_advice):
            pa, pt_col = permute_lookup(cfg, ladv_vals[j], pk.table_values[j])
            lk_items.append(((("pA", j)), pa))
            lk_items.append(((("pT", j)), pt_col))
        commit_cols_batched(lk_items, as_values=True)

    beta = tr.challenge()
    gamma = tr.challenge()

    # --- 3. permutation grand products (chunk-linked) ---
    # every column in [n, 4] limbs: the commit's arrays, the instance's
    # marshalled above, the key's marshalled once a key
    with phase("prove/grand_products"):
        col_keys = perm_column_keys(cfg)
        key_arrs = _key_columns(pk)

        def key_column(key, vals):
            arr = key_arrs.get(key)
            if arr is None:
                arr = key_arrs[key] = B.to_arr(vals)
            return arr

        # ONE span for the chunks (the ledger's `idle_gaps` label);
        # `from_ints`: the columns it marshalled from Python ints, the key's
        # on its first prove
        with span("grand_products/perm_chunk", columns=len(col_keys)):
            cached = len(key_arrs)
            columns = [
                inst_arrs[j] if kind == "inst"
                else key_column((kind, j), pk.fixed_values[j])
                if kind == "fix" else value_arrs[(kind, j)]
                for kind, j in col_keys]
            sigmas = [key_column(("sig", g), pk.sigma_values[g])
                      for g in range(len(col_keys))]
            annotate(from_ints=len(key_arrs) - cached)
            zs = perm_grand_products(bk, u, columns, sigmas,
                                     bk.powers(dom.omega, n), beta, gamma,
                                     rand)
        gp_items = [(("pz", ch), z) for ch, z in enumerate(zs)]

        # --- 4. lookup grand products ---
        for j in range(cfg.num_lookup_advice):
            with span("grand_products/lookup"):
                z = lookup_grand_product(
                    bk, n, u, value_arrs[("ladv", j)], value_arrs[("pA", j)],
                    value_arrs[("pT", j)],
                    key_column(("tab", cfg.table_id(j)), pk.table_values[j]),
                    beta, gamma)
                _blind_tail(z, u, rand)
            gp_items.append((("lz", j), z))
        # no challenge between pz and lz commits: one batched call
        commit_cols_batched(gp_items)
        value_arrs.clear()     # the quotient, the prove's peak, reads none

    y = tr.challenge()

    # instance polys (public-input binding in the identity) — both quotient
    # paths and nothing else create them, so hoist before the dispatch
    # (one batched iNTT over the instance-column stack)
    with phase("prove/instance_polys"):
        for j, c in enumerate(dom.lagrange_to_coeff_many(inst_arrs, bk)):
            polys[("inst", j)] = c

    def poly_for(key):
        return polys[key] if key in polys else pk.column_poly(key)

    if getattr(bk, "device_quotient", False):
        # device-resident path (quotient_device.py): the key's columns stay
        # extended on the device a key
        from .quotient_device import compute_quotient
        with phase("prove/quotient"):
            h_coeffs = compute_quotient(cfg, dom, poly_for, beta, gamma, y,
                                        pk=pk)
    else:
        h_coeffs = _quotient_host(cfg, dom, bk, pk, polys, beta, gamma, y)
    # deg h <= 3n-4, so the top chunk must vanish. A nonzero tail means the
    # division by the vanishing polynomial was inexact: either the witness
    # violates a constraint, or an expression exceeded the degree-4 budget.
    # Refusing here beats silently emitting an unverifiable proof.
    assert not np.any(h_coeffs[NUM_H_CHUNKS * n:]), \
        "quotient not a polynomial: witness violates constraints (or degree budget exceeded)"
    h_chunks = []
    for i in range(NUM_H_CHUNKS):
        chunk = h_coeffs[i * n:(i + 1) * n]
        if chunk.shape[0] < n:
            chunk = np.vstack([chunk, np.zeros((n - chunk.shape[0], 4), np.uint64)])
        polys[("h", i)] = chunk
        h_chunks.append(chunk)
    with phase("prove/commit_h"):
        for pt in kzg.commit_many(srs, h_chunks, bk):
            tr.write_point(pt)

    x = tr.challenge()

    # --- 6. evaluations per the query plan: each polynomial at all of its
    #        points in one native call, written in the plan's order ---
    plan = pk.vk.query_plan()
    by_key: dict = {}
    for key, rot in plan:
        by_key.setdefault(key, []).append(rot)
    rot_points = {rot: pk.vk.rotation_point(x, rot) for _, rot in plan}

    with phase("prove/evals"), span("evals/horner", queries=len(plan),
                                    polys=len(by_key), threads=host.threads()):
        values = host.fp_eval_many(
            host.FR, [poly_for(key) for key in by_key],
            [[rot_points[r] for r in rots] for rots in by_key.values()])
        evals = {(key, r): ev
                 for (key, rots), evs in zip(by_key.items(), values)
                 for r, ev in zip(rots, evs)}
        for key, rot in plan:
            tr.write_scalar(evals[(key, rot)])

    # --- 7. SHPLONK multiopen ---
    with phase("prove/multiopen"):
        entries = []
        for key, rots in by_key.items():
            pts = tuple(rot_points[r] for r in rots)
            evs = tuple(evals[(key, r)] for r in rots)
            entries.append(kzg.OpenEntry(poly_for(key), None, pts, evs))
        kzg.shplonk_open(srs, dom, entries, tr, bk)

    return tr.finalize()


class _BudgetedExtLRU:
    """Byte-budgeted LRU over derived extended-coset arrays (OOM guard).

    Every entry is pure DERIVED data — an NTT of a coeff-form polynomial the
    prover still holds, or a cyclic roll of another entry — so eviction
    costs recompute time, never correctness. The guard exists because the
    unbounded caches held one 512 MB extended array per distinct (column)
    and (column, rotation): the committee-update aggregation circuit
    (63.7M cells, k_agg=22, r5) accumulated ~250 of them and the prover was
    oom-killed at 130 GB. Budget: SPECTRE_QUOTIENT_CACHE_MB, default 30% of
    MemTotal minus the pk-resident fixed-column cache budget (floor 1 GB) —
    small circuits stay fully cached, huge ones evict cold families instead
    of dying."""

    # evicted-then-refetched keys past this count = the working set does not
    # fit the budget and the quotient phase is recomputing 4n NTTs/rolls in
    # a loop; warn once so the operator knows to raise the budget
    THRASH_WARN_THRESHOLD = 32

    def __init__(self, budget_bytes: int):
        import collections
        self.budget = budget_bytes
        self._d = collections.OrderedDict()
        self._bytes = 0
        self._warned_passthrough = False
        self._evicted: dict = {}          # key -> times evicted
        self.recompute_count = 0          # gets of previously-evicted keys
        self._warned_thrash = False

    def get(self, key):
        hit = self._d.get(key)
        if hit is not None:
            self._d.move_to_end(key)
        elif key in self._evicted:
            self.recompute_count += 1
            if (self.recompute_count >= self.THRASH_WARN_THRESHOLD
                    and not self._warned_thrash):
                self._warned_thrash = True
                import sys
                worst = sorted(self._evicted.items(), key=lambda kv: -kv[1])
                fams = ", ".join(f"{k[0] if isinstance(k, tuple) else k}"
                                 f" x{c}" for k, c in worst[:4])
                # stamp the thrash into the job's provenance manifest —
                # "raise SPECTRE_QUOTIENT_CACHE_MB" advice must survive
                # past this process's stderr
                from ..observability.manifest import record_event
                record_event("quotient_cache_thrash",
                             recomputes=self.recompute_count,
                             budget_mb=self.budget >> 20)
                print(f"[quotient] extended-array cache thrashing: "
                      f"{self.recompute_count} recomputes after eviction "
                      f"(budget {self.budget >> 20} MB; hottest evicted "
                      f"families: {fams}) — raise SPECTRE_QUOTIENT_CACHE_MB "
                      f"to avoid repeated 4n NTT/roll recomputes",
                      file=sys.stderr, flush=True)
        return hit

    def put(self, key, arr):
        if arr.nbytes > self.budget:
            # larger than the whole budget: pass through uncached — every
            # read of this key recomputes a 4n NTT/roll, so make the
            # misconfiguration visible once rather than silently burning
            # the quotient phase
            if not self._warned_passthrough:
                self._warned_passthrough = True
                import sys
                print(f"[quotient] extended array ({arr.nbytes >> 20} MB) "
                      f"exceeds SPECTRE_QUOTIENT_CACHE_MB budget "
                      f"({self.budget >> 20} MB): caching disabled, every "
                      f"read recomputes", file=sys.stderr, flush=True)
            return arr
        while self._bytes + arr.nbytes > self.budget and self._d:
            old_key, old = self._d.popitem(last=False)
            self._evicted[old_key] = self._evicted.get(old_key, 0) + 1
            self._bytes -= old.nbytes
        self._d[key] = arr
        self._bytes += arr.nbytes
        return arr


def _quotient_budget_bytes(pk_ext_budget: int) -> int:
    """LRU budget: explicit env, else 30% of RAM MINUS the coexisting
    pk-resident fixed-column cache budget — the two caches draw from one
    memory pool, so bounding them independently would not bound the
    prover (the r5 oom-kill lesson)."""
    import os as _os
    mb = _os.environ.get("SPECTRE_QUOTIENT_CACHE_MB")
    if mb is not None:
        return int(mb) << 20
    total = meminfo_total_bytes()
    if total is None:
        return 8 << 30
    return max(1 << 30, int(total * 0.30) - pk_ext_budget)


def _quotient_host(cfg, dom, bk, pk, polys, beta, gamma, y):
    """The original host-orchestrated quotient: per-op backend calls over
    the extended coset (CPU path)."""
    n, u = cfg.n, cfg.usable_rows
    # The key's own columns (KEY_KINDS) have the same extended form every
    # prove; their ~n-per-circuit 4n-NTTs were about half of quotient
    # wall-clock (BASELINE.md r4: quotient 41-49% of prove). Cache them on
    # the pk object (in-memory only, never persisted): a prover service
    # re-proving against one pk pays the NTTs once.
    pk_ext = pk.__dict__.setdefault("_ext_fixed_cache", {})
    # cap resident bytes per pk (idle-circuit caches stack in a service —
    # see ProvingKey.release_ext_cache); over budget we compute transiently.
    # The budget shares one pool with the LRU below
    ext_budget = ext_cache_budget_bytes()
    lru = _BudgetedExtLRU(_quotient_budget_bytes(ext_budget))

    def _within_budget(arr):
        return (sum(a.nbytes for a in pk_ext.values()) + arr.nbytes
                <= ext_budget)

    def ext(key):
        hit = lru.get(key)
        if hit is not None:
            return hit
        if key in polys:
            return lru.put(key, dom.coeff_to_extended(polys[key], bk))
        if key[0] in KEY_KINDS:
            hit = pk_ext.get(key)
            if hit is None:
                hit = dom.coeff_to_extended(pk.column_poly(key), bk)
                if _within_budget(hit):
                    pk_ext[key] = hit
                else:
                    hit = lru.put(key, hit)   # per-prove lifetime only
            return hit
        # ("inst", j) is pre-populated in polys by prove()
        raise KeyError(key)

    class LazyCtx(_ArrayCtx):
        def var(self, key, rot):
            if rot == 0:
                return ext(key)
            # a (key, rot) pair is read by several expressions; rolling a
            # 4n-row array per read was measurable quotient time — but the
            # rolled copies share the byte budget with the base arrays.
            # Check the rolled entry FIRST: under eviction pressure the base
            # may be gone while the roll survives, and recomputing the base
            # NTT just to discard it would waste ~a 4n NTT per read
            rkey = (key, "rot", rot)
            hit = lru.get(rkey)
            if hit is None:
                r = cfg.last_row if rot == ROT_LAST else rot
                hit = lru.put(rkey, dom.rotate_extended(ext(key), r))
            return hit

    ctx = LazyCtx(cfg, dom, bk, ext)
    # l0 / l_last / l_blind on the extended coset — circuit-fixed, cached
    # alongside the fixed-column extended forms
    if ("l0",) not in pk_ext:
        l0_vals = [0] * n
        l0_vals[0] = 1
        llast_vals = [0] * n
        llast_vals[cfg.last_row] = 1
        lblind_vals = [0] * n
        for i in range(u + 1, n):
            lblind_vals[i] = 1
        for name, vals in (("l0", l0_vals), ("llast", llast_vals),
                           ("lblind", lblind_vals)):
            pk_ext[(name,)] = dom.coeff_to_extended(
                dom.lagrange_to_coeff(B.to_arr(vals), bk), bk)
    ctx.l0 = pk_ext[("l0",)]
    ctx.llast = pk_ext[("llast",)]
    ctx.lblind = pk_ext[("lblind",)]

    with phase("prove/quotient"):
        exprs = all_expressions(cfg, ctx, beta, gamma)
        acc = None
        for e in exprs:
            acc = e if acc is None else bk.axpy(acc, y, e)
        h_evals = bk.mul(acc, dom.vanishing_inv_on_extended())
        return dom.extended_to_coeff(h_evals, bk)

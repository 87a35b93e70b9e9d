"""The prover: witness commitments -> grand products -> quotient -> multiopen.

Reference parity: halo2's create_proof (`gen_snark_shplonk` path,
`util/circuit.rs:163-180`, SURVEY.md §3.2 step 3 — "this is where the TPU
backend plugs in"). All bulk math goes through the backend (MSM commitments,
NTTs, pointwise quotient evaluation); transcript and control flow stay on host.
"""

from __future__ import annotations

import secrets

import numpy as np

from ..fields import bn254
from ..native import host
from ..observability.tracing import span
from ..utils.profiling import phase
from . import backend as B, kzg
from .constraint_system import (Assignment, NUM_H_CHUNKS, PERM_CHUNK,
                                permute_lookup)
from .domain import DELTA, Domain
from .expressions import all_expressions, perm_column_keys
from .keygen import ProvingKey, ROT_LAST
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


def lookup_grand_product(bk, n: int, u: int, a_v, pa_v, pt_v, t_v,
                         beta: int, gamma: int) -> list:
    """Running product z for one lookup column; telescopes to 1 at row u for
    honest witnesses (asserted — the l_last boundary constraint enforces it
    in-proof)."""
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
        inst_vals = [assignment.instance_column(j)
                     for j in range(cfg.num_instance)]

    polys: dict = {}      # key -> coefficient form
    values: dict = {}     # key -> int list (lagrange values)

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
        and stay on the coefficient path."""
        from concurrent.futures import ThreadPoolExecutor

        if not item_list:
            return
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = {i: ex.submit(B.to_arr, item_list[i][1])
                    for i in range(min(COMMIT_CHUNK, len(item_list)))}
            for base in range(0, len(item_list), COMMIT_CHUNK):
                chunk = item_list[base:base + COMMIT_CHUNK]
                for j in range(base + COMMIT_CHUNK,
                               min(base + 2 * COMMIT_CHUNK, len(item_list))):
                    if j not in futs:
                        futs[j] = ex.submit(B.to_arr, item_list[j][1])
                with span("commit/marshal"):
                    arrs = [futs.pop(base + off).result()
                            for off in range(len(chunk))]
                if as_values:
                    points = kzg.commit_lagrange_many(srs, arrs, bk, usable=u)
                coeffs = dom.lagrange_to_coeff_many(arrs, bk)
                for (key, vals), c in zip(chunk, coeffs):
                    values[key] = vals
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
    with phase("prove/grand_products"):
        col_keys = perm_column_keys(cfg)
        omega_pows = bk.powers(dom.omega, n)

        def col_values(key):
            kind, j = key
            if kind == "adv":
                return adv_vals[j]
            if kind == "ladv":
                return ladv_vals[j]
            if kind == "fix":
                return pk.fixed_values[j]
            if kind == "shw":
                return shw_vals[j]
            if kind == "inst":
                return inst_vals[j]
            raise KeyError(key)

        prev_end = 1
        nch = cfg.num_perm_chunks
        gp_items = []    # pz + lz columns, committed in one batched call
        for ch in range(nch):
            with span("grand_products/perm_chunk"):
                cols = list(enumerate(col_keys))[ch * PERM_CHUNK:
                                                 (ch + 1) * PERM_CHUNK]
                num = B.to_arr([1] * n)
                den = B.to_arr([1] * n)
                for gidx, key in cols:
                    v_arr = B.to_arr(col_values(key))
                    dj = pow(DELTA, gidx, R)
                    id_term = bk.add_scalar(
                        bk.add(v_arr, bk.scale(omega_pows, beta * dj % R)),
                        gamma)
                    sig_term = bk.add_scalar(
                        bk.add(v_arr,
                               bk.scale(B.to_arr(pk.sigma_values[gidx]),
                                        beta)),
                        gamma)
                    num = bk.mul(num, id_term)
                    den = bk.mul(den, sig_term)
                ratio = bk.mul(num, bk.inv(den))
                # deactivate blinding rows
                ratio_ints = B.arr_to_ints(ratio)
                for i in range(u, n):
                    ratio_ints[i] = 1
                prefix = bk.prefix_prod(B.to_arr(ratio_ints))
                prefix_ints = B.arr_to_ints(prefix)
                z = [prev_end] + [prev_end * p % R for p in prefix_ints[:-1]]
                prev_end = prev_end * prefix_ints[u - 1] % R if u >= 1 \
                    else prev_end
                # Blind the tail: every constraint touching z is inactive on
                # rows u+1..n-1 (act excludes them, llast hits row u, ROT_LAST
                # reads row u), but z is opened at x and omega*x —
                # deterministic tail rows would leak witness information
                # halo2 hides. Randomize them.
                for i in range(u + 1, n):
                    z[i] = rand()
            gp_items.append((("pz", ch), z))
        assert prev_end == 1, \
            "permutation product != 1 (copy constraints unsatisfiable)"

        # --- 4. lookup grand products ---
        for j in range(cfg.num_lookup_advice):
            with span("grand_products/lookup"):
                z = lookup_grand_product(
                    bk, n, u, values[("ladv", j)], values[("pA", j)],
                    values[("pT", j)], pk.table_values[j], beta, gamma)
                for i in range(u + 1, n):    # blind tail rows (see pz above)
                    z[i] = rand()
            gp_items.append((("lz", j), z))
        # no challenge between pz and lz commits: one batched call
        commit_cols_batched(gp_items)

    y = tr.challenge()

    # instance polys (public-input binding in the identity) — both quotient
    # paths and nothing else create them, so hoist before the dispatch
    # (one batched iNTT over the instance-column stack)
    with phase("prove/instance_polys"):
        for j, c in enumerate(dom.lagrange_to_coeff_many(
                [B.to_arr(v) for v in inst_vals], bk)):
            polys[("inst", j)] = c

    def poly_for(key):
        kind, j = key
        if key in polys:
            return polys[key]
        if kind == "q":
            return pk.selector_polys[j]
        if kind == "fix":
            return pk.fixed_polys[j]
        if kind == "sig":
            return pk.sigma_polys[j]
        if kind == "tab":
            return pk.table_polys[j]
        if kind == "shq":
            return pk.sha_selector_polys[j]
        if kind == "shk":
            return pk.sha_k_poly
        raise KeyError(key)

    if getattr(bk, "device_quotient", False):
        # device-resident path: the whole identity as one jitted XLA
        # program (quotient_device.py)
        from .quotient_device import compute_quotient
        with phase("prove/quotient"):
            h_coeffs = compute_quotient(cfg, dom, poly_for, beta, gamma, y)
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

    # --- 6. evaluations per the query plan ---
    plan = pk.vk.query_plan()

    with phase("prove/evals"), span("evals/horner", queries=len(plan)):
        evals = {}
        for key, rot in plan:
            pt = pk.vk.rotation_point(x, rot)
            ev = host.fp_horner(host.FR, poly_for(key), pt)
            evals[(key, rot)] = ev
            tr.write_scalar(ev)

    # --- 7. SHPLONK multiopen ---
    by_key: dict = {}
    for key, rot in plan:
        by_key.setdefault(key, []).append(rot)
    with phase("prove/multiopen"):
        entries = []
        for key, rots in by_key.items():
            pts = tuple(pk.vk.rotation_point(x, r) for r in rots)
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


def _meminfo_total_bytes():
    try:
        with open("/proc/meminfo") as f:
            return int(f.readline().split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return None


def _quotient_budget_bytes(pk_ext_budget: int) -> int:
    """LRU budget: explicit env, else 30% of RAM MINUS the coexisting
    pk-resident fixed-column cache budget — the two caches draw from one
    memory pool, so bounding them independently would not bound the
    prover (the r5 oom-kill lesson)."""
    import os as _os
    mb = _os.environ.get("SPECTRE_QUOTIENT_CACHE_MB")
    if mb is not None:
        return int(mb) << 20
    total = _meminfo_total_bytes()
    if total is None:
        return 8 << 30
    return max(1 << 30, int(total * 0.30) - pk_ext_budget)


def _quotient_host(cfg, dom, bk, pk, polys, beta, gamma, y):
    """The original host-orchestrated quotient: per-op backend calls over
    the extended coset (CPU path)."""
    n, u = cfg.n, cfg.usable_rows
    # Circuit-FIXED columns (selectors, fixed, sigmas, tables) have the same
    # extended form every prove; their ~n-per-circuit 4n-NTTs were about half
    # of quotient wall-clock (BASELINE.md r4: quotient 41-49% of prove).
    # Cache them on the pk object (in-memory only, never persisted): a
    # prover service re-proving against one pk pays the NTTs once.
    _FIXED_KINDS = ("q", "fix", "sig", "tab", "shq", "shk")
    pk_ext = pk.__dict__.setdefault("_ext_fixed_cache", {})
    # cap resident bytes per pk (idle-circuit caches stack in a service —
    # see ProvingKey.release_ext_cache); over budget we compute transiently.
    # Default: min(16 GB, 15% of RAM) — shares one pool with the LRU below
    import os as _os
    _mb = _os.environ.get("SPECTRE_EXT_CACHE_MB")
    if _mb is not None:
        ext_budget = int(_mb) << 20
    else:
        _total = _meminfo_total_bytes()
        ext_budget = (16 << 30 if _total is None
                      else min(16 << 30, int(_total * 0.15)))
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
        if key[0] in _FIXED_KINDS:
            hit = pk_ext.get(key)
            if hit is None:
                if key[0] == "q":
                    coeffs = pk.selector_polys[key[1]]
                elif key[0] == "fix":
                    coeffs = pk.fixed_polys[key[1]]
                elif key[0] == "sig":
                    coeffs = pk.sigma_polys[key[1]]
                elif key[0] == "tab":
                    coeffs = pk.table_polys[key[1]]
                elif key[0] == "shq":
                    coeffs = pk.sha_selector_polys[key[1]]
                else:
                    coeffs = pk.sha_k_poly
                hit = dom.coeff_to_extended(coeffs, bk)
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

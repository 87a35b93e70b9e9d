"""Pippenger MSM over BN254 G1 on device (the north-star kernel, N2).

Reference parity: halo2's CPU Pippenger (`halo2_proofs` best_multiexp, rayon-
parallel, SURVEY.md §2b N2). That algorithm is branch-and-scatter per point —
the wrong shape for a vector machine — so this is a ground-up redesign around
three TPU constraints: static shapes, no random-access writes, no data-
dependent control flow.

Per window (processed under `lax` control flow so the graph stays small;
`msm_windows` runs as many windows as the caller says the column's scalars
need, `active`, a traced count, so ONE program serves a column of single
bits and a column of full field elements; the windows it does not run are
the identity, which is what a window of zero digits sums to):
  1. digit extraction from limb scalars (branchless bit windowing)
  2. stable sort of point indices by bucket digit
  3. segmented halving reduction over the sorted array: at each of ~log2(n)
     levels adjacent pairs in the same bucket merge (complete projective add);
     pairs straddling a bucket boundary emit their left element into a
     [level, bucket] emission slot — each bucket emits at most once per level,
     so the scatter is conflict-free (OOB indices dropped). Skew-proof: a
     bucket with ALL n points still reduces in log2(n) levels with O(n) work,
     unlike padded-gather schemes whose memory explodes.
  4. bucket totals = tree-reduce of the emission array over levels
  5. weighted bucket aggregation sum_b b*B_b by split digits: the bucket id
     is hi*2^l + lo, so the buckets are a [2^h, 2^l] grid whose row and
     column sums (one tree) carry every bit of the id; the c bit sums are a
     second tree over those 2^h + 2^l sums, then a c-step double-and-add —
     about 2 * 2^c additions a window and depth c, where a serial scan is
     2^c deep and masking the buckets themselves for each bit c * 2^c wide.
  6. window combine: fori_loop of c doublings + add.

Complete RCB addition (ops.ec) makes every step branchless; infinity is the
identity everywhere, so masking = setting slots to (0:1:0).

On top of the vanilla path sit three composable, individually-flagged
optimizations (`SPECTRE_MSM_MODE`, see `msm_mode()`):

  glv         scalars split k = k1 + k2*lambda via the BN254 cube-root
              endomorphism (ops.glv, host prep): 2x the points (P and
              phi(P) = (beta*x, y), one field mul each) but ~127-bit half-
              scalars — half the window passes. Negative halves become point
              negations (one field sub).
  glv+signed  digits recoded on device into [-2^(c-1), 2^(c-1)] (carry scan,
              branchless): the bucket array and the emission space HALVE
              (2^(c-1)+1 instead of 2^c); digit signs fold into the same
              cheap point-negation mask as the GLV signs.
  fixed       for fixed commitment bases (the KZG SRS): the per-window
              doubling chains move into a PRECOMPUTED table T[w] = 2^{cw}*B
              cached per SRS digest (host-side byte-budgeted LRU mirroring
              the quotient cache in plonk/prover.py). Bucket sums merge
              ACROSS windows before one weighted aggregation and the final
              window-combine chain disappears; the reduction itself stays
              per-window-sized (a flattened nwin*2n mega-reduction measured
              ~2x slower — see msm_fixed_run). Implies glv+signed. A table
              that would exceed the budget BY ITSELF degrades the call to
              glv+signed instead of thrashing (see _degrade_fixed).

All modes produce the identical group element (tests/test_msm_modes.py
holds each to the host curve, tests/test_device_prove.py the default inside
byte-equal proofs); they differ only in work shape. Only `vanilla` has run
on the chip (PERF.md section 7), and only it reads how wide a column's
scalars are: the three modes above and the mesh kernels (parallel/) run
every window of their scalar width against any base, the SRS's Lagrange
base included (values committed as they are: correct, and no faster there).
"""

from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import numpy as np

from . import ec
from . import field_ops as F

NLIMBS = F.NLIMBS

MSM_MODES = ("vanilla", "glv", "glv+signed", "fixed")


def msm_mode() -> str:
    """Active MSM mode from SPECTRE_MSM_MODE (default: vanilla). Read per
    call so a test can flip it without reimporting."""
    mode = os.environ.get("SPECTRE_MSM_MODE", "vanilla")
    if mode not in MSM_MODES:
        raise ValueError(
            f"SPECTRE_MSM_MODE={mode!r}: expected one of {MSM_MODES}")
    return mode


def window_override() -> int | None:
    """Operator window override from SPECTRE_MSM_WINDOW (1..13, empty/unset
    = the tuned table). The device retuning knob: every caller of
    `default_window` / `default_window_fixed` (`msm` here,
    parallel/batch_msm.py, plonk/backend.py) honors it without plumbing c
    by hand. The ceiling of 13 is not a memory limit (the aggregation's
    widest tensor, [nwin, 2^h + 2^l, 2^l] points, is 47 MB at c = 13): it
    is the widest window any table entry or test has run, and since the
    emission array [levels + 1, 2^c] charges every window ~levels * 2^c
    additions, no size this prover commits (n <= 2^23) is served by a
    wider one."""
    v = os.environ.get("SPECTRE_MSM_WINDOW")
    if v is None or v == "":
        return None
    c = int(v)
    if not 1 <= c <= 13:
        raise ValueError(
            f"SPECTRE_MSM_WINDOW={v}: expected 1..13 (the widest window "
            "that has run — see window_override)")
    return c


def _digits_traced(scalars, w, c: int):
    """Extract window-w c-bit digits from [n, L] 16-bit limb scalars; w may
    be a traced int32 (used inside lax loops). Width-generic — see
    field_ops.limb_digits (GLV half-scalars are [n, 8])."""
    return F.limb_digits(scalars, w, c)


def signed_digit_stream(scalars, c: int, nwin: int):
    """[n, L] limb scalars -> [nwin, n] int32 signed digits in
    [-2^(c-1)+1, 2^(c-1)], lowest window first.

    Branchless carry recode (lax.scan over windows): a digit above 2^(c-1)
    becomes d - 2^c with a +1 carry into the next window. Needs
    nwin >= ceil((nbits+1)/c) so the final carry is always absorbed (the
    top digit is then <= 2^(c-1) and cannot re-carry)."""
    half = 1 << (c - 1)

    def step(carry, w):
        d = F.limb_digits(scalars, w, c) + carry
        cout = (d > half).astype(jnp.int32)
        return cout, d - (cout << c)

    _carry, digs = jax.lax.scan(
        step, jnp.zeros(scalars.shape[0], dtype=jnp.int32), jnp.arange(nwin))
    return digs


def _tree_sum(pts, axis: int):
    """Sum points over one axis by halving it (an odd width carries its
    last slice to the next level); the axis goes."""
    while pts.shape[axis] > 1:
        k = pts.shape[axis]
        half = k // 2
        merged = ec.padd(jax.lax.slice_in_dim(pts, 0, half, axis=axis),
                         jax.lax.slice_in_dim(pts, half, 2 * half, axis=axis))
        pts = jnp.concatenate(
            [merged, jax.lax.slice_in_dim(pts, 2 * half, k, axis=axis)],
            axis=axis) if k % 2 else merged
    return jnp.squeeze(pts, axis)


def _segmented_bucket_sums(points, digits, nbuckets: int):
    """Sorted segmented reduction -> [nbuckets, 3, 16] bucket sums.

    points: [n, 3, 16] projective Montgomery; digits: [n] int32 bucket ids
    (0 = skip — bucket 0 has weight zero in aggregation). Odd level widths
    append ONE sentinel (bucket id == nbuckets: sorts after every real
    digit, never merges with one, its emissions are OOB and dropped) instead
    of padding to a power of two up front — total work stays n + log n
    instead of up to 2n for awkward sizes (the fixed-base path feeds
    nwin*2n-sized arrays that are never powers of two)."""
    n = points.shape[0]
    order = jnp.argsort(digits, stable=True)
    buckets = digits[order]
    pts = points[order]
    levels = (n - 1).bit_length()

    emissions = ec.inf_point((levels + 1, nbuckets))
    for lvl in range(levels):
        if pts.shape[0] % 2:
            pts = jnp.concatenate([pts, ec.inf_point((1,))], axis=0)
            buckets = jnp.concatenate(
                [buckets, jnp.full((1,), nbuckets, dtype=buckets.dtype)])
        left, right = pts[0::2], pts[1::2]
        bl, br = buckets[0::2], buckets[1::2]
        same = bl == br
        merged = ec.padd(left, right)
        pts = ec.select_point(same, merged, right)
        # boundary pairs: left element is the tail of bucket bl -> emit.
        # at most one emission per bucket per level => conflict-free scatter;
        # non-emitting lanes target an out-of-range row and are dropped.
        emit_idx = jnp.where(same, nbuckets, bl)
        emissions = emissions.at[lvl, emit_idx].set(left, mode="drop")
        buckets = br
    # final survivor
    emissions = emissions.at[levels, buckets[0]].set(pts[0], mode="drop")

    # bucket totals: the emissions summed over the level axis
    return _tree_sum(emissions, 0)


def _aggregate_buckets(bucket_sums, c: int):
    """sum_b b * B_b for each window, by split digits.

    bucket_sums: [nwin, nbuckets, 3, 16] -> [nwin, 3, 16]. nbuckets may be
    any size with ids < 2^c (the signed paths pass 2^(c-1)+1): the axis is
    filled to 2^c with the identity.

    The id splits as b = hi * 2^l + lo (l = c // 2, h = c - l), the buckets
    are a [2^h, 2^l] grid, and
        sum_b b * B_b = 2^l * sum_hi hi * R_hi + sum_lo lo * C_lo
    with R_hi the grid's row sums and C_lo its column sums: bit j of b is
    bit j of lo (j < l) or bit j - l of hi, so the sum over the buckets
    whose id has bit j is a sum over 2^l columns or 2^h rows, not over 2^c
    buckets. Rows and columns are ONE tree over the grid's lo axis and its
    transpose's hi axis (for odd c the hi axis is halved once first, which
    makes the two as long), the c bit sums one tree over [c, 2^h], then
    the double-and-add: about 2 * 2^c + c * 2^h + 2c additions a window
    (masking the buckets themselves for each bit costs c * 2^c)."""
    nwin, nbuckets = bucket_sums.shape[0], bucket_sums.shape[1]
    l = c // 2
    h = c - l
    if nbuckets < 1 << c:
        bucket_sums = jnp.concatenate(
            [bucket_sums, ec.inf_point((nwin, (1 << c) - nbuckets))], axis=1)
    grid = bucket_sums.reshape((nwin, 1 << h, 1 << l) + bucket_sums.shape[2:])
    cols = ec.padd(grid[:, :1 << l], grid[:, 1 << l:]) if h > l else grid
    sums = _tree_sum(
        jnp.concatenate([grid, jnp.swapaxes(cols, 1, 2)], axis=1), 2)
    row_sums, col_sums = sums[:, :1 << h], sums[:, 1 << h:]
    if h > l:
        col_sums = jnp.concatenate(
            [col_sums, ec.inf_point((nwin, (1 << h) - (1 << l)))], axis=1)
    # [nwin, c, 2^h]: for bit j the column (j < l) or row sums whose index
    # has the bit
    shifts = np.concatenate([np.arange(l), np.arange(h)])
    masks = ((np.arange(1 << h)[None, :] >> shifts[:, None]) & 1).astype(bool)
    src = jnp.concatenate(
        [jnp.broadcast_to(col_sums[:, None], (nwin, l) + col_sums.shape[1:]),
         jnp.broadcast_to(row_sums[:, None], (nwin, h) + row_sums.shape[1:])],
        axis=1)
    bit_sums = _tree_sum(
        ec.select_point(masks[None], src, ec.inf_point((1, 1, 1))), 2)
    # acc = sum_j 2^j bit_sums[:, j] by high-to-low double-and-add
    # (a scan, not c unrolled steps: the same chain of additions as one loop
    # body where the unrolled form is 2c copies of an addition, its two
    # CIOS loops and some hundred fused programs each, to lower, compile
    # and load; PERF.md section 5 has what the TPU compiler makes of each)
    def step(acc, bit_sum):
        acc = ec.padd(acc, acc)
        return ec.padd(acc, bit_sum), None

    acc, _ = jax.lax.scan(step, ec.inf_point((nwin,)),
                          jnp.moveaxis(bit_sums, 1, 0), reverse=True)
    return acc


def window_count(nbits: int, c: int) -> int:
    """Windows of c bits that hold a scalar of nbits bits."""
    return (nbits + c - 1) // c


def _msm_windows_impl(points, scalars, c: int, nbits: int, active=None):
    """`active` (None: all): a traced int32, the low windows to run. The
    others stay the identity, so the caller owes that every scalar is below
    2^(c * active); the loop's trip count is data, not shape."""
    nwin = window_count(nbits, c)
    nbuckets = 1 << c

    def one_window(w):
        d = F.limb_digits(scalars, w, c)
        return _segmented_bucket_sums(points, d, nbuckets)

    if active is None:
        bucket_sums = jax.lax.map(one_window, jnp.arange(nwin))
    else:
        bucket_sums = jax.lax.fori_loop(
            0, active, lambda w, buf: buf.at[w].set(one_window(w)),
            ec.inf_point((nwin, nbuckets)))      # [nwin, nb, 3, 16]
    return _aggregate_buckets(bucket_sums, c)


# module-level jitted entry points (trace-cache hygiene lint roots):
# analysis/trace_lint verifies each name below is a stable module-level
# jit — the discipline that keeps per-prove calls on a warm trace cache.
TRACE_JIT_ROOTS = ("msm_windows", "msm_windows_bits", "msm_windows_signed",
                   "combine_windows", "_build_window_table", "msm_fixed_run",
                   "combine_windows_batch", "pad_window_sums")


@functools.partial(jax.jit, static_argnums=(2,))
def msm_windows(points, scalars, c: int, active=None):
    """Per-window partial MSM sums: [nwin, 3, 16].

    points: [n, 3, 16] projective Montgomery; scalars: [n, 16] standard-form
    16-bit limbs. Separated from the final combine so the window axis can be
    sharded across devices (parallel.sharded_msm all-reduces these).

    active: an int32 scalar, TRACED (`np.int32`, so that every count is the
    same program): only the low `active` windows are run, the others are the
    identity; every scalar must be below 2^(c * active)
    (`windows_needed` reads the count off a column). The one-device commit
    path and `msm` always pass it. None runs every window with a static
    trip count: the form the mesh kernels trace inside their own programs
    (parallel/), which take any base at full width."""
    return _msm_windows_impl(points, scalars, c, 254, active)


def windows_needed(scalars_u64: np.ndarray, c: int) -> np.int32:
    """The windows of c bits the largest scalar of a host column reaches:
    [n, 4] u64 standard limbs -> `msm_windows`' `active`. 0 for a column of
    zeros, 1 for a column of bits or of bytes at c = 8, ceil(254 / c) for
    one that holds a full field element."""
    bits = max((64 * j + int(limb).bit_length()
                for j, limb in enumerate(scalars_u64.max(axis=0)) if limb),
               default=0)
    return np.int32(window_count(bits, c))


@functools.partial(jax.jit, static_argnums=(2, 3))
def msm_windows_bits(points, scalars, c: int, nbits: int):
    """msm_windows for scalars of a declared bit-length (GLV half-scalars:
    nbits = glv.glv_bits(), scalars [n, 8])."""
    return _msm_windows_impl(points, scalars, c, nbits)


@functools.partial(jax.jit, static_argnums=(3, 4))
def msm_windows_signed(points, scalars, neg, c: int, nbits: int):
    """Signed-digit window phase: [nwin, 3, 16] partial sums.

    scalars: [n, L] limb magnitudes; neg: [n] bool per-point sign (the GLV
    half-scalar signs). Digit signs and point signs fold into ONE negation
    mask per window — negation is a single field subtract, so the halved
    bucket array (2^(c-1)+1) is nearly free."""
    nwin = (nbits + c) // c          # ceil((nbits + 1) / c): room for carry
    nbuckets = (1 << (c - 1)) + 1
    digs = signed_digit_stream(scalars, c, nwin)

    def one_window(s):
        eff = ec.cneg((s < 0) ^ neg, points)
        return _segmented_bucket_sums(eff, jnp.abs(s), nbuckets)

    bucket_sums = jax.lax.map(one_window, digs)
    return _aggregate_buckets(bucket_sums, c)


@functools.partial(jax.jit, static_argnums=(1,))
def combine_windows(window_sums, c: int):
    """res = sum_w 2^{cw} W_w, high window to low: c doublings + add each."""
    nwin = window_sums.shape[0]

    def body(i, acc):
        acc = jax.lax.fori_loop(0, c, lambda _, a: ec.padd(a, a), acc)
        return ec.padd(acc, window_sums[nwin - 1 - i])

    return jax.lax.fori_loop(0, nwin, body, ec.inf_point(()))


# ---------------------------------------------------------------------------
# GLV expansion (device side; host scalar prep lives in ops.glv)
# ---------------------------------------------------------------------------

@jax.jit
def _expand_endo(points):
    """[n, 3, 16] -> [2n, 3, 16]: [P ; phi(P)], phi the GLV endomorphism."""
    return jnp.concatenate([points, ec.endo(points)], axis=0)


@jax.jit
def _apply_sign(points, neg):
    return ec.cneg(neg, points)


def _glv_scalars_device(scalars):
    """(sc2 [2n, 8], neg [2n]) via the TRACED decomposition — no host
    round trip (glv.decompose_device matches decompose_batch bit-exactly,
    so every mode keeps byte-identical results)."""
    from . import glv
    a1, a2, n1, n2 = glv.decompose_device(jnp.asarray(scalars))
    return (jnp.concatenate([a1, a2], axis=0),
            jnp.concatenate([n1, n2], axis=0))


def glv_split(points, scalars):
    """Device GLV prep: (points2 [2n,3,16], sc2 [2n,8], neg [2n]).

    points2 = [P ; phi(P)] WITHOUT signs applied — the signed-digit kernel
    folds `neg` into its digit-sign mask; the unsigned path applies it with
    `_apply_sign` once. The Babai rounding runs on device
    (glv.decompose_device) so scalar prep never serializes against the
    device windows."""
    sc2, neg = _glv_scalars_device(scalars)
    return _expand_endo(points), sc2, neg


# ---------------------------------------------------------------------------
# fixed-base tables (per-SRS precompute, host-side budgeted LRU)
# ---------------------------------------------------------------------------

class _TableLRU:
    """Byte-budgeted LRU over derived device tables (OOM guard).

    Mirrors the quotient-phase `_BudgetedExtLRU` (plonk/prover.py): every
    entry is pure DERIVED data — a doubling-chain expansion of a base the
    caller still holds, or an NTT twiddle/coset power table — so eviction
    costs recompute time, never correctness. A 2^16-point GLV table at c=13
    is ~252 MB; an unbounded cache across several SRS sizes would quietly
    eat the prover's memory pool. Entries hold a strong ref to the base
    object so id-derived keys can never alias a recycled array.

    Shared machinery: `ops/ntt.py` instantiates a second LRU over its
    twiddle/coset tables (SPECTRE_NTT_TABLE_MB); entries there are TUPLES
    of per-stage arrays, so byte accounting sums over sequence entries."""

    def __init__(self, budget_bytes: int, label: str = "msm fixed-base table",
                 budget_var: str = "SPECTRE_MSM_TABLE_MB", on_event=None):
        import collections
        self.budget = budget_bytes
        self.label = label
        self.budget_var = budget_var
        # best-effort `fn(kind, **detail)` hook (provenance-manifest event
        # recorder): fires on evictions and oversize passthroughs so cache
        # churn during a prove lands in that job's manifest
        self.on_event = on_event
        self._d = collections.OrderedDict()   # key -> (base_ref, table)
        self._bytes = 0
        self.hits = 0
        self.builds = 0
        self.evictions = 0
        # thrash visibility (exported via GET /metrics): a build whose
        # key was previously evicted is a RECOMPUTE — budget too small
        # for the working set
        self.recomputes = 0
        self._evicted_keys: set = set()

    @staticmethod
    def _entry_bytes(table) -> int:
        if isinstance(table, (tuple, list)):
            return sum(t.size * t.dtype.itemsize for t in table)
        return table.size * table.dtype.itemsize

    def get(self, key, base):
        hit = self._d.get(key)
        if hit is not None and (hit[0] is None or hit[0] is base):
            self._d.move_to_end(key)
            self.hits += 1
            return hit[1]
        return None

    def put(self, key, base, table):
        nbytes = self._entry_bytes(table)
        self.builds += 1
        if key in self._evicted_keys:
            self.recomputes += 1
            self._evicted_keys.discard(key)
        if nbytes > self.budget:
            import sys
            print(f"[lru] {self.label} ({nbytes >> 20} MB) exceeds "
                  f"{self.budget_var} budget ({self.budget >> 20} MB): "
                  f"uncached — every use rebuilds it",
                  file=sys.stderr, flush=True)
            if self.on_event is not None:
                self.on_event("lru_oversize", cache=self.label,
                              entry_mb=nbytes >> 20,
                              budget_mb=self.budget >> 20)
            return table
        evicted = 0
        while self._bytes + nbytes > self.budget and self._d:
            _k, (_ref, old) = self._d.popitem(last=False)
            self._bytes -= self._entry_bytes(old)
            self.evictions += 1
            evicted += 1
            self._evicted_keys.add(_k)
        if evicted and self.on_event is not None:
            self.on_event("lru_evictions", cache=self.label, count=evicted)
        self._d[key] = (base, table)
        self._bytes += nbytes
        return table

    def stats(self) -> dict:
        """Counter/occupancy snapshot for the Prometheus exporter
        (observability/prom.py reads this via `lru_stats()`)."""
        return {"hits": self.hits, "builds": self.builds,
                "evictions": self.evictions,
                "recomputes": self.recomputes,
                "bytes": self._bytes, "budget_bytes": self.budget,
                "entries": len(self._d)}


def _table_budget_bytes() -> int:
    mb = os.environ.get("SPECTRE_MSM_TABLE_MB")
    if mb is not None:
        return int(mb) << 20
    try:
        with open("/proc/meminfo") as f:
            total = int(f.readline().split()[1]) * 1024
    except (OSError, ValueError, IndexError):
        return 8 << 30
    return min(8 << 30, int(total * 0.25))


def _record_event(kind, **detail):
    """Forward cache/degrade events to the per-job provenance-manifest
    collector (no-op outside a collecting job; stdlib-only import)."""
    from ..observability.manifest import record_event
    record_event(kind, **detail)


_TABLES = _TableLRU(_table_budget_bytes(), on_event=_record_event)


def lru_stats() -> dict:
    """Fixed-base table cache stats for GET /metrics."""
    return _TABLES.stats()


@functools.partial(jax.jit, static_argnums=(1, 2))
def _build_window_table(points, c: int, nwin: int):
    """[nwin, n, 3, 16] with T[w] = 2^{cw} * points, by chained doubling
    (c doublings per window step; the last step skips its chain — T[nwin]
    is never read)."""
    def step(cur, w):
        def dbl_chain(p):
            return jax.lax.fori_loop(0, c, lambda _i, q: ec.padd(q, q), p)
        nxt = jax.lax.cond(w < nwin - 1, dbl_chain, lambda p: p, cur)
        return nxt, cur

    _last, tables = jax.lax.scan(step, points, jnp.arange(nwin))
    return tables


def _fixed_table_bytes(n: int, c: int, nbits: int) -> int:
    """Exact byte size of the [nwin, 2n, 3, 16] uint32 GLV window table."""
    nwin = (nbits + c) // c
    return nwin * 2 * n * 3 * 16 * 4


def _fixed_fits_budget(n: int, c: int, nbits: int) -> bool:
    return _fixed_table_bytes(n, c, nbits) <= _TABLES.budget


def _degrade_fixed(n: int, c: int, nbits: int) -> bool:
    """Graceful degradation (ISSUE 3): when one fixed-base table would
    exceed the SPECTRE_MSM_TABLE_MB budget, fall back to glv+signed
    (identical group element, no precompute residency) instead of
    thrashing an uncacheable doubling-chain rebuild on every MSM — the
    mesh-sharded path already degrades the same way. Recorded on the
    ServiceHealth counter `msm_fixed_degraded`."""
    if _fixed_fits_budget(n, c, nbits):
        return False
    from ..utils.health import HEALTH
    HEALTH.incr("msm_fixed_degraded")
    _record_event("msm_fixed_degraded", n=n, window=c,
                  table_mb=_fixed_table_bytes(n, c, nbits) >> 20,
                  budget_mb=_TABLES.budget >> 20)
    return True


def fixed_base_table(points, c: int, nwin: int, base_key=None):
    """[nwin, 2n, 3, 16] GLV fixed-base table, LRU-cached: T[w] holds
    2^{cw} * [P ; phi(P)].

    The doubling chains run on the P half only — phi commutes with
    doubling, so the endomorphism half is one field multiply per entry
    instead of a second chain. base_key (e.g. the SRS digest) names the
    base stably across processes/encodings; without it the cache keys on
    id(points) with a strong ref pin."""
    n = points.shape[0]
    key = (base_key if base_key is not None else ("id", id(points)),
           int(n), int(c), int(nwin))
    ref = None if base_key is not None else points
    hit = _TABLES.get(key, ref)
    if hit is not None:
        return hit
    tab = _build_window_table(points, c, nwin)            # [nwin, n, 3, 16]
    tab = jnp.concatenate([tab, ec.endo(tab)], axis=1)    # [nwin, 2n, 3, 16]
    return _TABLES.put(key, ref, tab)


@functools.partial(jax.jit, static_argnums=(3, 4))
def msm_fixed_run(table, scalars, neg, c: int, nbits: int):
    """Fixed-base MSM over a precomputed window table. Returns [3, 16].

    table: [nwin, N, 3, 16] from fixed_base_table; scalars: [N, L] half-
    scalar magnitudes; neg: [N] bool signs. Three structural savings over
    the dynamic-base signed path: no per-window doubling work (the table
    pre-shifts the base), bucket sums MERGE ACROSS WINDOWS before the
    weighted aggregation (one aggregation pass instead of nwin — sound
    because weight b is window-independent once bases carry 2^{cw}), and
    the final combine chain disappears entirely. The reduction stays
    per-window-sized: a single nwin*N mega-reduction measured ~2x slower
    per element on CPU (the 250 MB working set falls out of cache; the
    ~25 MB window slices stream)."""
    nwin = (nbits + c) // c
    nbuckets = (1 << (c - 1)) + 1
    digs = signed_digit_stream(scalars, c, nwin)          # [nwin, N]

    def one_window(args):
        tw, s = args
        eff = ec.cneg((s < 0) ^ neg, tw)
        return _segmented_bucket_sums(eff, jnp.abs(s), nbuckets)

    bucket_sums = jax.lax.map(one_window, (table, digs))  # [nwin, nb, 3, 16]
    # cross-window bucket merge: tree-fold the window axis
    return _aggregate_buckets(_tree_sum(bucket_sums, 0)[None], c)[0]


# ---------------------------------------------------------------------------
# window-size tuning + top-level dispatch
# ---------------------------------------------------------------------------

def default_window(n: int, signed: bool = False) -> int:
    """Pippenger window size for n points (the EXPANDED count under GLV).

    The unsigned entries for 2^12 <= n < 2^18 were chosen on the chip (TPU
    v5 lite, `msm_windows` in a process of its own, the median of three
    repeats), twice. PR 32, chip call 1, while every field operation was a
    16-step scan: at n = 2^14, c = 7 / 8 / 9 / 10 / 11 read 0.345 / 0.309 /
    0.293 / 0.280 / 0.309 s; at 2^15, c = 8 / 9 / 10 read 0.427 / 0.398 /
    0.375; at 2^16, c = 8 / 9 / 10 / 11 read 0.685 / 0.636 / 0.594 / 0.608:
    a window cost ~9 ms whatever its buckets (the carry chains' steps at
    its ten narrow levels), so fewer, wider windows won and the whole class
    took 10. PR 36, chip call 2, with the carries resolved in one pass: at
    2^14, c = 8 / 9 / 10 read 0.0916 / 0.0924 / 0.0991 s; at 2^15, 0.1665 /
    0.1615 / 0.1614; at 2^16, 0.3455 / 0.3299 / 0.3141. A window is now
    ~0.14 us an insertion and ~0.09 us an addition of the emission tree
    (14 x 2^c a window) on top of ~0.5 ms, which is the count of additions'
    own answer: 8 under 2^15, 10 from there (at 2^15 itself 9 and 10 tie).
    Every other entry (signed, n >= 2^18, n < 2^12) dates from XLA:CPU
    sweeps and has not run on the chip. With signed digits the bucket array
    is 2^(c-1)+1, so the emission term that caps c relaxes by one bucket-
    doubling and each size class affords a larger window (pinned by
    tests/test_msm_modes.py). SPECTRE_MSM_WINDOW overrides the whole table
    (the sweep's knob; 1..13, see window_override)."""
    ov = window_override()
    if ov is not None:
        return ov
    if signed:
        if n >= 1 << 18:
            return 13
        if n >= 1 << 12:
            return 11
        if n >= 1 << 7:
            return 8
        return 5
    if n >= 1 << 18:
        return 13
    if n >= 1 << 15:
        return 10
    if n >= 1 << 12:
        return 8
    if n >= 1 << 7:
        return 7
    return 4


def default_window_fixed(n: int) -> int:
    """Window size for the fixed-base path (n = expanded point count).

    The reduction shape matches the signed path window-for-window (the
    table removes doubling/combine work, not reduction work), so the
    signed tuning table applies; table MEMORY scales with nwin*n, which
    the larger signed windows also help."""
    return default_window(n, signed=True)


def msm(points, scalars, c: int | None = None, mode: str | None = None,
        base_key=None):
    """Full MSM on one device. points [n,3,16] proj Montgomery
    (ec.encode_points), scalars [n,16] standard limbs
    (limbs.ints_to_limbs16). Returns [3,16].

    mode defaults to SPECTRE_MSM_MODE (msm_mode()); base_key names a fixed
    base (SRS digest) for the fixed-mode table cache."""
    mode = mode if mode is not None else msm_mode()
    if mode not in MSM_MODES:
        raise ValueError(f"unknown MSM mode {mode!r}")
    n = points.shape[0]
    if mode == "vanilla":
        if c is None:
            c = default_window(n)
        # every window, through the program the commit path runs
        full = np.int32(window_count(254, c))
        return combine_windows(msm_windows(points, scalars, c, full), c)

    from . import glv
    nbits = glv.glv_bits()
    if mode == "fixed":
        cf = c if c is not None else default_window_fixed(2 * n)
        if _degrade_fixed(n, cf, nbits):
            mode = "glv+signed"
        else:
            nwin = (nbits + cf) // cf
            sc2, neg = _glv_scalars_device(scalars)
            table = fixed_base_table(points, cf, nwin, base_key=base_key)
            return msm_fixed_run(table, sc2, neg, cf, nbits)

    pts2, sc2, neg = glv_split(points, scalars)
    if mode == "glv":
        if c is None:
            c = default_window(2 * n)
        wins = msm_windows_bits(_apply_sign(pts2, neg), sc2, c, nbits)
    else:  # glv+signed
        if c is None:
            c = default_window(2 * n, signed=True)
        wins = msm_windows_signed(pts2, sc2, neg, c, nbits)
    return combine_windows(wins, c)


@functools.partial(jax.jit, static_argnums=(1,))
def combine_windows_batch(window_sums_batch, c: int):
    """[m, nwin, 3, 16] -> [m, 3, 16]: `combine_windows`' chain of
    doublings once, at width m. The chain is nwin x (c + 1) dependent
    additions whatever m is, and on the chip 16 columns cost less than one
    (PERF.md section 5)."""
    return jax.vmap(lambda w: combine_windows.__wrapped__(w, c))(
        window_sums_batch)


# Columns a run of the one-chip commit path combines and converts together
# (plonk/backend.py `TpuBackend._msm_chunks`): the prover's COMMIT_CHUNK.
CHUNK_WIDTH = 16


@functools.partial(jax.jit, static_argnums=(1,))
def pad_window_sums(window_sums: tuple, width: int):
    """<= width arrays [nwin, 3, 16] -> [width, nwin, 3, 16], the missing
    columns identity window sums (they combine to the identity). Jitted:
    one small program for each count of columns a prove sends, where the
    same few operations run eagerly are a dozen."""
    stack = jnp.stack(window_sums)
    short = width - stack.shape[0]
    if short:
        stack = jnp.concatenate(
            [stack, ec.inf_point((short, stack.shape[1]))], axis=0)
    return stack

// spectre_host: C++ host-side math for spectre_tpu.
//
// Role (SURVEY.md §2b): the native component of the stack — the CPU reference
// implementation of BN254 field arithmetic (N1), Pippenger MSM (N2) and NTT
// (N3) that (a) serves as the measured CPU baseline for bench.py and (b) is
// the exact oracle the JAX/Pallas device kernels are tested against. Where the
// reference uses Rust (`halo2curves-axiom`, halo2's rayon Pippenger/FFT), this
// is an independent C++ implementation: 4x64-bit limbs, CIOS Montgomery
// multiplication, jacobian coordinates.
//
// Exported ABI is C (ctypes-friendly): field elements are 4 little-endian
// uint64 limbs in standard (non-Montgomery) form at the boundary; points are
// affine (x, y) limb pairs, infinity flagged separately.

#include <algorithm>
#include <array>
#include <atomic>
#include <cstdint>
#include <cstring>
#include <map>
#include <memory>
#include <mutex>
#include <thread>
#include <vector>

using u64 = uint64_t;
using u128 = unsigned __int128;

namespace {

struct Fp {
  u64 v[4];
};

struct FpCtx {
  u64 mod[4];
  u64 n0inv;  // -mod^{-1} mod 2^64
  Fp r2;      // R^2 mod p, R = 2^256
  Fp one;     // R mod p (Montgomery 1)
};

// BN254 base field (G1 coordinates)
constexpr u64 FQ_MOD[4] = {0x3c208c16d87cfd47ULL, 0x97816a916871ca8dULL,
                           0xb85045b68181585dULL, 0x30644e72e131a029ULL};
// BN254 scalar field (NTT / witness scalars)
constexpr u64 FR_MOD[4] = {0x43e1f593f0000001ULL, 0x2833e84879b97091ULL,
                           0xb85045b68181585dULL, 0x30644e72e131a029ULL};

FpCtx g_fq, g_fr;

inline bool ge(const u64* a, const u64* b) {
  for (int i = 3; i >= 0; --i) {
    if (a[i] != b[i]) return a[i] > b[i];
  }
  return true;
}

inline void sub_nocheck(u64* out, const u64* a, const u64* b) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a[i] - b[i] - (u64)borrow;
    out[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

inline void cond_sub_mod(u64* t, const FpCtx& C) {
  if (ge(t, C.mod)) sub_nocheck(t, t, C.mod);
}

inline void fp_add(Fp& out, const Fp& a, const Fp& b, const FpCtx& C) {
  u128 carry = 0;
  u64 t[5];
  for (int i = 0; i < 4; ++i) {
    u128 s = (u128)a.v[i] + b.v[i] + (u64)carry;
    t[i] = (u64)s;
    carry = s >> 64;
  }
  t[4] = (u64)carry;
  if (t[4] || ge(t, C.mod)) sub_nocheck(t, t, C.mod);
  std::memcpy(out.v, t, 32);
}

inline void fp_sub(Fp& out, const Fp& a, const Fp& b, const FpCtx& C) {
  u128 borrow = 0;
  u64 t[4];
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - b.v[i] - (u64)borrow;
    t[i] = (u64)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  if (borrow) {
    u128 carry = 0;
    for (int i = 0; i < 4; ++i) {
      u128 s = (u128)t[i] + C.mod[i] + (u64)carry;
      t[i] = (u64)s;
      carry = s >> 64;
    }
  }
  std::memcpy(out.v, t, 32);
}

// CIOS Montgomery multiplication (Acar): out = a*b*R^{-1} mod p
inline void fp_mul(Fp& out, const Fp& a, const Fp& b, const FpCtx& C) {
  u64 t[6] = {0, 0, 0, 0, 0, 0};
  for (int i = 0; i < 4; ++i) {
    u64 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a.v[j] * b.v[i] + t[j] + carry;
      t[j] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    u128 cur = (u128)t[4] + carry;
    t[4] = (u64)cur;
    t[5] = (u64)(cur >> 64);

    u64 m = t[0] * C.n0inv;
    cur = (u128)t[0] + (u128)m * C.mod[0];
    carry = (u64)(cur >> 64);
    for (int j = 1; j < 4; ++j) {
      cur = (u128)t[j] + (u128)m * C.mod[j] + carry;
      t[j - 1] = (u64)cur;
      carry = (u64)(cur >> 64);
    }
    cur = (u128)t[4] + carry;
    t[3] = (u64)cur;
    t[4] = t[5] + (u64)(cur >> 64);
  }
  cond_sub_mod(t, C);
  std::memcpy(out.v, t, 32);
}

inline void fp_sqr(Fp& out, const Fp& a, const FpCtx& C) { fp_mul(out, a, a, C); }

inline bool fp_is_zero(const Fp& a) {
  return (a.v[0] | a.v[1] | a.v[2] | a.v[3]) == 0;
}

inline bool fp_eq(const Fp& a, const Fp& b) {
  return std::memcmp(a.v, b.v, 32) == 0;
}

inline void to_mont(Fp& out, const Fp& a, const FpCtx& C) { fp_mul(out, a, C.r2, C); }
inline void from_mont(Fp& out, const Fp& a, const FpCtx& C) {
  Fp one = {{1, 0, 0, 0}};
  fp_mul(out, a, one, C);
}

// out = a^e (Montgomery in/out), e standard 4-limb little-endian
void fp_pow(Fp& out, const Fp& a, const u64* e, const FpCtx& C) {
  Fp result = C.one;
  Fp base = a;
  for (int limb = 0; limb < 4; ++limb) {
    u64 bits = e[limb];
    for (int i = 0; i < 64; ++i) {
      if (bits & 1) fp_mul(result, result, base, C);
      fp_sqr(base, base, C);
      bits >>= 1;
    }
  }
  out = result;
}

void fp_inv(Fp& out, const Fp& a, const FpCtx& C) {
  u64 e[4];
  std::memcpy(e, C.mod, 32);
  e[0] -= 2;  // p is odd, no borrow
  fp_pow(out, a, e, C);
}

void ctx_init(FpCtx& C, const u64* mod) {
  std::memcpy(C.mod, mod, 32);
  // n0inv = -mod^{-1} mod 2^64 via Newton iteration
  u64 inv = 1;
  for (int i = 0; i < 63; ++i) inv *= 2 - mod[0] * inv;
  C.n0inv = ~inv + 1;
  // R mod p by long division-free doubling: start at 1, double 256 times
  Fp r = {{1, 0, 0, 0}};
  for (int i = 0; i < 256; ++i) fp_add(r, r, r, C);  // fp_add reduces mod p
  C.one = r;
  // R^2 mod p: double one 256 more times
  Fp r2 = r;
  for (int i = 0; i < 256; ++i) fp_add(r2, r2, r2, C);
  C.r2 = r2;
}

// fn(i) for every i in [0, count), on up to `nthreads` threads (the calling
// one among them), each taking the next i from a shared counter so that
// work units of unequal size still balance.
template <class Fn>
void parallel_for(size_t count, int nthreads, Fn&& fn) {
  const size_t t_n = std::min<size_t>(nthreads > 1 ? nthreads : 1, count);
  if (t_n <= 1) {
    for (size_t i = 0; i < count; ++i) fn(i);
    return;
  }
  std::atomic<size_t> next{0};
  auto work = [&]() {
    for (size_t i; (i = next.fetch_add(1)) < count;) fn(i);
  };
  std::vector<std::thread> pool;
  for (size_t t = 1; t < t_n; ++t) pool.emplace_back(work);
  work();
  for (auto& th : pool) th.join();
}

// ---------------------------------------------------------------------------
// G1 jacobian arithmetic over Fq (a = 0, b = 3); Z == 0 means infinity.
// ---------------------------------------------------------------------------

struct G1 {
  Fp x, y, z;  // Montgomery form
};

inline void g1_set_inf(G1& p) { std::memset(&p, 0, sizeof(G1)); }
inline bool g1_is_inf(const G1& p) { return fp_is_zero(p.z); }

// dbl-2009-l
void g1_dbl(G1& out, const G1& p) {
  if (g1_is_inf(p)) {
    out = p;
    return;
  }
  const FpCtx& C = g_fq;
  Fp A, B, Cc, D, E, F, t0, t1;
  fp_sqr(A, p.x, C);
  fp_sqr(B, p.y, C);
  fp_sqr(Cc, B, C);
  fp_add(t0, p.x, B, C);
  fp_sqr(t0, t0, C);
  fp_sub(t0, t0, A, C);
  fp_sub(t0, t0, Cc, C);
  fp_add(D, t0, t0, C);
  fp_add(E, A, A, C);
  fp_add(E, E, A, C);
  fp_sqr(F, E, C);
  G1 r;
  fp_add(t0, D, D, C);
  fp_sub(r.x, F, t0, C);
  fp_sub(t0, D, r.x, C);
  fp_mul(t0, E, t0, C);
  fp_add(t1, Cc, Cc, C);
  fp_add(t1, t1, t1, C);
  fp_add(t1, t1, t1, C);
  fp_sub(r.y, t0, t1, C);
  fp_mul(r.z, p.y, p.z, C);
  fp_add(r.z, r.z, r.z, C);
  out = r;
}

// add-2007-bl (general jacobian add)
void g1_add(G1& out, const G1& p, const G1& q) {
  if (g1_is_inf(p)) {
    out = q;
    return;
  }
  if (g1_is_inf(q)) {
    out = p;
    return;
  }
  const FpCtx& C = g_fq;
  Fp z1z1, z2z2, u1, u2, s1, s2, h, i, j, rr, v, t0, t1;
  fp_sqr(z1z1, p.z, C);
  fp_sqr(z2z2, q.z, C);
  fp_mul(u1, p.x, z2z2, C);
  fp_mul(u2, q.x, z1z1, C);
  fp_mul(t0, q.z, z2z2, C);
  fp_mul(s1, p.y, t0, C);
  fp_mul(t0, p.z, z1z1, C);
  fp_mul(s2, q.y, t0, C);
  fp_sub(h, u2, u1, C);
  fp_sub(rr, s2, s1, C);
  if (fp_is_zero(h)) {
    if (fp_is_zero(rr)) {
      g1_dbl(out, p);
      return;
    }
    g1_set_inf(out);
    return;
  }
  fp_add(rr, rr, rr, C);  // r = 2(S2-S1)
  fp_add(i, h, h, C);
  fp_sqr(i, i, C);  // I = (2H)^2
  fp_mul(j, h, i, C);
  fp_mul(v, u1, i, C);
  G1 r;
  fp_sqr(r.x, rr, C);
  fp_sub(r.x, r.x, j, C);
  fp_add(t0, v, v, C);
  fp_sub(r.x, r.x, t0, C);
  fp_sub(t0, v, r.x, C);
  fp_mul(t0, rr, t0, C);
  fp_mul(t1, s1, j, C);
  fp_add(t1, t1, t1, C);
  fp_sub(r.y, t0, t1, C);
  fp_add(t0, p.z, q.z, C);
  fp_sqr(t0, t0, C);
  fp_sub(t0, t0, z1z1, C);
  fp_sub(t0, t0, z2z2, C);
  fp_mul(r.z, t0, h, C);
  out = r;
}

// mixed add: q affine (Montgomery coords), q_inf flag; madd-2007-bl
void g1_madd(G1& out, const G1& p, const Fp& qx, const Fp& qy) {
  if (g1_is_inf(p)) {
    out.x = qx;
    out.y = qy;
    out.z = g_fq.one;
    return;
  }
  const FpCtx& C = g_fq;
  Fp z1z1, u2, s2, h, hh, i, j, rr, v, t0, t1;
  fp_sqr(z1z1, p.z, C);
  fp_mul(u2, qx, z1z1, C);
  fp_mul(t0, p.z, z1z1, C);
  fp_mul(s2, qy, t0, C);
  fp_sub(h, u2, p.x, C);
  fp_sub(rr, s2, p.y, C);
  if (fp_is_zero(h)) {
    if (fp_is_zero(rr)) {
      g1_dbl(out, p);
      return;
    }
    g1_set_inf(out);
    return;
  }
  fp_add(rr, rr, rr, C);  // r = 2(S2-Y1)
  fp_sqr(hh, h, C);
  fp_add(i, hh, hh, C);
  fp_add(i, i, i, C);  // I = 4 HH
  fp_mul(j, h, i, C);
  fp_mul(v, p.x, i, C);
  G1 r;
  fp_sqr(r.x, rr, C);
  fp_sub(r.x, r.x, j, C);
  fp_add(t0, v, v, C);
  fp_sub(r.x, r.x, t0, C);
  fp_sub(t0, v, r.x, C);
  fp_mul(t0, rr, t0, C);
  fp_mul(t1, p.y, j, C);
  fp_add(t1, t1, t1, C);
  fp_sub(r.y, t0, t1, C);
  fp_add(t0, p.z, h, C);
  fp_sqr(t0, t0, C);
  fp_sub(t0, t0, z1z1, C);
  fp_sub(r.z, t0, hh, C);
  out = r;
}

void g1_to_affine_inner(Fp& ox, Fp& oy, const G1& p) {
  const FpCtx& C = g_fq;
  Fp zinv, zinv2, zinv3;
  fp_inv(zinv, p.z, C);
  fp_sqr(zinv2, zinv, C);
  fp_mul(zinv3, zinv2, zinv, C);
  fp_mul(ox, p.x, zinv2, C);
  fp_mul(oy, p.y, zinv3, C);
}

}  // namespace

// ---------------------------------------------------------------------------
// exported C ABI
// ---------------------------------------------------------------------------

extern "C" {

void spectre_init() {
  static bool done = false;
  if (!done) {
    ctx_init(g_fq, FQ_MOD);
    ctx_init(g_fr, FR_MOD);
    done = true;
  }
}

// ---- batched field ops (standard form at the boundary); field: 0=Fq, 1=Fr ----

static const FpCtx& pick(int field) {
  spectre_init();
  return field ? g_fr : g_fq;
}

void fp_mul_batch(int field, const u64* a, const u64* b, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  for (size_t i = 0; i < n; ++i) {
    Fp am, bm, r;
    std::memcpy(am.v, a + 4 * i, 32);
    std::memcpy(bm.v, b + 4 * i, 32);
    to_mont(am, am, C);
    to_mont(bm, bm, C);
    fp_mul(r, am, bm, C);
    from_mont(r, r, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

void fp_add_batch(int field, const u64* a, const u64* b, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  for (size_t i = 0; i < n; ++i) {
    Fp am, bm, r;
    std::memcpy(am.v, a + 4 * i, 32);
    std::memcpy(bm.v, b + 4 * i, 32);
    fp_add(r, am, bm, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

void fp_sub_batch(int field, const u64* a, const u64* b, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  for (size_t i = 0; i < n; ++i) {
    Fp am, bm, r;
    std::memcpy(am.v, a + 4 * i, 32);
    std::memcpy(bm.v, b + 4 * i, 32);
    fp_sub(r, am, bm, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

void fp_inv_batch(int field, const u64* a, u64* out, size_t n) {
  // Montgomery batch-inversion trick: one fp_inv for the whole batch.
  const FpCtx& C = pick(field);
  std::vector<Fp> vals(n), prefix(n);
  Fp acc = C.one;
  for (size_t i = 0; i < n; ++i) {
    std::memcpy(vals[i].v, a + 4 * i, 32);
    to_mont(vals[i], vals[i], C);
    prefix[i] = acc;
    if (!fp_is_zero(vals[i])) fp_mul(acc, acc, vals[i], C);
  }
  Fp inv_acc;
  fp_inv(inv_acc, acc, C);
  for (size_t i = n; i-- > 0;) {
    Fp r;
    if (fp_is_zero(vals[i])) {
      std::memset(out + 4 * i, 0, 32);  // inv(0) := 0 convention
      continue;
    }
    fp_mul(r, inv_acc, prefix[i], C);
    fp_mul(inv_acc, inv_acc, vals[i], C);
    from_mont(r, r, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

void fp_pow_single(int field, const u64* a, const u64* e, u64* out) {
  const FpCtx& C = pick(field);
  Fp am, r;
  std::memcpy(am.v, a, 32);
  to_mont(am, am, C);
  fp_pow(r, am, e, C);
  from_mont(r, r, C);
  std::memcpy(out, r.v, 32);
}

// ---- NTT over Fr (in place, standard form at the boundary) ----
// omega must be a primitive 2^logn-th root of unity.

// Twiddle plan: all stage twiddles in Montgomery form, stage with half-width
// h occupying entries [h-1, 2h-1) — total n-1 entries. A prove runs ~90
// same-(omega, size) NTTs over the extended domain (one per committed
// column, `prover.py::_quotient_host`), so the table is built once (~n muls)
// and every butterfly thereafter costs ONE mul instead of two (the serial
// `w *= wm` chain per block is gone). Same arithmetic, bit-identical output.
struct NttPlan {
  std::vector<Fp> tw;
};

std::mutex g_ntt_plan_mu;
std::map<std::array<u64, 5>, std::shared_ptr<NttPlan>> g_ntt_plans;

std::shared_ptr<NttPlan> ntt_plan(size_t logn, const Fp& omega_mont,
                                  const FpCtx& C) {
  std::array<u64, 5> key{omega_mont.v[0], omega_mont.v[1], omega_mont.v[2],
                         omega_mont.v[3], (u64)logn};
  {
    std::lock_guard<std::mutex> g(g_ntt_plan_mu);
    auto it = g_ntt_plans.find(key);
    if (it != g_ntt_plans.end()) return it->second;
  }
  const size_t n = (size_t)1 << logn;
  auto plan = std::make_shared<NttPlan>();
  plan->tw.resize(n - 1);
  for (size_t m = 2; m <= n; m <<= 1) {
    const size_t h = m >> 1;
    Fp wm = omega_mont;
    for (size_t k = m; k < n; k <<= 1) fp_sqr(wm, wm, C);  // omega^(n/m)
    Fp w = C.one;
    Fp* row = plan->tw.data() + (h - 1);
    for (size_t j = 0; j < h; ++j) {
      row[j] = w;
      fp_mul(w, w, wm, C);
    }
  }
  std::lock_guard<std::mutex> g(g_ntt_plan_mu);
  // the prover uses 4 (omega, size) pairs per circuit degree (fwd/inv x
  // base/extended); bound the cache, but evict ONE entry — clear() would
  // wipe the hot set whenever a service rotates through 3+ degrees and
  // re-pay the plan build ~90x per prove
  if (g_ntt_plans.size() > 12) g_ntt_plans.erase(g_ntt_plans.begin());
  g_ntt_plans[key] = plan;
  return plan;
}

void fr_ntt(u64* data, size_t logn, const u64* omega_std) {
  spectre_init();
  const FpCtx& C = g_fr;
  const size_t n = (size_t)1 << logn;
  // load to Montgomery
  std::vector<Fp> a(n);
  for (size_t i = 0; i < n; ++i) {
    std::memcpy(a[i].v, data + 4 * i, 32);
    to_mont(a[i], a[i], C);
  }
  // bit-reverse permutation
  for (size_t i = 1, j = 0; i < n; ++i) {
    size_t bit = n >> 1;
    for (; j & bit; bit >>= 1) j ^= bit;
    j ^= bit;
    if (i < j) std::swap(a[i], a[j]);
  }
  Fp omega;
  std::memcpy(omega.v, omega_std, 32);
  to_mont(omega, omega, C);
  auto plan = ntt_plan(logn, omega, C);
  const Fp* tw = plan->tw.data();
  for (size_t m = 2; m <= n; m <<= 1) {
    const size_t h = m >> 1;
    const Fp* wrow = tw + (h - 1);
    for (size_t start = 0; start < n; start += m) {
      Fp* lo = a.data() + start;
      Fp* hi = lo + h;
      for (size_t j = 0; j < h; ++j) {
        Fp t, u;
        fp_mul(t, hi[j], wrow[j], C);
        u = lo[j];
        fp_add(lo[j], u, t, C);
        fp_sub(hi[j], u, t, C);
      }
    }
  }
  for (size_t i = 0; i < n; ++i) {
    Fp r;
    from_mont(r, a[i], C);
    std::memcpy(data + 4 * i, r.v, 32);
  }
}

// ---- Pippenger MSM over G1 ----
// points: n * 8 limbs (x,y affine standard form; (0,0) = infinity, skipped)
// scalars: n * 4 limbs standard form
// out: 8 limbs affine + is_inf flag

static inline unsigned window_of(const u64* s, unsigned w, unsigned c) {
  unsigned bit = w * c;
  unsigned limb = bit >> 6, off = bit & 63;
  u64 v = s[limb] >> off;
  if (off + c > 64 && limb + 1 < 4) v |= s[limb + 1] << (64 - off);
  return (unsigned)(v & (((u64)1 << c) - 1));
}

void g1_msm(const u64* points, const u64* scalars, size_t n, int nthreads,
            u64* out_xy, int* out_inf) {
  spectre_init();
  const FpCtx& C = g_fq;
  unsigned c = 13;
  if (n < (1u << 12)) c = 8;
  if (n < (1u << 6)) c = 4;
  const unsigned nwin = (254 + c - 1) / c;
  const size_t nbuckets = ((size_t)1 << c) - 1;

  // pre-convert points to Montgomery affine
  std::vector<Fp> px(n), py(n);
  std::vector<char> pinf(n);
  for (size_t i = 0; i < n; ++i) {
    Fp x, y;
    std::memcpy(x.v, points + 8 * i, 32);
    std::memcpy(y.v, points + 8 * i + 4, 32);
    pinf[i] = fp_is_zero(x) && fp_is_zero(y);
    to_mont(px[i], x, C);
    to_mont(py[i], y, C);
  }

  std::vector<G1> win_res(nwin);
  auto do_window = [&](unsigned w) {
    std::vector<G1> buckets(nbuckets);
    for (auto& b : buckets) g1_set_inf(b);
    for (size_t i = 0; i < n; ++i) {
      if (pinf[i]) continue;
      unsigned idx = window_of(scalars + 4 * i, w, c);
      if (idx) g1_madd(buckets[idx - 1], buckets[idx - 1], px[i], py[i]);
    }
    G1 sum, acc;
    g1_set_inf(sum);
    g1_set_inf(acc);
    for (size_t b = nbuckets; b-- > 0;) {
      g1_add(sum, sum, buckets[b]);
      g1_add(acc, acc, sum);
    }
    win_res[w] = acc;
  };

  if (nthreads > 1) {
    std::vector<std::thread> pool;
    for (int t = 0; t < nthreads; ++t) {
      pool.emplace_back([&, t]() {
        for (unsigned w = t; w < nwin; w += nthreads) do_window(w);
      });
    }
    for (auto& th : pool) th.join();
  } else {
    for (unsigned w = 0; w < nwin; ++w) do_window(w);
  }

  G1 res;
  g1_set_inf(res);
  for (unsigned w = nwin; w-- > 0;) {
    for (unsigned d = 0; d < c && !g1_is_inf(res); ++d) g1_dbl(res, res);
    g1_add(res, res, win_res[w]);
  }
  if (g1_is_inf(res)) {
    *out_inf = 1;
    std::memset(out_xy, 0, 64);
    return;
  }
  *out_inf = 0;
  Fp ax, ay;
  g1_to_affine_inner(ax, ay, res);
  from_mont(ax, ax, C);
  from_mont(ay, ay, C);
  std::memcpy(out_xy, ax.v, 32);
  std::memcpy(out_xy + 4, ay.v, 32);
}

// ---- batched G1 ops for testing device EC kernels ----

// out = a + b where a, b, out are affine standard-form; (0,0) = infinity
void g1_add_affine_batch(const u64* a, const u64* b, u64* out, size_t n) {
  spectre_init();
  const FpCtx& C = g_fq;
  for (size_t i = 0; i < n; ++i) {
    Fp ax, ay, bx, by;
    std::memcpy(ax.v, a + 8 * i, 32);
    std::memcpy(ay.v, a + 8 * i + 4, 32);
    std::memcpy(bx.v, b + 8 * i, 32);
    std::memcpy(by.v, b + 8 * i + 4, 32);
    bool ainf = fp_is_zero(ax) && fp_is_zero(ay);
    bool binf = fp_is_zero(bx) && fp_is_zero(by);
    G1 pa;
    if (ainf) {
      g1_set_inf(pa);
    } else {
      to_mont(pa.x, ax, C);
      to_mont(pa.y, ay, C);
      pa.z = C.one;
    }
    if (!binf) {
      Fp bxm, bym;
      to_mont(bxm, bx, C);
      to_mont(bym, by, C);
      g1_madd(pa, pa, bxm, bym);
    }
    if (g1_is_inf(pa)) {
      std::memset(out + 8 * i, 0, 64);
    } else {
      Fp ox, oy;
      g1_to_affine_inner(ox, oy, pa);
      from_mont(ox, ox, C);
      from_mont(oy, oy, C);
      std::memcpy(out + 8 * i, ox.v, 32);
      std::memcpy(out + 8 * i + 4, oy.v, 32);
    }
  }
}

// Horner evaluation: out = sum a[i] x^i (a standard form, length n)
void fp_horner(int field, const u64* a, const u64* x, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp xm, acc;
  std::memcpy(xm.v, x, 32);
  to_mont(xm, xm, C);
  std::memset(acc.v, 0, 32);
  for (size_t i = n; i-- > 0;) {
    Fp ai;
    std::memcpy(ai.v, a + 4 * i, 32);
    to_mont(ai, ai, C);
    fp_mul(acc, acc, xm, C);
    fp_add(acc, acc, ai, C);
  }
  from_mont(acc, acc, C);
  std::memcpy(out, acc.v, 32);
}

// Polynomial k (lens[k] standard-form coefficients at polys[k]) evaluated at
// each of its points xs[offs[k]] .. xs[offs[k + 1] - 1], into the same rows
// of out: ONE pass over a polynomial's coefficients for all of its points.
// Horner runs in standard form with each point in Montgomery form, since
// mont(a, s*R) = a*s: one product an element a point, and neither the
// coefficients nor the accumulators are converted. Coefficients are
// canonical (< p). A point's Horner is split into kChains independent
// chains, p(x) = sum_r x^r P_r(x^kChains) with P_r the coefficients r mod
// kChains, so that the products of a row do not wait on one another. The
// polynomials are the threads' work units.
void fp_eval_many(int field, const u64* const* polys, const size_t* lens,
                  size_t npolys, const u64* xs, const size_t* offs, u64* out,
                  int nthreads) {
  constexpr size_t kChains = 4;
  const FpCtx& C = pick(field);
  parallel_for(npolys, nthreads, [&](size_t k) {
    const size_t m = offs[k + 1] - offs[k], n = lens[k];
    std::vector<Fp> xm(m), ym(m), acc(m * kChains);
    for (size_t j = 0; j < m; ++j) {
      std::memcpy(xm[j].v, xs + 4 * (offs[k] + j), 32);
      to_mont(xm[j], xm[j], C);
      ym[j] = xm[j];
      for (size_t c = 1; c < kChains; c <<= 1) fp_sqr(ym[j], ym[j], C);
    }
    std::memset(acc.data(), 0, sizeof(Fp) * acc.size());
    const u64* a = polys[k];
    for (size_t b = (n + kChains - 1) / kChains; b-- > 0;) {
      Fp ai[kChains] = {};
      for (size_t r = 0; r < kChains && b * kChains + r < n; ++r)
        std::memcpy(ai[r].v, a + 4 * (b * kChains + r), 32);
      for (size_t j = 0; j < m; ++j) {
        Fp* aj = acc.data() + j * kChains;
        for (size_t r = 0; r < kChains; ++r) {
          fp_mul(aj[r], aj[r], ym[j], C);
          fp_add(aj[r], aj[r], ai[r], C);
        }
      }
    }
    for (size_t j = 0; j < m; ++j) {  // (P_3 x + P_2) x + P_1) x + P_0
      const Fp* aj = acc.data() + j * kChains;
      Fp v = aj[kChains - 1];
      for (size_t r = kChains - 1; r-- > 0;) {
        fp_mul(v, v, xm[j], C);
        fp_add(v, v, aj[r], C);
      }
      std::memcpy(out + 4 * (offs[k] + j), v.v, 32);
    }
  });
}

// sum of all elements
void fp_sum(int field, const u64* a, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp acc;
  std::memset(acc.v, 0, 32);
  for (size_t i = 0; i < n; ++i) {
    Fp ai;
    std::memcpy(ai.v, a + 4 * i, 32);
    fp_add(acc, acc, ai, C);
  }
  std::memcpy(out, acc.v, 32);
}

}  // extern "C"

namespace {

// Batch-normalize jacobian points to affine standard form [n, 8] limbs:
// one Montgomery batch inversion of z, skipping infinity points (z == 0
// would otherwise poison the whole product); infinity -> (0, 0).
void g1_batch_to_affine(const std::vector<G1>& jac, u64* out) {
  const FpCtx& C = g_fq;
  const size_t n = jac.size();
  std::vector<Fp> prefix(n);
  Fp accp = C.one;
  for (size_t i = 0; i < n; ++i) {
    prefix[i] = accp;
    if (!fp_is_zero(jac[i].z)) fp_mul(accp, accp, jac[i].z, C);
  }
  Fp inv_acc;
  fp_inv(inv_acc, accp, C);
  for (size_t i = n; i-- > 0;) {
    if (fp_is_zero(jac[i].z)) {
      std::memset(out + 8 * i, 0, 64);
      continue;
    }
    Fp zinv, zinv2, zinv3, ax, ay;
    fp_mul(zinv, inv_acc, prefix[i], C);
    fp_mul(inv_acc, inv_acc, jac[i].z, C);
    fp_sqr(zinv2, zinv, C);
    fp_mul(zinv3, zinv2, zinv, C);
    fp_mul(ax, jac[i].x, zinv2, C);
    fp_mul(ay, jac[i].y, zinv3, C);
    from_mont(ax, ax, C);
    from_mont(ay, ay, C);
    std::memcpy(out + 8 * i, ax.v, 32);
    std::memcpy(out + 8 * i + 4, ay.v, 32);
  }
}

bool g1_load_affine(G1& p, const u64* xy) {
  Fp x, y;
  std::memcpy(x.v, xy, 32);
  std::memcpy(y.v, xy + 4, 32);
  if (fp_is_zero(x) && fp_is_zero(y)) {
    g1_set_inf(p);
    return false;
  }
  to_mont(p.x, x, g_fq);
  to_mont(p.y, y, g_fq);
  p.z = g_fq.one;
  return true;
}

// out[i] = scalars[i] * g for n standard-form scalars, by FIXED-BASE
// windowed multiplication: one shared table of g-multiples (256 / W windows
// x 2^W entries) turns every point into <= 256 / W additions, where a
// double-and-add a scalar is O(256) EC ops per point (which made 2^22+ SRS
// generation dominate setup wall-clock).
void fixed_base_mul_many(const u64* g_xy, const std::vector<Fp>& scalars,
                         u64* out) {
  const size_t n = scalars.size();
  G1 base;
  g1_load_affine(base, g_xy);

  // Window width from n (W must divide 64 so digits never straddle limbs).
  // Total adds ~ (256/W) * (2^W + n): the pure-add break-evens are n=224
  // (4->8) and n=65024 (8->16), but W=16 also means a 16x65536-entry table
  // (~100 MB) and ~1M precompute adds before any output — on a small-RAM
  // host that spike only pays off for multi-million-point SRS sizes, so the
  // 8->16 switch is held back to n >= 2^20.
  const int W = n <= 224 ? 4 : n < (1u << 20) ? 8 : 16;
  const int NW = 256 / W;
  const size_t TSZ = (size_t)1 << W;
  // table[j][d] = (d << (W*j)) * g ; entry 0 = infinity
  std::vector<G1> table((size_t)NW * TSZ);
  G1 wbase = base;                    // g * 2^(W*j)
  for (int j = 0; j < NW; ++j) {
    G1* row = table.data() + (size_t)j * TSZ;
    g1_set_inf(row[0]);
    row[1] = wbase;
    for (size_t d = 2; d < TSZ; ++d) g1_add(row[d], row[d - 1], wbase);
    if (j + 1 < NW) {
      wbase = row[TSZ - 1];
      g1_add(wbase, wbase, row[1]);   // g * 2^(W*(j+1))
    }
  }

  std::vector<G1> jac(n);
  for (size_t i = 0; i < n; ++i) {
    const Fp& s = scalars[i];
    G1 acc;
    g1_set_inf(acc);
    for (int j = 0; j < NW; ++j) {
      u64 d = (s.v[(j * W) / 64] >> ((j * W) % 64)) & (TSZ - 1);
      if (d) g1_add(acc, acc, table[(size_t)j * TSZ + d]);
    }
    jac[i] = acc;
  }
  g1_batch_to_affine(jac, out);
}

// p <- s * p for a standard-form scalar, 4-bit windows high to low
void g1_mul_var(G1& p, const Fp& s) {
  if (g1_is_inf(p)) return;
  G1 tab[16];
  g1_set_inf(tab[0]);
  tab[1] = p;
  for (int d = 2; d < 16; ++d) g1_add(tab[d], tab[d - 1], p);
  G1 acc;
  g1_set_inf(acc);
  for (int j = 63; j >= 0; --j) {
    for (int d = 0; d < 4; ++d) g1_dbl(acc, acc);
    unsigned dig = (unsigned)((s.v[j / 16] >> (4 * (j % 16))) & 15);
    if (dig) g1_add(acc, acc, tab[dig]);
  }
  p = acc;
}

}  // namespace

extern "C" {

// SRS generation: out[i] = tau^i * G, affine standard form [n, 8] limbs.
void g1_scalar_powers(const u64* g_xy, const u64* tau, size_t n, u64* out) {
  spectre_init();
  const FpCtx& Cr = g_fr;
  // scalar powers tau^i in Montgomery Fr, kept in standard form
  Fp tau_m;
  std::memcpy(tau_m.v, tau, 32);
  to_mont(tau_m, tau_m, Cr);
  Fp cur_s = Cr.one;                  // tau^0 (Montgomery)
  std::vector<Fp> scalars(n);
  for (size_t i = 0; i < n; ++i) {
    from_mont(scalars[i], cur_s, Cr);
    fp_mul(cur_s, cur_s, tau_m, Cr);
  }
  fixed_base_mul_many(g_xy, scalars, out);
}

// out[i] = scalars[i] * G for scalars GIVEN ([n, 4] limbs, standard form):
// g1_scalar_powers' table for a set-up that knows tau and wants another
// base than the powers (the Lagrange base L_i(tau) G).
void g1_fixed_base_mul(const u64* g_xy, const u64* scalars, size_t n,
                       u64* out) {
  spectre_init();
  std::vector<Fp> sc(n);
  std::memcpy(sc.data(), scalars, 32 * n);
  fixed_base_mul_many(g_xy, sc, out);
}

// Radix-2 FFT over G1, in place on affine standard-form points [2^logn, 8]
// (natural order in and out): out[i] = sum_j omega^(i j) P_j. The upstream's
// `g_to_lagrange` (its `best_fft` over curve points): with omega^-1 and a
// final scaling by 1/n, `scale_std`, it turns the powers tau^j G into the
// Lagrange base L_i(tau) G without tau. Each butterfly multiplies a POINT by
// a twiddle, 254 doublings: ~9 s at 2^14 on one thread, so the butterflies
// of a stage are split over `nthreads`. scale_std may be null (no scaling).
void g1_fft(u64* points, size_t logn, const u64* omega_std,
            const u64* scale_std, int nthreads) {
  spectre_init();
  const FpCtx& Cr = g_fr;
  const size_t n = (size_t)1 << logn;
  std::vector<G1> a(n);
  for (size_t i = 0; i < n; ++i) {
    size_t r = 0;
    for (size_t b = 0; b < logn; ++b) r |= ((i >> b) & 1) << (logn - 1 - b);
    g1_load_affine(a[r], points + 8 * i);
  }
  Fp om;
  std::memcpy(om.v, omega_std, 32);
  to_mont(om, om, Cr);
  // twiddles omega^j, j < n/2, standard form
  std::vector<Fp> tw(n / 2 ? n / 2 : 1);
  Fp cur = Cr.one;
  for (size_t j = 0; j < n / 2; ++j) {
    from_mont(tw[j], cur, Cr);
    fp_mul(cur, cur, om, Cr);
  }
  for (size_t s = 1; s <= logn; ++s) {
    const size_t m = (size_t)1 << s, half = m >> 1, stride = n / m;
    parallel_for(n / 2, nthreads, [&](size_t idx) {
      const size_t k = (idx / half) * m, j = idx % half;
      G1 t = a[k + j + half];
      if (j) g1_mul_var(t, tw[j * stride]);
      G1 u = a[k + j], nt = t;
      const Fp zero = {{0, 0, 0, 0}};
      fp_sub(nt.y, zero, t.y, g_fq);
      g1_add(a[k + j], u, t);
      g1_add(a[k + j + half], u, nt);
    });
  }
  if (scale_std) {
    Fp sc;
    std::memcpy(sc.v, scale_std, 32);
    parallel_for(n, nthreads, [&](size_t i) { g1_mul_var(a[i], sc); });
  }
  g1_batch_to_affine(a, points);
}

// pointwise ops used by the prover's quotient evaluation (standard form)

// out[i] = a[i] + s mod p. Representation-agnostic (add needs no Montgomery),
// one pass — replaces building an n-row constant array host-side just to
// call fp_add_batch (the expression contexts' add_const was doing exactly
// that, ~2s of Python marshalling per call at the k=21 extended domain).
void fp_add_scalar_batch(int field, const u64* a, const u64* s /*4 limbs*/,
                         u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp sv;
  std::memcpy(sv.v, s, 32);
  for (size_t i = 0; i < n; ++i) {
    Fp am, r;
    std::memcpy(am.v, a + 4 * i, 32);
    fp_add(r, am, sv, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

// out[i] = a[i]*s + b[i] mod p: the quotient's y-combination
// (acc = acc*y + e) as ONE pass instead of scale-then-add two-pass, and one
// product an element: with s in Montgomery form mont(a, s*R) = a*s, already
// in standard form.
void fp_axpy_batch(int field, const u64* a, const u64* s /*4 limbs*/,
                   const u64* b, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp sm;
  std::memcpy(sm.v, s, 32);
  to_mont(sm, sm, C);
  for (size_t i = 0; i < n; ++i) {
    Fp am, bm, r;
    std::memcpy(am.v, a + 4 * i, 32);
    std::memcpy(bm.v, b + 4 * i, 32);
    fp_mul(r, am, sm, C);
    fp_add(r, r, bm, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

// P_S = sum of scalars[k] * polys[k] over the entries k with sets[k] == S,
// for every S < nsets, into out ([nsets, n, 4], standard form). Entry k has
// lens[k] <= n coefficients; its rows past lens[k] add nothing, so a short
// polynomial needs no padded copy. One product an element an entry, by
// mont(a, s*R) = a*s with each scalar in Montgomery form. The rows are cut
// into blocks, the threads' work units; a block takes the entries in order.
void fp_combine_sets(int field, const u64* const* polys, const size_t* lens,
                     const u64* scalars, const size_t* sets, size_t nentries,
                     size_t nsets, size_t n, u64* out, int nthreads) {
  const FpCtx& C = pick(field);
  std::vector<Fp> sm(nentries);
  for (size_t k = 0; k < nentries; ++k) {
    std::memcpy(sm[k].v, scalars + 4 * k, 32);
    to_mont(sm[k], sm[k], C);
  }
  constexpr size_t kBlock = 1024;
  parallel_for((n + kBlock - 1) / kBlock, nthreads, [&](size_t b) {
    const size_t lo = b * kBlock, hi = std::min(n, lo + kBlock);
    for (size_t s = 0; s < nsets; ++s)
      std::memset(out + 4 * (s * n + lo), 0, 32 * (hi - lo));
    for (size_t k = 0; k < nentries; ++k) {
      const u64* a = polys[k];
      u64* dst = out + 4 * sets[k] * n;
      for (size_t i = lo, end = std::min(hi, lens[k]); i < end; ++i) {
        Fp ai, acc, r;
        std::memcpy(ai.v, a + 4 * i, 32);
        std::memcpy(acc.v, dst + 4 * i, 32);
        fp_mul(r, ai, sm[k], C);
        fp_add(acc, acc, r, C);
        std::memcpy(dst + 4 * i, acc.v, 32);
      }
    }
  });
}

void fp_scale_batch(int field, const u64* a, const u64* s /*4 limbs*/, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp sm;
  std::memcpy(sm.v, s, 32);
  to_mont(sm, sm, C);
  for (size_t i = 0; i < n; ++i) {
    Fp am, r;
    std::memcpy(am.v, a + 4 * i, 32);
    to_mont(am, am, C);
    fp_mul(r, am, sm, C);
    from_mont(r, r, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

// out[i] = x^i for i in [0, n)
void fp_powers(int field, const u64* x, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp xm, cur;
  std::memcpy(xm.v, x, 32);
  to_mont(xm, xm, C);
  cur = C.one;
  for (size_t i = 0; i < n; ++i) {
    Fp r;
    from_mont(r, cur, C);
    std::memcpy(out + 4 * i, r.v, 32);
    fp_mul(cur, cur, xm, C);
  }
}

// prefix products: out[i] = prod_{j<=i} a[j]
void fp_prefix_prod(int field, const u64* a, u64* out, size_t n) {
  const FpCtx& C = pick(field);
  Fp acc = C.one;
  for (size_t i = 0; i < n; ++i) {
    Fp am, r;
    std::memcpy(am.v, a + 4 * i, 32);
    to_mont(am, am, C);
    fp_mul(acc, acc, am, C);
    from_mont(r, acc, C);
    std::memcpy(out + 4 * i, r.v, 32);
  }
}

}  // extern "C"

// secp256k1 ECDSA batch verification — C++ CPU engine.
//
// The reference consumes libsecp256k1 (C) through haskoin-core
// (reference /root/reference/stack.yaml:5,9; SURVEY.md C9).  This is the
// framework's native CPU equivalent: the single-core baseline the TPU kernel
// is benchmarked against, and the small-batch fallback path of
// tpunode/verify/engine.py.  Written from scratch: 4x64-bit limb field
// arithmetic with __int128 products, Jacobian points (a = 0), and interleaved
// 4-bit fixed-window double-and-add (Shamir's trick) for u1*G + u2*Q.
//
// Exposed C ABI (ctypes): secp_verify_batch().

#include <cstdint>
#include <cstring>

namespace {

typedef unsigned __int128 u128;

// ---------- 256-bit field element, little-endian u64 limbs ----------

struct Fe {
  uint64_t v[4];
};

// p = 2^256 - 0x1000003D1
constexpr uint64_t P0 = 0xFFFFFFFEFFFFFC2FULL;
constexpr uint64_t P1 = 0xFFFFFFFFFFFFFFFFULL;
constexpr uint64_t P2 = 0xFFFFFFFFFFFFFFFFULL;
constexpr uint64_t P3 = 0xFFFFFFFFFFFFFFFFULL;
constexpr uint64_t PC = 0x1000003D1ULL;  // 2^256 mod p

// n = group order
constexpr uint64_t N0 = 0xBFD25E8CD0364141ULL;
constexpr uint64_t N1 = 0xBAAEDCE6AF48A03BULL;
constexpr uint64_t N2 = 0xFFFFFFFFFFFFFFFEULL;
constexpr uint64_t N3 = 0xFFFFFFFFFFFFFFFFULL;

inline bool ge(const Fe &a, const uint64_t m[4]) {
  for (int i = 3; i >= 0; --i) {
    if (a.v[i] > m[i]) return true;
    if (a.v[i] < m[i]) return false;
  }
  return true;  // equal
}

inline void sub_mod_raw(Fe &a, const uint64_t m[4]) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - m[i] - (uint64_t)borrow;
    a.v[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
}

inline bool is_zero(const Fe &a) {
  return (a.v[0] | a.v[1] | a.v[2] | a.v[3]) == 0;
}

inline bool fe_eq(const Fe &a, const Fe &b) {
  return a.v[0] == b.v[0] && a.v[1] == b.v[1] && a.v[2] == b.v[2] &&
         a.v[3] == b.v[3];
}

struct Mod {
  uint64_t m[4];   // modulus
  uint64_t fold;   // 2^256 mod m (single limb for both p and n folds)
  uint64_t fold1;  // second limb of 2^256 mod m (n needs 3 limbs; see below)
  uint64_t fold2;
};

// 2^256 mod n = 2^256 - n  (since 2^255 < n < 2^256)
// = 0x...01457365 4... compute: (~n + 1) over 256 bits.
constexpr uint64_t NF0 = 0x402DA1732FC9BEBFULL;  // -N0 mod 2^64 with borrows
constexpr uint64_t NF1 = 0x4551231950B75FC4ULL;
constexpr uint64_t NF2 = 0x0000000000000001ULL;
constexpr uint64_t NF3 = 0x0000000000000000ULL;

inline void add_limb_at(uint64_t t[9], int idx, uint64_t val) {
  u128 cur = (u128)t[idx] + val;
  t[idx] = (uint64_t)cur;
  uint64_t carry = (uint64_t)(cur >> 64);
  for (int i = idx + 1; carry && i < 9; ++i) {
    u128 c2 = (u128)t[i] + carry;
    t[i] = (uint64_t)c2;
    carry = (uint64_t)(c2 >> 64);
  }
}

// Generic 512-bit -> 256-bit reduction given fold = 2^256 mod m (up to 3 limbs).
inline Fe reduce512(const uint64_t t[8], const uint64_t fold[4],
                    const uint64_t m[4]) {
  // r = lo + hi * fold ; hi*fold <= (2^256)(2^130ish) so iterate twice.
  uint64_t acc[9];
  std::memcpy(acc, t, 8 * sizeof(uint64_t));
  acc[8] = 0;
  for (int round = 0; round < 2; ++round) {
    uint64_t hi[5];
    std::memcpy(hi, acc + 4, 4 * sizeof(uint64_t));
    hi[4] = acc[8];
    uint64_t lo[9];
    std::memcpy(lo, acc, 4 * sizeof(uint64_t));
    std::memset(lo + 4, 0, 5 * sizeof(uint64_t));
    // lo += hi * fold
    for (int i = 0; i < 5; ++i) {
      if (hi[i] == 0) continue;
      for (int j = 0; j < 4; ++j) {
        if (fold[j] == 0) continue;
        u128 prod = (u128)hi[i] * fold[j];
        add_limb_at(lo, i + j, (uint64_t)prod);
        if ((uint64_t)(prod >> 64)) add_limb_at(lo, i + j + 1, (uint64_t)(prod >> 64));
      }
    }
    std::memcpy(acc, lo, 9 * sizeof(uint64_t));
    acc[8] = lo[8];
  }
  Fe r{{acc[0], acc[1], acc[2], acc[3]}};
  // after two folds the high limbs are tiny; fold remaining once more
  uint64_t hi4 = acc[4];
  if (hi4 | acc[5] | acc[6] | acc[7] | acc[8]) {
    uint64_t lo[9] = {r.v[0], r.v[1], r.v[2], r.v[3], 0, 0, 0, 0, 0};
    uint64_t hi[5] = {acc[4], acc[5], acc[6], acc[7], acc[8]};
    for (int i = 0; i < 5; ++i) {
      if (hi[i] == 0) continue;
      for (int j = 0; j < 4; ++j) {
        if (fold[j] == 0) continue;
        u128 prod = (u128)hi[i] * fold[j];
        add_limb_at(lo, i + j, (uint64_t)prod);
        if ((uint64_t)(prod >> 64)) add_limb_at(lo, i + j + 1, (uint64_t)(prod >> 64));
      }
    }
    r = Fe{{lo[0], lo[1], lo[2], lo[3]}};
  }
  while (ge(r, m)) sub_mod_raw(r, m);
  return r;
}

struct Field {
  uint64_t m[4];
  uint64_t fold[4];

  Fe add(const Fe &a, const Fe &b) const {
    Fe r;
    u128 carry = 0;
    for (int i = 0; i < 4; ++i) {
      u128 s = (u128)a.v[i] + b.v[i] + (uint64_t)carry;
      r.v[i] = (uint64_t)s;
      carry = s >> 64;
    }
    if (carry) {
      // r += fold (2^256 mod m)
      u128 c2 = 0;
      for (int i = 0; i < 4; ++i) {
        u128 s = (u128)r.v[i] + fold[i] + (uint64_t)c2;
        r.v[i] = (uint64_t)s;
        c2 = s >> 64;
      }
    }
    while (ge(r, m)) sub_mod_raw(r, m);
    return r;
  }

  Fe sub(const Fe &a, const Fe &b) const {
    Fe r = a;
    if (!ge(r, b.v)) {
      // r += m first
      u128 carry = 0;
      for (int i = 0; i < 4; ++i) {
        u128 s = (u128)r.v[i] + m[i] + (uint64_t)carry;
        r.v[i] = (uint64_t)s;
        carry = s >> 64;
      }
      // a < b <= m so a+m-b < m: carry out of 2^256 may happen; ignore since
      // result computed with borrow below stays correct modulo 2^256 when
      // carry==1 cancels the borrow.
    }
    sub_mod_raw(r, b.v);
    return r;
  }

  Fe mul(const Fe &a, const Fe &b) const {
    uint64_t t[8] = {0};
    for (int i = 0; i < 4; ++i) {
      uint64_t carry = 0;
      for (int j = 0; j < 4; ++j) {
        u128 cur = (u128)a.v[i] * b.v[j] + t[i + j] + carry;
        t[i + j] = (uint64_t)cur;
        carry = (uint64_t)(cur >> 64);
      }
      t[i + 4] = carry;
    }
    if (fold[1] == 0) {
      // Single-limb fold constant (the field prime p): fast two-pass fold.
      // r = lo + hi*PC where PC = 2^256 mod p fits one limb.
      uint64_t c = fold[0];
      uint64_t lo[5] = {t[0], t[1], t[2], t[3], 0};
      uint64_t carry = 0;
      for (int i = 0; i < 4; ++i) {
        u128 cur = (u128)t[4 + i] * c + lo[i] + carry;
        lo[i] = (uint64_t)cur;
        carry = (uint64_t)(cur >> 64);
      }
      lo[4] = carry;
      // second fold: lo[4] * c
      u128 cur = (u128)lo[4] * c + lo[0];
      Fe r{{(uint64_t)cur, lo[1], lo[2], lo[3]}};
      uint64_t c2 = (uint64_t)(cur >> 64);
      for (int i = 1; c2 && i < 4; ++i) {
        u128 s2 = (u128)r.v[i] + c2;
        r.v[i] = (uint64_t)s2;
        c2 = (uint64_t)(s2 >> 64);
      }
      // c2 can only be nonzero if r wrapped; fold once more
      if (c2) {
        u128 s3 = (u128)r.v[0] + c;
        r.v[0] = (uint64_t)s3;
        uint64_t c3 = (uint64_t)(s3 >> 64);
        for (int i = 1; c3 && i < 4; ++i) {
          u128 s4 = (u128)r.v[i] + c3;
          r.v[i] = (uint64_t)s4;
          c3 = (uint64_t)(s4 >> 64);
        }
      }
      while (ge(r, m)) sub_mod_raw(r, m);
      return r;
    }
    return reduce512(t, fold, m);
  }

  Fe sqr(const Fe &a) const { return mul(a, a); }

  Fe pow(const Fe &a, const uint64_t e[4]) const {
    Fe result{{1, 0, 0, 0}};
    Fe base = a;
    for (int limb = 0; limb < 4; ++limb) {
      uint64_t bits = e[limb];
      for (int i = 0; i < 64; ++i) {
        if (bits & 1) result = mul(result, base);
        base = sqr(base);
        bits >>= 1;
      }
    }
    return result;
  }

  Fe inv(const Fe &a) const {
    // Fermat: a^(m-2); both p and n are prime.
    uint64_t e[4] = {m[0] - 2, m[1], m[2], m[3]};  // m odd, no borrow
    return pow(a, e);
  }
};

const Field FP = {{P0, P1, P2, P3}, {PC, 0, 0, 0}};
const Field FN = {{N0, N1, N2, N3}, {NF0, NF1, NF2, NF3}};

// ---------- Jacobian points, a = 0, b = 7 ----------

struct Pt {
  Fe x, y, z;  // z == 0 => infinity
};

inline bool pt_inf(const Pt &p) { return is_zero(p.z); }

Pt pt_double(const Pt &p) {
  if (pt_inf(p) || is_zero(p.y)) return Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  // dbl-2009-l: A=X^2, B=Y^2, C=B^2, D=2((X+B)^2-A-C), E=3A, F=E^2
  Fe A = FP.sqr(p.x);
  Fe B = FP.sqr(p.y);
  Fe C = FP.sqr(B);
  Fe t = FP.sqr(FP.add(p.x, B));
  Fe D = FP.sub(FP.sub(t, A), C);
  D = FP.add(D, D);
  Fe E = FP.add(FP.add(A, A), A);
  Fe F = FP.sqr(E);
  Pt r;
  r.x = FP.sub(F, FP.add(D, D));
  Fe C8 = FP.add(C, C);
  C8 = FP.add(C8, C8);
  C8 = FP.add(C8, C8);
  r.y = FP.sub(FP.mul(E, FP.sub(D, r.x)), C8);
  r.z = FP.mul(FP.add(p.y, p.y), p.z);
  return r;
}

Pt pt_add(const Pt &p, const Pt &q) {
  if (pt_inf(p)) return q;
  if (pt_inf(q)) return p;
  // add-2007-bl
  Fe Z1Z1 = FP.sqr(p.z);
  Fe Z2Z2 = FP.sqr(q.z);
  Fe U1 = FP.mul(p.x, Z2Z2);
  Fe U2 = FP.mul(q.x, Z1Z1);
  Fe S1 = FP.mul(FP.mul(p.y, q.z), Z2Z2);
  Fe S2 = FP.mul(FP.mul(q.y, p.z), Z1Z1);
  if (fe_eq(U1, U2)) {
    if (fe_eq(S1, S2)) return pt_double(p);
    return Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};  // P + (-P) = O
  }
  Fe H = FP.sub(U2, U1);
  Fe I = FP.sqr(FP.add(H, H));
  Fe J = FP.mul(H, I);
  Fe rr = FP.sub(S2, S1);
  rr = FP.add(rr, rr);
  Fe V = FP.mul(U1, I);
  Pt out;
  out.x = FP.sub(FP.sub(FP.sqr(rr), J), FP.add(V, V));
  Fe S1J = FP.mul(S1, J);
  out.y = FP.sub(FP.mul(rr, FP.sub(V, out.x)), FP.add(S1J, S1J));
  Fe z1z2 = FP.mul(p.z, q.z);
  out.z = FP.mul(FP.add(z1z2, z1z2), H);  // add-2007-bl: Z3 = 2*Z1*Z2*H
  return out;
}

Fe fe_from_be(const uint8_t *b) {
  Fe r;
  for (int i = 0; i < 4; ++i) {
    uint64_t limb = 0;
    for (int j = 0; j < 8; ++j) limb = (limb << 8) | b[(3 - i) * 8 + j];
    r.v[i] = limb;
  }
  return r;
}

// Generator
const Fe GX = {{0x59F2815B16F81798ULL, 0x029BFCDB2DCE28D9ULL,
                0x55A06295CE870B07ULL, 0x79BE667EF9DCBBACULL}};
const Fe GY = {{0x9C47D08FFB10D4B8ULL, 0xFD17B448A6855419ULL,
                0x5DA4FBFC0E1108A8ULL, 0x483ADA7726A3C465ULL}};

struct Tables {
  Pt g[16];
  Tables() {
    g[0] = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
    g[1] = Pt{GX, GY, {{1, 0, 0, 0}}};
    for (int i = 2; i < 16; ++i) g[i] = pt_add(g[i - 1], g[1]);
  }
};
const Tables TAB;

// w = s^-1 mod n, precomputed by the caller (batch inversion).
bool verify_one(const uint8_t *px, const uint8_t *py, const uint8_t *z32,
                const uint8_t *r32, const Fe &w) {
  Fe qx = fe_from_be(px), qy = fe_from_be(py);
  Fe z = fe_from_be(z32);
  while (ge(z, FN.m)) sub_mod_raw(z, FN.m);  // digest reduced mod n
  Fe r = fe_from_be(r32);
  if (is_zero(r) || ge(r, FN.m)) return false;
  // curve membership: qy^2 == qx^3 + 7, coords < p
  if (ge(qx, FP.m) || ge(qy, FP.m)) return false;
  Fe lhs = FP.sqr(qy);
  Fe rhs = FP.add(FP.mul(FP.sqr(qx), qx), Fe{{7, 0, 0, 0}});
  if (!fe_eq(lhs, rhs)) return false;

  Fe u1 = FN.mul(z, w);
  Fe u2 = FN.mul(r, w);

  // per-key table
  Pt tq[16];
  tq[0] = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  tq[1] = Pt{qx, qy, {{1, 0, 0, 0}}};
  for (int i = 2; i < 16; ++i) tq[i] = pt_add(tq[i - 1], tq[1]);

  // interleaved 4-bit windows, MSB first
  Pt acc = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  for (int w4 = 63; w4 >= 0; --w4) {
    if (!pt_inf(acc)) {
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
    }
    int limb = w4 / 16, shift = (w4 % 16) * 4;
    int d1 = (int)((u1.v[limb] >> shift) & 0xF);
    int d2 = (int)((u2.v[limb] >> shift) & 0xF);
    if (d1) acc = pt_add(acc, TAB.g[d1]);
    if (d2) acc = pt_add(acc, tq[d2]);
  }
  if (pt_inf(acc)) return false;
  // accept iff acc.X == (r + k*n) * acc.Z^2 mod p for k in {0,1} with r+kn < p
  Fe zz = FP.sqr(acc.z);
  Fe cand = r;  // r < n < p: valid candidate
  if (fe_eq(FP.mul(cand, zz), acc.x)) return true;
  // second candidate r + n if it fits below p
  Fe rn = r;
  u128 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 s2 = (u128)rn.v[i] + FN.m[i] + (uint64_t)carry;
    rn.v[i] = (uint64_t)s2;
    carry = s2 >> 64;
  }
  if (!carry && !ge(rn, FP.m)) {
    if (fe_eq(FP.mul(rn, zz), acc.x)) return true;
  }
  return false;
}

// Euler's criterion: a^((p-1)/2) == 1 (mod p) — the jacobi(y) = 1
// acceptance test of BCH Schnorr.  Square-and-multiply over the constant
// exponent, MSB first.
bool fe_euler_is_one(const Fe &a) {
  // (p-1)/2, big-endian limb order for MSB-first iteration
  static const uint64_t E[4] = {0x7FFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL,
                                0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFF7FFFFE17ULL};
  Fe acc{{1, 0, 0, 0}};
  bool started = false;
  for (int w = 0; w < 4; ++w) {
    for (int b = 63; b >= 0; --b) {
      if (started) acc = FP.sqr(acc);
      if ((E[w] >> b) & 1) {
        if (started)
          acc = FP.mul(acc, a);
        else {
          acc = a;
          started = true;
        }
      }
    }
  }
  Fe one{{1, 0, 0, 0}};
  return fe_eq(acc, one);
}

// Shared core of both Schnorr-family verifiers (BCH 2019 and BIP340):
// identical range rules (r < p, s < n, zero allowed), curve membership,
// u1 = s / u2 = n - e, and the window MSM — only the final acceptance
// test differs (jacobi(y) = 1 vs y even), exactly as the TPU kernel
// splits it with per-lane flags.  Returns false on any pre-acceptance
// failure; on success fills r_out and the Jacobian accumulator.
bool schnorr_msm(const uint8_t *px, const uint8_t *py, const uint8_t *e32,
                 const uint8_t *r32, const uint8_t *s32, Fe &r_out,
                 Pt &acc_out) {
  Fe qx = fe_from_be(px), qy = fe_from_be(py);
  r_out = fe_from_be(r32);
  if (ge(r_out, FP.m)) return false;  // r is an Fp x-coordinate
  Fe s = fe_from_be(s32);
  if (ge(s, FN.m)) return false;  // s a scalar (zero allowed by spec)
  if (ge(qx, FP.m) || ge(qy, FP.m)) return false;
  Fe lhs = FP.sqr(qy);
  Fe rhs = FP.add(FP.mul(FP.sqr(qx), qx), Fe{{7, 0, 0, 0}});
  if (!fe_eq(lhs, rhs)) return false;

  Fe e = fe_from_be(e32);
  while (ge(e, FN.m)) sub_mod_raw(e, FN.m);
  // u2 = n - e (mod n)
  Fe u2{{0, 0, 0, 0}};
  if (!is_zero(e)) {
    u2 = Fe{{FN.m[0], FN.m[1], FN.m[2], FN.m[3]}};
    sub_mod_raw(u2, e.v);
  }
  const Fe &u1 = s;

  Pt tq[16];
  tq[0] = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  tq[1] = Pt{qx, qy, {{1, 0, 0, 0}}};
  for (int i = 2; i < 16; ++i) tq[i] = pt_add(tq[i - 1], tq[1]);

  Pt acc = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  for (int w4 = 63; w4 >= 0; --w4) {
    if (!pt_inf(acc)) {
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
    }
    int limb = w4 / 16, shift = (w4 % 16) * 4;
    int d1 = (int)((u1.v[limb] >> shift) & 0xF);
    int d2 = (int)((u2.v[limb] >> shift) & 0xF);
    if (d1) acc = pt_add(acc, TAB.g[d1]);
    if (d2) acc = pt_add(acc, tq[d2]);
  }
  if (pt_inf(acc)) return false;
  // x(R) == r over Fp (Jacobian: X == r * Z^2)
  Fe zz = FP.sqr(acc.z);
  if (!fe_eq(FP.mul(r_out, zz), acc.x)) return false;
  acc_out = acc;
  return true;
}

// BCH Schnorr (2019-05 upgrade spec), challenge e precomputed by the
// extractor: accept iff the common checks pass and jacobi(y(R)) == 1.
bool verify_one_schnorr(const uint8_t *px, const uint8_t *py,
                        const uint8_t *e32, const uint8_t *r32,
                        const uint8_t *s32) {
  Fe r;
  Pt acc;
  if (!schnorr_msm(px, py, e32, r32, s32, r, acc)) return false;
  // jacobi(y(R)) with y = Y/Z^3: jacobi(Y/Z^3) = jacobi(Y)*jacobi(Z) =
  // jacobi(Y*Z) (the symbol is multiplicative; squares vanish)
  return fe_euler_is_one(FP.mul(acc.y, acc.z));
}

// BIP340 (taproot): accept iff the common checks pass and y(R) is EVEN
// (the pubkey columns carry the lift_x'd even-y point).
bool verify_one_bip340(const uint8_t *px, const uint8_t *py,
                       const uint8_t *e32, const uint8_t *r32,
                       const uint8_t *s32) {
  Fe r;
  Pt acc;
  if (!schnorr_msm(px, py, e32, r32, s32, r, acc)) return false;
  // evenness needs the affine y = Y / Z^3
  Fe zi = FP.inv(acc.z);
  Fe zi2 = FP.sqr(zi);
  Fe y_aff = FP.mul(acc.y, FP.mul(zi2, zi));
  return (y_aff.v[0] & 1) == 0;
}

// Shared prologue of the batch verifiers: validity of each ECDSA row's s
// (Schnorr-family rows never join the inversion) and the Montgomery batch
// inversion producing w[i] = s_i^-1.  ONE definition so the serial and
// threaded entries can never diverge on the s-validity rule.
void batch_inversion_prologue(const uint8_t *s, const uint8_t *present,
                              int count, bool *s_ok, Fe *w) {
  Fe *sv = new Fe[count];
  Fe *prefix = new Fe[count];
  Fe run{{1, 0, 0, 0}};
  for (int i = 0; i < count; ++i) {
    bool schnorr = present != nullptr && present[i] >= 2;
    Fe si = fe_from_be(s + 32 * i);
    s_ok[i] = !schnorr && !(is_zero(si) || ge(si, FN.m));
    sv[i] = s_ok[i] ? si : Fe{{1, 0, 0, 0}};
    run = FN.mul(run, sv[i]);
    prefix[i] = run;
  }
  Fe inv_all = FN.inv(run);
  for (int i = count - 1; i >= 0; --i) {
    Fe before = (i == 0) ? Fe{{1, 0, 0, 0}} : prefix[i - 1];
    w[i] = FN.mul(inv_all, before);
    inv_all = FN.mul(inv_all, sv[i]);
  }
  delete[] sv;
  delete[] prefix;
}

// Verify rows [lo, hi) (shared by the serial entry and the threaded one);
// returns the number of valid rows in the range.
int secp_verify_rows(const uint8_t *px, const uint8_t *py, const uint8_t *z,
                     const uint8_t *r, const uint8_t *s,
                     const uint8_t *present, const bool *s_ok, const Fe *w,
                     int lo, int hi, uint8_t *out) {
  int valid = 0;
  for (int i = lo; i < hi; ++i) {
    bool ok;
    if (present != nullptr && present[i] == 0) {
      ok = false;
    } else if (present != nullptr && present[i] == 2) {
      ok = verify_one_schnorr(px + 32 * i, py + 32 * i, z + 32 * i,
                              r + 32 * i, s + 32 * i);
    } else if (present != nullptr && present[i] == 3) {
      ok = verify_one_bip340(px + 32 * i, py + 32 * i, z + 32 * i,
                             r + 32 * i, s + 32 * i);
    } else {
      ok = s_ok[i] && verify_one(px + 32 * i, py + 32 * i, z + 32 * i,
                                 r + 32 * i, w[i]);
    }
    out[i] = ok ? 1 : 0;
    valid += ok;
  }
  return valid;
}

}  // namespace

namespace {
void fe_to_be(const Fe &a, uint8_t *out) {
  for (int i = 0; i < 4; ++i)
    for (int j = 0; j < 8; ++j)
      out[(3 - i) * 8 + j] = (uint8_t)(a.v[i] >> (8 * (7 - j)));
}
}  // namespace

extern "C" {

// Debug/test hooks: 32-byte big-endian in/out field operations.
void secp_dbg_op(int op, const uint8_t *a32, const uint8_t *b32, uint8_t *out) {
  Fe a = fe_from_be(a32), b = fe_from_be(b32);
  Fe r{{0, 0, 0, 0}};
  switch (op) {
    case 0: r = FP.mul(a, b); break;
    case 1: r = FP.add(a, b); break;
    case 2: r = FP.sub(a, b); break;
    case 3: r = FP.inv(a); break;
    case 4: r = FN.mul(a, b); break;
    case 5: r = FN.inv(a); break;
  }
  fe_to_be(r, out);
}

// Debug: kG via the window table path; writes affine x,y (inverts Z).
void secp_dbg_mulg(const uint8_t *k32, uint8_t *x_out, uint8_t *y_out) {
  Fe k = fe_from_be(k32);
  Pt acc = Pt{{{0}}, {{1, 0, 0, 0}}, {{0}}};
  for (int w4 = 63; w4 >= 0; --w4) {
    if (!pt_inf(acc)) {
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
      acc = pt_double(acc);
    }
    int limb = w4 / 16, shift = (w4 % 16) * 4;
    int d = (int)((k.v[limb] >> shift) & 0xF);
    if (d) acc = pt_add(acc, TAB.g[d]);
  }
  Fe zi = FP.inv(acc.z);
  Fe zi2 = FP.sqr(zi);
  fe_to_be(FP.mul(acc.x, zi2), x_out);
  fe_to_be(FP.mul(acc.y, FP.mul(zi2, zi)), y_out);
}

// Inputs: concatenated 32-byte big-endian arrays, one entry per signature.
//   px, py: affine public key coordinates
//   z: message digests (ECDSA) or precomputed challenges (Schnorr)
//   r, s: signature scalars
//   present: per-row algorithm, or NULL for all-ECDSA: 0 = auto-invalid,
//            1 = ECDSA, 2 = BCH Schnorr (RawBatch.present semantics)
// Output: out[i] = 1 if valid else 0.  Returns number of valid signatures.
int secp_verify_batch(const uint8_t *px, const uint8_t *py, const uint8_t *z,
                      const uint8_t *r, const uint8_t *s,
                      const uint8_t *present, int count, uint8_t *out) {
  bool *s_ok = new bool[count];
  Fe *w = new Fe[count];
  batch_inversion_prologue(s, present, count, s_ok, w);
  int valid = secp_verify_rows(px, py, z, r, s, present, s_ok, w, 0, count,
                               out);
  delete[] s_ok;
  delete[] w;
  return valid;
}

}  // extern "C"

// ===========================================================================
// Host-side batch preparation for the TPU kernel (tpunode/verify/kernel.py
// prepare_batch): range checks, Montgomery batch inversion of s, u1/u2,
// GLV decomposition, 4-bit window digits and radix-11 limb conversion —
// the per-item big-int work that dominates Python prep.  Layouts match
// PreparedBatch exactly (limb-major / batch-minor int32).
// ===========================================================================

#include <atomic>
#include <memory>
#include <thread>
#include <vector>

namespace {

// GLV lattice constants (standard public secp256k1 endomorphism basis;
// same values as tpunode/verify/kernel.py:71-74, verified bit-exact against
// kernel.glv_split in tests/test_native_verify.py).
const uint64_t GLV_A1[2] = {0xE86C90E49284EB15ULL, 0x3086D221A7D46BCDULL};
const uint64_t GLV_B1N[2] = {0x6F547FA90ABFE4C3ULL, 0xE4437ED6010E8828ULL};
const uint64_t GLV_A2[3] = {0x57C1108D9D44CFD8ULL, 0x14CA50F7A8E2F3F6ULL, 1ULL};
// b2 == a1

// ---- fixed-width helpers on little-endian u64 arrays ----------------------

// out[no] = a[na] * b[nb] (no >= na+nb)
inline void mp_mul(const uint64_t *a, int na, const uint64_t *b, int nb,
                   uint64_t *out, int no) {
  for (int i = 0; i < no; ++i) out[i] = 0;
  for (int i = 0; i < na; ++i) {
    uint64_t carry = 0;
    for (int j = 0; j < nb; ++j) {
      u128 cur = (u128)a[i] * b[j] + out[i + j] + carry;
      out[i + j] = (uint64_t)cur;
      carry = (uint64_t)(cur >> 64);
    }
    int k = i + nb;
    while (carry && k < no) {
      u128 cur = (u128)out[k] + carry;
      out[k] = (uint64_t)cur;
      carry = (uint64_t)(cur >> 64);
      ++k;
    }
  }
}

// a[n] += b[nb]; returns carry out
inline uint64_t mp_add(uint64_t *a, int n, const uint64_t *b, int nb) {
  uint64_t carry = 0;
  for (int i = 0; i < n; ++i) {
    u128 cur = (u128)a[i] + (i < nb ? b[i] : 0) + carry;
    a[i] = (uint64_t)cur;
    carry = (uint64_t)(cur >> 64);
  }
  return carry;
}

// a[n] -= b[nb]; returns borrow out
inline uint64_t mp_sub(uint64_t *a, int n, const uint64_t *b, int nb) {
  uint64_t borrow = 0;
  for (int i = 0; i < n; ++i) {
    uint64_t bi = i < nb ? b[i] : 0;
    u128 d = (u128)a[i] - bi - borrow;
    a[i] = (uint64_t)d;
    borrow = (d >> 64) ? 1 : 0;
  }
  return borrow;
}

// Barrett reciprocals round(2^384 * b / n) for b = b2(=a1) and |b1| —
// the same constants as libsecp256k1's scalar_split_lambda g1/g2 and
// kernel.py's _G1/_G2 (bit-identical digits across all three).
const uint64_t GLV_G1[4] = {0xE893209A45DBB031ULL, 0x3DAA8A1471E8CA7FULL,
                            0xE86C90E49284EB15ULL, 0x3086D221A7D46BCDULL};
const uint64_t GLV_G2[4] = {0x1571B4AE8AC47F71ULL, 0x221208AC9DF506C6ULL,
                            0x6F547FA90ABFE4C4ULL, 0xE4437ED6010E8828ULL};

// c = round(k * g / 2^384): one 4x4 multiply + a shifted rounding add.
inline void glv_c(const uint64_t g[4], const Fe &k, uint64_t c[3]) {
  uint64_t t[8];
  mp_mul(k.v, 4, g, 4, t, 8);
  uint64_t half[6] = {0, 0, 0, 0, 0, 0x8000000000000000ULL};  // 2^383
  mp_add(t, 8, half, 6);
  c[0] = t[6];
  c[1] = t[7];
  c[2] = 0;
}

// signed k1/k2 halves: value = (-1)^neg * abs[3]
struct Half {
  uint64_t abs[3];
  bool neg;
};

// k1 = k - c1*a1 - c2*a2 ; k2 = c1*b1n - c2*b2  (b1 = -b1n, b2 = a1),
// computed in 448-bit two's complement.
inline void glv_halves(const Fe &k, const uint64_t c1[3], const uint64_t c2[3],
                       Half &h1, Half &h2) {
  uint64_t acc[7] = {k.v[0], k.v[1], k.v[2], k.v[3], 0, 0, 0};
  uint64_t t[7];
  mp_mul(c1, 3, GLV_A1, 2, t, 7);
  mp_sub(acc, 7, t, 7);
  mp_mul(c2, 3, GLV_A2, 3, t, 7);
  mp_sub(acc, 7, t, 7);
  h1.neg = (acc[6] >> 63) != 0;
  if (h1.neg) {  // negate two's complement
    for (int i = 0; i < 7; ++i) acc[i] = ~acc[i];
    uint64_t one[1] = {1};
    mp_add(acc, 7, one, 1);
  }
  h1.abs[0] = acc[0]; h1.abs[1] = acc[1]; h1.abs[2] = acc[2];

  uint64_t acc2[7] = {0, 0, 0, 0, 0, 0, 0};
  mp_mul(c1, 3, GLV_B1N, 2, acc2, 7);
  mp_mul(c2, 3, GLV_A1, 2, t, 7);  // b2 == a1
  mp_sub(acc2, 7, t, 7);
  h2.neg = (acc2[6] >> 63) != 0;
  if (h2.neg) {
    for (int i = 0; i < 7; ++i) acc2[i] = ~acc2[i];
    uint64_t one[1] = {1};
    mp_add(acc2, 7, one, 1);
  }
  h2.abs[0] = acc2[0]; h2.abs[1] = acc2[1]; h2.abs[2] = acc2[2];
}

// kernel.py's WINDOW_BITS / WINDOWS: 33 4-bit windows cover the GLV
// half-scalars' 132 bits.
constexpr int PREP_WINDOW_BITS = 4;
constexpr int PREP_WINDOWS = 33;

// A lane's wire buffer (kernel.py, "the lane's wire form"): int32
// (PREP_ROWS, size), batch minor, little-endian 32-bit words down a column.
constexpr int PREP_HALF_WORDS = 5;   // a GLV half-scalar magnitude, 132 bits
constexpr int PREP_FIELD_WORDS = 8;  // a field element
constexpr int PREP_FIELD_ROW0 = 4 * PREP_HALF_WORDS;  // qx, qy, r1, r2
constexpr int PREP_FLAGS_ROW = PREP_FIELD_ROW0 + 4 * PREP_FIELD_WORDS;
constexpr int PREP_ROWS = PREP_FLAGS_ROW + 1;
// flag bits 0..3 are the half-scalars' signs (n1a, n1b, n2a, n2b)
constexpr uint32_t PREP_R2_VALID = 1u << 4, PREP_HOST_VALID = 1u << 5,
                   PREP_SCHNORR = 1u << 6, PREP_BIP340 = 1u << 7;

// The low ``nwords`` 32-bit words of v into out[(row + k) * size + lane].
inline void write_words(const uint64_t *v, int nwords, int32_t *out, int row,
                        int size, int lane) {
  for (int k = 0; k < nwords; ++k)
    out[(size_t)(row + k) * size + lane] =
        (int32_t)(uint32_t)(v[k / 2] >> (32 * (k % 2)));
}

}  // namespace

extern "C" {

// Threaded batch verify for multi-core hosts: same semantics as
// secp_verify_batch, rows split across ``nthreads`` (0 = hardware
// concurrency).  The Montgomery inversion stays serial (it is ~0.1% of
// the work); each MSM row is independent.
int secp_verify_batch_mt(const uint8_t *px, const uint8_t *py,
                         const uint8_t *z, const uint8_t *r, const uint8_t *s,
                         const uint8_t *present, int count, uint8_t *out,
                         int nthreads) {
  int T = nthreads > 0 ? nthreads : (int)std::thread::hardware_concurrency();
  if (T < 1) T = 1;
  if (T == 1 || count < 64)
    return secp_verify_batch(px, py, z, r, s, present, count, out);

  std::vector<Fe> w(count);
  std::unique_ptr<bool[]> s_ok(new bool[count]);
  batch_inversion_prologue(s, present, count, s_ok.get(), w.data());

  std::atomic<int> valid{0};
  std::vector<std::thread> ts;
  int chunk = (count + T - 1) / T;
  for (int t = 0; t < T; ++t) {
    int lo = t * chunk, hi = lo + chunk < count ? lo + chunk : count;
    if (lo >= hi) break;
    ts.emplace_back([&, lo, hi]() {
      valid.fetch_add(
          secp_verify_rows(px, py, z, r, s, present, s_ok.get(), w.data(),
                           lo, hi, out),
          std::memory_order_relaxed);
    });
  }
  for (auto &th : ts) th.join();
  return valid.load();
}

// Rows of the buffer secp_prepare_batch fills: the wrapper allocates by it
// and refuses a library whose layout is another.
int secp_prepare_rows() { return PREP_ROWS; }

// Host prep for one device batch.  All byte inputs are 32-byte big-endian,
// one entry per item; ``present[i]`` carries the RawBatch algorithm code
// (0 = absent, 1 = ECDSA, 2 = BCH Schnorr, 3 = BIP340 — for Schnorr, ``z``
// is the precomputed challenge e, u1 = s and u2 = n - e need no inversion,
// and ``r`` is an Fp x-coordinate with no r+n candidate).  ``lane`` is the
// (PREP_ROWS, size) wire buffer, zero-initialized by the caller; columns
// >= count, and those of rows refused here, stay zero.  Returns the number
// of GLV bound violations (0 = success; cannot occur for in-range scalars
// — a nonzero return means a bug and the caller must refuse the batch).
int secp_prepare_batch(const uint8_t *px, const uint8_t *py, const uint8_t *z,
                       const uint8_t *r, const uint8_t *s,
                       const uint8_t *present, int count, int size,
                       int32_t *lane, int nthreads) {
  // half-scalars live in abs[0..2]: bits >= 132 sit at abs[2] >> 4
  constexpr int bound_shift = PREP_WINDOW_BITS * PREP_WINDOWS - 128;
  // ---- serial: validity + Montgomery batch inversion of s (ECDSA rows) ----
  std::vector<Fe> sv(count), prefix(count), w(count);
  std::vector<uint8_t> ok(count), is_sch(count);
  Fe run{{1, 0, 0, 0}};
  for (int i = 0; i < count; ++i) {
    Fe si = fe_from_be(s + 32 * i);
    Fe ri = fe_from_be(r + 32 * i);
    is_sch[i] = present[i] >= 2;  // both Schnorr variants: u1=s, u2=n-e
    if (is_sch[i]) {
      // spec ranges: r < p, s < n; zero allowed for both
      ok[i] = !ge(si, FN.m) && !ge(ri, FP.m);
      sv[i] = Fe{{1, 0, 0, 0}};  // no inversion needed
    } else {
      ok[i] = present[i] && !is_zero(si) && !ge(si, FN.m) && !is_zero(ri) &&
              !ge(ri, FN.m);
      sv[i] = ok[i] ? si : Fe{{1, 0, 0, 0}};
    }
    run = FN.mul(run, sv[i]);
    prefix[i] = run;
  }
  Fe inv_all = FN.inv(run);
  for (int i = count - 1; i >= 0; --i) {
    Fe before = (i == 0) ? Fe{{1, 0, 0, 0}} : prefix[i - 1];
    w[i] = FN.mul(inv_all, before);
    inv_all = FN.mul(inv_all, sv[i]);
  }

  // ---- parallel: per-item GLV + digits + limbs ----
  std::atomic<int> violations{0};
  auto work = [&](int lo, int hi) {
    for (int i = lo; i < hi; ++i) {
      if (!ok[i]) continue;
      uint32_t flags = PREP_HOST_VALID;
      Fe zi = fe_from_be(z + 32 * i);
      while (ge(zi, FN.m)) sub_mod_raw(zi, FN.m);
      Fe ri = fe_from_be(r + 32 * i);
      Fe u1, u2;
      if (is_sch[i]) {
        flags |= present[i] == 2 ? PREP_SCHNORR : PREP_BIP340;
        u1 = fe_from_be(s + 32 * i);  // u1 = s (< n, checked)
        u2 = Fe{{0, 0, 0, 0}};        // u2 = n - e (mod n)
        if (!is_zero(zi)) {
          u2 = Fe{{FN.m[0], FN.m[1], FN.m[2], FN.m[3]}};
          sub_mod_raw(u2, zi.v);
        }
      } else {
        u1 = FN.mul(zi, w[i]);
        u2 = FN.mul(ri, w[i]);
      }
      Half h[4];
      uint64_t c1[3], c2[3];
      glv_c(GLV_G1, u1, c1);
      glv_c(GLV_G2, u1, c2);
      glv_halves(u1, c1, c2, h[0], h[1]);
      glv_c(GLV_G1, u2, c1);
      glv_c(GLV_G2, u2, c2);
      glv_halves(u2, c1, c2, h[2], h[3]);
      for (int j = 0; j < 4; ++j) {
        // |k| >= 2^132: outside the window range
        if (h[j].abs[2] >> bound_shift) {
          violations.fetch_add(1, std::memory_order_relaxed);
          continue;
        }
        write_words(h[j].abs, PREP_HALF_WORDS, lane, j * PREP_HALF_WORDS,
                    size, i);
        if (h[j].neg) flags |= 1u << j;
      }
      const Fe q[3] = {fe_from_be(px + 32 * i), fe_from_be(py + 32 * i), ri};
      for (int j = 0; j < 3; ++j)
        write_words(q[j].v, PREP_FIELD_WORDS, lane,
                    PREP_FIELD_ROW0 + j * PREP_FIELD_WORDS, size, i);
      // r + n < p ?  (ECDSA-only: Schnorr compares x(R) to r over Fp)
      if (!is_sch[i]) {
        Fe rn = ri;
        uint64_t carry = mp_add(rn.v, 4, FN.m, 4);
        if (!carry && !ge(rn, FP.m)) {
          write_words(rn.v, PREP_FIELD_WORDS, lane,
                      PREP_FIELD_ROW0 + 3 * PREP_FIELD_WORDS, size, i);
          flags |= PREP_R2_VALID;
        }
      }
      lane[(size_t)PREP_FLAGS_ROW * size + i] = (int32_t)flags;
    }
  };
  int T = nthreads > 0 ? nthreads : (int)std::thread::hardware_concurrency();
  if (T < 1) T = 1;
  if (T == 1 || count < 256) {
    work(0, count);
  } else {
    std::vector<std::thread> ts;
    int chunk = (count + T - 1) / T;
    for (int t = 0; t < T; ++t) {
      int lo = t * chunk, hi = lo + chunk < count ? lo + chunk : count;
      if (lo >= hi) break;
      ts.emplace_back(work, lo, hi);
    }
    for (auto &th : ts) th.join();
  }
  return violations.load();
}

}  // extern "C"

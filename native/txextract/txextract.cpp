// Native transaction signature-item extractor.
//
// The host-side producer of the verify pipeline: takes a raw serialized
// transaction region (a block's tx area or concatenated loose txs) and emits,
// per verifiable input, exactly the 32-byte big-endian buffers the rest of
// the native path consumes (secp_prepare_batch / secp_verify_batch in
// native/secp256k1/secp256k1.cpp):
//
//     z (sighash mod n) | px | py (decompressed pubkey) | r | s | present
//
// plus per-item (tx_index, input_index) and per-tx (txid, stats) metadata.
//
// Semantics are a bit-exact mirror of the Python reference path
// (tpunode/txverify.py + tpunode/sighash.py + ecdsa_cpu.decode_pubkey /
// parse_der_signature) — the parity test suite checks item-for-item
// equality on randomized workloads.  The reference node outsources all of
// this to haskoin-core/libsecp256k1 (SURVEY.md C6/C9); this is the
// TPU-framework's native equivalent of that hot path.
//
// Build: make -C native build/libtxextract.so
// Python binding: tpunode/txextract.py (ctypes).

#include <algorithm>
#include <cstdint>
#include <cstring>
#include <ctime>
#include <memory>
#include <mutex>
#include <string>
#include <unordered_map>
#include <vector>

namespace {

// ---------------------------------------------------------------------------
// SHA-256 (FIPS 180-4), streaming.
// ---------------------------------------------------------------------------

struct Sha256 {
  uint32_t h[8];
  uint8_t buf[64];
  uint64_t len = 0;

  Sha256() { reset(); }

  void reset() {
    static const uint32_t init[8] = {0x6a09e667, 0xbb67ae85, 0x3c6ef372,
                                     0xa54ff53a, 0x510e527f, 0x9b05688c,
                                     0x1f83d9ab, 0x5be0cd19};
    memcpy(h, init, sizeof(h));
    len = 0;
  }

  static uint32_t rotr(uint32_t x, int n) { return (x >> n) | (x << (32 - n)); }

  void block(const uint8_t *p) {
    static const uint32_t K[64] = {
        0x428a2f98, 0x71374491, 0xb5c0fbcf, 0xe9b5dba5, 0x3956c25b, 0x59f111f1,
        0x923f82a4, 0xab1c5ed5, 0xd807aa98, 0x12835b01, 0x243185be, 0x550c7dc3,
        0x72be5d74, 0x80deb1fe, 0x9bdc06a7, 0xc19bf174, 0xe49b69c1, 0xefbe4786,
        0x0fc19dc6, 0x240ca1cc, 0x2de92c6f, 0x4a7484aa, 0x5cb0a9dc, 0x76f988da,
        0x983e5152, 0xa831c66d, 0xb00327c8, 0xbf597fc7, 0xc6e00bf3, 0xd5a79147,
        0x06ca6351, 0x14292967, 0x27b70a85, 0x2e1b2138, 0x4d2c6dfc, 0x53380d13,
        0x650a7354, 0x766a0abb, 0x81c2c92e, 0x92722c85, 0xa2bfe8a1, 0xa81a664b,
        0xc24b8b70, 0xc76c51a3, 0xd192e819, 0xd6990624, 0xf40e3585, 0x106aa070,
        0x19a4c116, 0x1e376c08, 0x2748774c, 0x34b0bcb5, 0x391c0cb3, 0x4ed8aa4a,
        0x5b9cca4f, 0x682e6ff3, 0x748f82ee, 0x78a5636f, 0x84c87814, 0x8cc70208,
        0x90befffa, 0xa4506ceb, 0xbef9a3f7, 0xc67178f2};
    uint32_t w[64];
    for (int i = 0; i < 16; ++i)
      w[i] = (uint32_t(p[i * 4]) << 24) | (uint32_t(p[i * 4 + 1]) << 16) |
             (uint32_t(p[i * 4 + 2]) << 8) | p[i * 4 + 3];
    for (int i = 16; i < 64; ++i) {
      uint32_t s0 = rotr(w[i - 15], 7) ^ rotr(w[i - 15], 18) ^ (w[i - 15] >> 3);
      uint32_t s1 = rotr(w[i - 2], 17) ^ rotr(w[i - 2], 19) ^ (w[i - 2] >> 10);
      w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint32_t a = h[0], b = h[1], c = h[2], d = h[3], e = h[4], f = h[5],
             g = h[6], hh = h[7];
    for (int i = 0; i < 64; ++i) {
      uint32_t S1 = rotr(e, 6) ^ rotr(e, 11) ^ rotr(e, 25);
      uint32_t ch = (e & f) ^ (~e & g);
      uint32_t t1 = hh + S1 + ch + K[i] + w[i];
      uint32_t S0 = rotr(a, 2) ^ rotr(a, 13) ^ rotr(a, 22);
      uint32_t maj = (a & b) ^ (a & c) ^ (b & c);
      uint32_t t2 = S0 + maj;
      hh = g; g = f; f = e; e = d + t1;
      d = c; c = b; b = a; a = t1 + t2;
    }
    h[0] += a; h[1] += b; h[2] += c; h[3] += d;
    h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
  }

  void update(const uint8_t *p, size_t n) {
    size_t fill = len % 64;
    len += n;
    if (fill) {
      size_t take = 64 - fill;
      if (take > n) take = n;
      memcpy(buf + fill, p, take);
      p += take;
      n -= take;
      if (fill + take == 64) block(buf);
      else return;
    }
    while (n >= 64) {
      block(p);
      p += 64;
      n -= 64;
    }
    if (n) memcpy(buf, p, n);
  }

  void final(uint8_t out[32]) {
    uint64_t bits = len * 8;
    uint8_t pad = 0x80;
    update(&pad, 1);
    uint8_t zero = 0;
    while (len % 64 != 56) update(&zero, 1);
    uint8_t lenb[8];
    for (int i = 0; i < 8; ++i) lenb[i] = uint8_t(bits >> (56 - 8 * i));
    update(lenb, 8);
    for (int i = 0; i < 8; ++i) {
      out[i * 4] = uint8_t(h[i] >> 24);
      out[i * 4 + 1] = uint8_t(h[i] >> 16);
      out[i * 4 + 2] = uint8_t(h[i] >> 8);
      out[i * 4 + 3] = uint8_t(h[i]);
    }
  }
};

void sha256(const uint8_t *p, size_t n, uint8_t out[32]) {
  Sha256 c;
  c.update(p, n);
  c.final(out);
}

void dsha256(const uint8_t *p, size_t n, uint8_t out[32]) {
  uint8_t t[32];
  sha256(p, n, t);
  sha256(t, 32, out);
}

// ---------------------------------------------------------------------------
// RIPEMD-160 (for hash160 of the pubkey -> P2PKH script code).
// ---------------------------------------------------------------------------

struct Ripemd160 {
  static uint32_t rol(uint32_t x, int n) { return (x << n) | (x >> (32 - n)); }
  static uint32_t f(int j, uint32_t x, uint32_t y, uint32_t z) {
    if (j < 16) return x ^ y ^ z;
    if (j < 32) return (x & y) | (~x & z);
    if (j < 48) return (x | ~y) ^ z;
    if (j < 64) return (x & z) | (y & ~z);
    return x ^ (y | ~z);
  }

  static void hash(const uint8_t *msg, size_t n, uint8_t out[20]) {
    static const int r1[80] = {
        0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15,
        7, 4, 13, 1, 10, 6, 15, 3, 12, 0, 9, 5, 2, 14, 11, 8,
        3, 10, 14, 4, 9, 15, 8, 1, 2, 7, 0, 6, 13, 11, 5, 12,
        1, 9, 11, 10, 0, 8, 12, 4, 13, 3, 7, 15, 14, 5, 6, 2,
        4, 0, 5, 9, 7, 12, 2, 10, 14, 1, 3, 8, 11, 6, 15, 13};
    static const int r2[80] = {
        5, 14, 7, 0, 9, 2, 11, 4, 13, 6, 15, 8, 1, 10, 3, 12,
        6, 11, 3, 7, 0, 13, 5, 10, 14, 15, 8, 12, 4, 9, 1, 2,
        15, 5, 1, 3, 7, 14, 6, 9, 11, 8, 12, 2, 10, 0, 4, 13,
        8, 6, 4, 1, 3, 11, 15, 0, 5, 12, 2, 13, 9, 7, 10, 14,
        12, 15, 10, 4, 1, 5, 8, 7, 6, 2, 13, 14, 0, 3, 9, 11};
    static const int s1[80] = {
        11, 14, 15, 12, 5, 8, 7, 9, 11, 13, 14, 15, 6, 7, 9, 8,
        7, 6, 8, 13, 11, 9, 7, 15, 7, 12, 15, 9, 11, 7, 13, 12,
        11, 13, 6, 7, 14, 9, 13, 15, 14, 8, 13, 6, 5, 12, 7, 5,
        11, 12, 14, 15, 14, 15, 9, 8, 9, 14, 5, 6, 8, 6, 5, 12,
        9, 15, 5, 11, 6, 8, 13, 12, 5, 12, 13, 14, 11, 8, 5, 6};
    static const int s2[80] = {
        8, 9, 9, 11, 13, 15, 15, 5, 7, 7, 8, 11, 14, 14, 12, 6,
        9, 13, 15, 7, 12, 8, 9, 11, 7, 7, 12, 7, 6, 15, 13, 11,
        9, 7, 15, 11, 8, 6, 6, 14, 12, 13, 5, 14, 13, 13, 7, 5,
        15, 5, 8, 11, 14, 14, 6, 14, 6, 9, 12, 9, 12, 5, 15, 8,
        8, 5, 12, 9, 12, 5, 14, 6, 8, 13, 6, 5, 15, 13, 11, 11};
    static const uint32_t K1[5] = {0, 0x5a827999, 0x6ed9eba1, 0x8f1bbcdc,
                                   0xa953fd4e};
    static const uint32_t K2[5] = {0x50a28be6, 0x5c4dd124, 0x6d703ef3,
                                   0x7a6d76e9, 0};
    uint32_t h[5] = {0x67452301, 0xefcdab89, 0x98badcfe, 0x10325476,
                     0xc3d2e1f0};
    // pad
    std::vector<uint8_t> m(msg, msg + n);
    m.push_back(0x80);
    while (m.size() % 64 != 56) m.push_back(0);
    uint64_t bits = uint64_t(n) * 8;
    for (int i = 0; i < 8; ++i) m.push_back(uint8_t(bits >> (8 * i)));
    for (size_t off = 0; off < m.size(); off += 64) {
      uint32_t x[16];
      for (int i = 0; i < 16; ++i)
        x[i] = uint32_t(m[off + i * 4]) | (uint32_t(m[off + i * 4 + 1]) << 8) |
               (uint32_t(m[off + i * 4 + 2]) << 16) |
               (uint32_t(m[off + i * 4 + 3]) << 24);
      uint32_t a1 = h[0], b1 = h[1], c1 = h[2], d1 = h[3], e1 = h[4];
      uint32_t a2 = a1, b2 = b1, c2 = c1, d2 = d1, e2 = e1;
      for (int j = 0; j < 80; ++j) {
        uint32_t t = rol(a1 + f(j, b1, c1, d1) + x[r1[j]] + K1[j / 16], s1[j]) + e1;
        a1 = e1; e1 = d1; d1 = rol(c1, 10); c1 = b1; b1 = t;
        t = rol(a2 + f(79 - j, b2, c2, d2) + x[r2[j]] + K2[j / 16], s2[j]) + e2;
        a2 = e2; e2 = d2; d2 = rol(c2, 10); c2 = b2; b2 = t;
      }
      uint32_t t = h[1] + c1 + d2;
      h[1] = h[2] + d1 + e2;
      h[2] = h[3] + e1 + a2;
      h[3] = h[4] + a1 + b2;
      h[4] = h[0] + b1 + c2;
      h[0] = t;
    }
    for (int i = 0; i < 5; ++i) {
      out[i * 4] = uint8_t(h[i]);
      out[i * 4 + 1] = uint8_t(h[i] >> 8);
      out[i * 4 + 2] = uint8_t(h[i] >> 16);
      out[i * 4 + 3] = uint8_t(h[i] >> 24);
    }
  }
};

void hash160(const uint8_t *p, size_t n, uint8_t out[20]) {
  uint8_t s[32];
  sha256(p, n, s);
  Ripemd160::hash(s, 32, out);
}

// ---------------------------------------------------------------------------
// secp256k1 base field (mod p) — only what pubkey decompression needs.
// Independent of native/secp256k1/secp256k1.cpp (that unit verifies;
// this one parses) so neither build depends on the other.
// ---------------------------------------------------------------------------

typedef unsigned __int128 u128;

struct F4 {
  uint64_t v[4];  // little-endian limbs
};

const uint64_t P_LIMBS[4] = {0xFFFFFFFEFFFFFC2FULL, 0xFFFFFFFFFFFFFFFFULL,
                             0xFFFFFFFFFFFFFFFFULL, 0xFFFFFFFFFFFFFFFFULL};
const uint64_t FOLD_K = 0x1000003D1ULL;  // 2^256 mod p

bool f_ge_p(const F4 &a) {
  for (int i = 3; i >= 0; --i) {
    if (a.v[i] > P_LIMBS[i]) return true;
    if (a.v[i] < P_LIMBS[i]) return false;
  }
  return true;  // equal
}

void f_sub_p(F4 &a) {
  u128 borrow = 0;
  for (int i = 0; i < 4; ++i) {
    u128 d = (u128)a.v[i] - P_LIMBS[i] - borrow;
    a.v[i] = (uint64_t)d;
    borrow = (d >> 64) & 1;
  }
}

void f_normalize(F4 &a) {
  while (f_ge_p(a)) f_sub_p(a);
}

void f_mul(F4 &out, const F4 &a, const F4 &b) {
  uint64_t t[8] = {0};
  for (int i = 0; i < 4; ++i) {
    u128 carry = 0;
    for (int j = 0; j < 4; ++j) {
      u128 cur = (u128)a.v[i] * b.v[j] + t[i + j] + carry;
      t[i + j] = (uint64_t)cur;
      carry = cur >> 64;
    }
    t[i + 4] += (uint64_t)carry;
  }
  // fold high 256 bits: r = lo + hi * FOLD_K
  uint64_t r[5] = {0};
  u128 carry = 0;
  for (int i = 0; i < 4; ++i) {
    u128 cur = (u128)t[i] + (u128)t[i + 4] * FOLD_K + carry;
    r[i] = (uint64_t)cur;
    carry = cur >> 64;
  }
  r[4] = (uint64_t)carry;
  // fold the (small) carry limb once more
  u128 cur = (u128)r[0] + (u128)r[4] * FOLD_K;
  F4 res;
  res.v[0] = (uint64_t)cur;
  carry = cur >> 64;
  for (int i = 1; i < 4; ++i) {
    cur = (u128)r[i] + carry;
    res.v[i] = (uint64_t)cur;
    carry = cur >> 64;
  }
  if (carry) {  // wrapped past 2^256: add FOLD_K (== 2^256 mod p)
    cur = (u128)res.v[0] + FOLD_K;
    res.v[0] = (uint64_t)cur;
    carry = cur >> 64;
    for (int i = 1; carry && i < 4; ++i) {
      cur = (u128)res.v[i] + carry;
      res.v[i] = (uint64_t)cur;
      carry = cur >> 64;
    }
  }
  f_normalize(res);
  out = res;
}

void f_sqr(F4 &out, const F4 &a) { f_mul(out, a, a); }

void f_add(F4 &out, const F4 &a, const F4 &b) {
  u128 carry = 0;
  F4 res;
  for (int i = 0; i < 4; ++i) {
    u128 cur = (u128)a.v[i] + b.v[i] + carry;
    res.v[i] = (uint64_t)cur;
    carry = cur >> 64;
  }
  if (carry) {
    u128 cur = (u128)res.v[0] + FOLD_K;
    res.v[0] = (uint64_t)cur;
    carry = cur >> 64;
    for (int i = 1; carry && i < 4; ++i) {
      cur = (u128)res.v[i] + carry;
      res.v[i] = (uint64_t)cur;
      carry = cur >> 64;
    }
  }
  f_normalize(res);
  out = res;
}

bool f_is_eq(const F4 &a, const F4 &b) {
  return memcmp(a.v, b.v, sizeof(a.v)) == 0;
}

void f_from_be(F4 &out, const uint8_t b[32]) {
  for (int i = 0; i < 4; ++i) {
    uint64_t limb = 0;
    for (int j = 0; j < 8; ++j) limb = (limb << 8) | b[(3 - i) * 8 + j];
    out.v[i] = limb;
  }
}

void f_to_be(const F4 &a, uint8_t out[32]) {
  for (int i = 0; i < 4; ++i)
    for (int j = 0; j < 8; ++j)
      out[(3 - i) * 8 + j] = uint8_t(a.v[i] >> (56 - 8 * j));
}

// a^((p+1)/4) mod p: square root when a is a quadratic residue.
// (p+1)/4 = 2^254 - 2^30 - 244, whose bits are long runs of ones:
//   ((2^223-1) << 23 | (2^22-1)) << 6 | (2^2-1)) << 2
// so an addition chain over x^(2^k - 1) blocks costs ~253 squarings +
// 14 multiplies instead of ~500 ops for plain square-and-multiply —
// this is the hot op of pubkey decompression (one per compressed key).
void f_sqrt_candidate(F4 &out, const F4 &a) {
  F4 x2, x3, x6, x9, x11, x22, x44, x88, x176, x220, x223, t;
  auto sqn = [](F4 &r, const F4 &v, int n) {
    r = v;
    for (int i = 0; i < n; ++i) f_sqr(r, r);
  };
  f_sqr(x2, a);
  f_mul(x2, x2, a);  // x^(2^2 - 1)
  f_sqr(x3, x2);
  f_mul(x3, x3, a);  // x^(2^3 - 1)
  sqn(t, x3, 3);
  f_mul(x6, t, x3);
  sqn(t, x6, 3);
  f_mul(x9, t, x3);
  sqn(t, x9, 2);
  f_mul(x11, t, x2);
  sqn(t, x11, 11);
  f_mul(x22, t, x11);
  sqn(t, x22, 22);
  f_mul(x44, t, x22);
  sqn(t, x44, 44);
  f_mul(x88, t, x44);
  sqn(t, x88, 88);
  f_mul(x176, t, x88);
  sqn(t, x176, 44);
  f_mul(x220, t, x44);
  sqn(t, x220, 3);
  f_mul(x223, t, x3);  // x^(2^223 - 1)
  sqn(t, x223, 23);
  f_mul(t, t, x22);
  sqn(t, t, 6);
  f_mul(t, t, x2);
  sqn(t, t, 2);
  out = t;
}

// Decode a SEC1 pubkey into affine (x, y).  Mirrors ecdsa_cpu.decode_pubkey:
// returns false (present=0, auto-invalid) for malformed / off-curve keys.
bool decode_pubkey(const uint8_t *data, size_t len, uint8_t px[32],
                   uint8_t py[32]) {
  static const F4 B7 = {{7, 0, 0, 0}};
  if (len == 33 && (data[0] == 2 || data[0] == 3)) {
    F4 x;
    f_from_be(x, data + 1);
    if (f_ge_p(x)) return false;
    F4 y2, x2;
    f_sqr(x2, x);
    f_mul(y2, x2, x);
    f_add(y2, y2, B7);
    F4 y;
    f_sqrt_candidate(y, y2);
    F4 check;
    f_sqr(check, y);
    if (!f_is_eq(check, y2)) return false;  // non-residue: not on curve
    if ((y.v[0] & 1) != (data[0] & 1)) {
      // y = p - y
      F4 neg = {{P_LIMBS[0], P_LIMBS[1], P_LIMBS[2], P_LIMBS[3]}};
      u128 borrow = 0;
      for (int i = 0; i < 4; ++i) {
        u128 d = (u128)neg.v[i] - y.v[i] - borrow;
        neg.v[i] = (uint64_t)d;
        borrow = (d >> 64) & 1;
      }
      y = neg;
    }
    f_to_be(x, px);
    f_to_be(y, py);
    return true;
  }
  if (len == 65 && data[0] == 4) {
    F4 x, y;
    f_from_be(x, data + 1);
    f_from_be(y, data + 33);
    if (f_ge_p(x) || f_ge_p(y)) return false;
    // on-curve check: y^2 == x^3 + 7.  (0,0) fails: 0 != 7 — matching the
    // oracle, which treats the infinity encoding as not-on-curve.
    F4 lhs, x2, rhs;
    f_sqr(lhs, y);
    f_sqr(x2, x);
    f_mul(rhs, x2, x);
    f_add(rhs, rhs, B7);
    if (!f_is_eq(lhs, rhs)) return false;
    memcpy(px, data + 1, 32);
    memcpy(py, data + 33, 32);
    return true;
  }
  return false;
}

// BIP340 lift_x: the EVEN-y point with x-coordinate `x32` (big-endian).
// Mirrors ecdsa_cpu.lift_x — taproot output keys are x-only; an off-curve
// x makes the spend consensus-invalid.
bool lift_x(const uint8_t x32[32], uint8_t px[32], uint8_t py[32]) {
  static const F4 B7 = {{7, 0, 0, 0}};
  F4 x;
  f_from_be(x, x32);
  if (f_ge_p(x)) return false;
  F4 y2, x2;
  f_sqr(x2, x);
  f_mul(y2, x2, x);
  f_add(y2, y2, B7);
  F4 y;
  f_sqrt_candidate(y, y2);
  F4 check;
  f_sqr(check, y);
  if (!f_is_eq(check, y2)) return false;  // non-residue: not on curve
  if (y.v[0] & 1) {
    // y = p - y (pick the even root)
    F4 neg = {{P_LIMBS[0], P_LIMBS[1], P_LIMBS[2], P_LIMBS[3]}};
    u128 borrow = 0;
    for (int i = 0; i < 4; ++i) {
      u128 d = (u128)neg.v[i] - y.v[i] - borrow;
      neg.v[i] = (uint64_t)d;
      borrow = (d >> 64) & 1;
    }
    y = neg;
  }
  memcpy(px, x32, 32);
  f_to_be(y, py);
  return true;
}

// BIP340-style tagged hash: SHA256(SHA256(tag) || SHA256(tag) || data).
// The two tag digests taproot needs are computed once per process.
struct TagMidstate {
  uint8_t th[32];
  explicit TagMidstate(const char *tag) {
    sha256(reinterpret_cast<const uint8_t *>(tag), strlen(tag), th);
  }
};

void tagged_hash_init(Sha256 &h, const TagMidstate &tag) {
  h.update(tag.th, 32);
  h.update(tag.th, 32);
}

const TagMidstate &tap_sighash_tag() {
  static const TagMidstate t("TapSighash");
  return t;
}

const TagMidstate &bip340_challenge_tag() {
  static const TagMidstate t("BIP0340/challenge");
  return t;
}

const TagMidstate &tap_leaf_tag() {
  static const TagMidstate t("TapLeaf");
  return t;
}

// Curve order n, big-endian — sighash digests are reduced mod n before
// packing (parity with NativeVerifier.verify_batch's `z % CURVE_N`).
const uint8_t N_BE[32] = {0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF,
                          0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFF, 0xFE,
                          0xBA, 0xAE, 0xDC, 0xE6, 0xAF, 0x48, 0xA0, 0x3B,
                          0xBF, 0xD2, 0x5E, 0x8C, 0xD0, 0x36, 0x41, 0x41};

void reduce_mod_n(uint8_t z[32]) {
  if (memcmp(z, N_BE, 32) < 0) return;  // z < n (z < 2^256 < 2n: one sub)
  int borrow = 0;
  for (int i = 31; i >= 0; --i) {
    int d = int(z[i]) - int(N_BE[i]) - borrow;
    borrow = d < 0;
    z[i] = uint8_t(d & 0xFF);
  }
}

// ---------------------------------------------------------------------------
// Wire parsing (mirrors tpunode/wire.py Reader/Tx.deserialize).
// ---------------------------------------------------------------------------

struct Cursor {
  const uint8_t *p;
  const uint8_t *end;
  bool ok = true;

  size_t remaining() const { return size_t(end - p); }

  bool need(size_t n) {
    if (!ok || remaining() < n) {
      ok = false;
      return false;
    }
    return true;
  }

  uint32_t u32() {
    if (!need(4)) return 0;
    uint32_t v = uint32_t(p[0]) | (uint32_t(p[1]) << 8) |
                 (uint32_t(p[2]) << 16) | (uint32_t(p[3]) << 24);
    p += 4;
    return v;
  }

  uint64_t u64() {
    if (!need(8)) return 0;
    uint64_t v = 0;
    for (int i = 7; i >= 0; --i) v = (v << 8) | p[i];
    p += 8;
    return v;
  }

  uint64_t varint() {
    // Rejects non-minimal encodings (Bitcoin Core ReadCompactSize): txid
    // and sighash here are dsha256 over RAW spans, so accepting e.g. an
    // input count of "fd 01 00" would hash different bytes than the
    // canonically re-serializing Python reference path.
    if (!need(1)) return 0;
    uint8_t first = *p++;
    if (first < 0xFD) return first;
    uint64_t v, lo;
    if (first == 0xFD) {
      if (!need(2)) return 0;
      v = uint64_t(p[0]) | (uint64_t(p[1]) << 8);
      p += 2;
      lo = 0xFD;
    } else if (first == 0xFE) {
      v = u32();
      lo = 0x10000;
    } else {
      v = u64();
      lo = 0x100000000ULL;
    }
    if (ok && v < lo) ok = false;
    return ok ? v : 0;
  }

  const uint8_t *bytes(size_t n) {
    if (!need(n)) return nullptr;
    const uint8_t *r = p;
    p += n;
    return r;
  }
};

// Witness spans kept per input: enough for every template we extract
// (multisig needs dummy + 16 sigs + script = 18); larger witnesses keep
// their true count but only the first spans, and no template matches them.
const int MAX_WIT_SPANS = 19;

struct InSpan {
  const uint8_t *prevout;  // 36 bytes (txid + index)
  const uint8_t *script;
  uint32_t script_len;
  uint32_t sequence;
  uint32_t wit_count = 0;
  const uint8_t *wit[MAX_WIT_SPANS];
  uint32_t wit_len[MAX_WIT_SPANS];
};

struct OutSpan {
  const uint8_t *start;  // value(8) + varstr(script): contiguous raw span
  uint32_t len;
  int64_t value;
};

struct TxSpan {
  const uint8_t *version;        // 4 bytes
  const uint8_t *inout_start;    // varint(n_in) .. outputs end (witness-free)
  uint32_t inout_len;
  const uint8_t *locktime;       // 4 bytes
  const uint8_t *outputs_start;  // contiguous serialized outputs region
  uint32_t outputs_len;
  std::vector<InSpan> ins;
  std::vector<OutSpan> outs;
  uint8_t txid[32];
  // lazy BIP143 per-tx caches (flag 1 = computed)
  uint8_t hash_prevouts[32], hash_sequence[32], hash_outputs[32];
  bool hp = false, hs = false, ho = false;
};

// Parse one tx at the cursor.  Returns false on malformed data.
bool parse_tx(Cursor &c, TxSpan &tx, bool compute_txid) {
  tx.version = c.bytes(4);
  if (!c.ok) return false;
  bool segwit = c.remaining() >= 2 && c.p[0] == 0x00 && c.p[1] == 0x01;
  if (segwit) c.p += 2;
  tx.inout_start = c.p;
  uint64_t n_in = c.varint();
  // Clamp by the minimum encoded size (36B prevout + 1B script len + 4B
  // sequence) BEFORE allocating: a tiny malformed buffer claiming 2^24
  // inputs must fail here, not after a GB-scale transient resize.
  if (!c.ok || n_in > c.remaining() / 41) return false;
  tx.ins.resize(n_in);
  for (uint64_t i = 0; i < n_in; ++i) {
    InSpan &in = tx.ins[i];
    in.prevout = c.bytes(36);
    uint64_t slen = c.varint();
    if (!c.ok || slen > c.remaining()) return false;
    in.script = c.bytes(slen);
    in.script_len = uint32_t(slen);
    in.sequence = c.u32();
    if (!c.ok) return false;
  }
  uint64_t n_out = c.varint();
  // Same pre-allocation clamp: an output is at least value(8) + varstr(1).
  if (!c.ok || n_out > c.remaining() / 9) return false;
  tx.outs.resize(n_out);
  tx.outputs_start = c.p;
  for (uint64_t i = 0; i < n_out; ++i) {
    OutSpan &out = tx.outs[i];
    out.start = c.p;
    out.value = int64_t(c.u64());
    uint64_t slen = c.varint();
    if (!c.ok || slen > c.remaining()) return false;
    c.bytes(slen);
    out.len = uint32_t(c.p - out.start);
    if (!c.ok) return false;
  }
  tx.outputs_len = uint32_t(c.p - tx.outputs_start);
  tx.inout_len = uint32_t(c.p - tx.inout_start);
  if (segwit) {
    for (uint64_t i = 0; i < n_in; ++i) {
      uint64_t cnt = c.varint();
      if (!c.ok || cnt > (1u << 20)) return false;
      InSpan &in = tx.ins[i];
      in.wit_count = uint32_t(cnt);
      for (uint64_t w = 0; w < cnt; ++w) {
        uint64_t wlen = c.varint();
        if (!c.ok || wlen > c.remaining()) return false;
        const uint8_t *wp = c.bytes(wlen);
        if (w < MAX_WIT_SPANS) {
          in.wit[w] = wp;
          in.wit_len[w] = uint32_t(wlen);
        }
      }
    }
  }
  tx.locktime = c.bytes(4);
  if (!c.ok) return false;
  if (compute_txid) {
    // txid = dsha256 of the witness-stripped serialization
    Sha256 h1;
    h1.update(tx.version, 4);
    h1.update(tx.inout_start, tx.inout_len);
    h1.update(tx.locktime, 4);
    uint8_t t[32];
    h1.final(t);
    sha256(t, 32, tx.txid);
  }
  return true;
}

// ---------------------------------------------------------------------------
// DER signature parsing (mirrors ecdsa_cpu.parse_der_signature's lax rules).
// r/s land right-aligned in 32-byte big-endian buffers; values with more
// than 32 significant bytes (> 2^256, possible under lax DER) come out as
// zero — zero fails the 0 < r,s < n range check downstream exactly like the
// oversized original would, with no aliasing.
// ---------------------------------------------------------------------------

bool parse_der(const uint8_t *sig, size_t len, uint8_t r[32], uint8_t s[32]) {
  if (len < 8 || sig[0] != 0x30) return false;
  if (sig[1] != len - 2) return false;
  if (sig[2] != 0x02) return false;
  size_t rlen = sig[3];
  size_t pos = 4 + rlen;
  if (pos + 1 >= len) return false;  // need the 0x02 and slen bytes
  if (sig[pos] != 0x02) return false;
  size_t slen = sig[pos + 1];
  if (pos + 2 + slen != len) return false;
  const uint8_t *rp = sig + 4;
  const uint8_t *sp = sig + pos + 2;
  // strip leading zeros; reject (as out-of-range zero) if > 32 bytes remain
  while (rlen > 0 && *rp == 0) { ++rp; --rlen; }
  while (slen > 0 && *sp == 0) { ++sp; --slen; }
  memset(r, 0, 32);
  memset(s, 0, 32);
  if (rlen <= 32) memcpy(r + 32 - rlen, rp, rlen);
  if (slen <= 32) memcpy(s + 32 - slen, sp, slen);
  return true;
}

// Parse a pushes-only script (OP_0, opcodes 1-75, PUSHDATA1/2) — mirror of
// txverify._parse_pushes.  OP_0 parses as an empty push (the CHECKMULTISIG
// dummy).  Fills at most `max_out` spans; returns the push count or -1 if
// any non-push opcode appears.
int parse_pushes(const uint8_t *script, size_t n,
                 const uint8_t **out, size_t *out_len, int max_out) {
  int count = 0;
  size_t i = 0;
  while (i < n) {
    uint8_t op = script[i++];
    size_t ln;
    if (op == 0) {
      ln = 0;
    } else if (op >= 1 && op <= 75) {
      ln = op;
    } else if (op == 76 && i < n) {
      ln = script[i++];
    } else if (op == 77 && i + 1 < n) {
      ln = size_t(script[i]) | (size_t(script[i + 1]) << 8);
      i += 2;
    } else {
      return -1;
    }
    if (i + ln > n) return -1;
    if (count < max_out) {
      out[count] = script + i;
      out_len[count] = ln;
    }
    ++count;
    i += ln;
  }
  return count;
}

// Bare multisig template: OP_m <key>*n OP_n OP_CHECKMULTISIG, keys 33/65
// bytes — mirror of txverify._parse_multisig.
struct MsigTemplate {
  int m = 0, n = 0;
  const uint8_t *keys[16];
  size_t key_len[16];
};

bool parse_multisig(const uint8_t *s, size_t len, MsigTemplate &out) {
  if (len < 3 || s[len - 1] != 0xAE) return false;
  int n_op = s[len - 2], m_op = s[0];
  if (n_op < 0x51 || n_op > 0x60 || m_op < 0x51 || m_op > 0x60) return false;
  out.n = n_op - 0x50;
  out.m = m_op - 0x50;
  if (out.m > out.n) return false;
  size_t i = 1, end = len - 2;
  int k = 0;
  while (i < end) {
    size_t ln = s[i++];
    if ((ln != 33 && ln != 65) || i + ln > end || k >= 16) return false;
    out.keys[k] = s + i;
    out.key_len[k] = ln;
    ++k;
    i += ln;
  }
  return k == out.n;
}

// Bare P2PK template <33/65-byte pubkey> OP_CHECKSIG (also the P2WSH
// single-key witness-script shape); returns the key span or nullptr.
const uint8_t *is_p2pk_script(const uint8_t *s, uint32_t len,
                              size_t *key_len) {
  if (len == 35 && s[0] == 33 && s[34] == 0xAC) {
    *key_len = 33;
    return s + 1;
  }
  if (len == 67 && s[0] == 65 && s[66] == 0xAC) {
    *key_len = 65;
    return s + 1;
  }
  return nullptr;
}

// Single-push scriptSig (the bare-P2PK spend shape) — mirror of the
// wants_amount shape check.
bool single_push_script_sig(const InSpan &in) {
  return in.script_len >= 10 && in.script_len == uint32_t(in.script[0]) + 1;
}

// The spend-template classifier shared by txx_scan (capacity) and
// txx_extract (emission) — mirror of the template dispatch in
// txverify.extract_sig_items.
struct InTemplate {
  enum Kind { UNSUPPORTED, SINGLE, MULTISIG } kind = UNSUPPORTED;
  bool segwit = false;  // BIP143 digest (amount required)
  const uint8_t *sig = nullptr;  // SINGLE
  size_t sig_len = 0;
  const uint8_t *pub = nullptr;
  size_t pub_len = 0;
  MsigTemplate ms;  // MULTISIG
  const uint8_t *sigs[16];
  size_t sig_lens[16];
  // script_code: redeem/witness script for MULTISIG; for SINGLE, set
  // only when it is NOT the derived P2PKH template (P2WSH single-key's
  // witness script, bare P2PK's prevout script)
  const uint8_t *sc = nullptr;
  size_t sc_len = 0;
};

// P2WSH multisig witness shape: [<empty dummy>, <sig>*m, script].
bool is_msig_witness(const InSpan &in, InTemplate &t) {
  if (in.wit_count < 3 || in.wit_count > 18) return false;
  if (in.wit_len[0] != 0) return false;
  uint32_t last = in.wit_count - 1;
  if (!parse_multisig(in.wit[last], in.wit_len[last], t.ms)) return false;
  if (int(in.wit_count) - 2 != t.ms.m) return false;
  for (int i = 0; i < t.ms.m; ++i) {
    t.sigs[i] = in.wit[1 + i];
    t.sig_lens[i] = in.wit_len[1 + i];
  }
  t.sc = in.wit[last];
  t.sc_len = in.wit_len[last];
  return true;
}

void classify_input(const InSpan &in, InTemplate &t) {
  if (in.script_len == 0 && in.wit_count == 2) {
    if (in.wit_len[1] == 33 || in.wit_len[1] == 65) {
      // P2WPKH: [sig, pubkey]
      t.kind = InTemplate::SINGLE;
      t.segwit = true;
      t.sig = in.wit[0]; t.sig_len = in.wit_len[0];
      t.pub = in.wit[1]; t.pub_len = in.wit_len[1];
      return;
    }
    size_t klen;
    const uint8_t *key = is_p2pk_script(in.wit[1], in.wit_len[1], &klen);
    if (key != nullptr) {
      // P2WSH single-key: [sig, <key> OP_CHECKSIG]; the witness script
      // is the BIP143 script_code (a non-matching 2-element witness is
      // UNSUPPORTED, not auto-invalid — mirror of txverify)
      t.kind = InTemplate::SINGLE;
      t.segwit = true;
      t.sig = in.wit[0]; t.sig_len = in.wit_len[0];
      t.pub = key; t.pub_len = klen;
      t.sc = in.wit[1]; t.sc_len = in.wit_len[1];
    }
    return;
  }
  if (in.script_len == 0 && is_msig_witness(in, t)) {
    t.kind = InTemplate::MULTISIG;
    t.segwit = true;
    return;
  }
  const uint8_t *pushes[MAX_WIT_SPANS];
  size_t plen[MAX_WIT_SPANS];
  int np = parse_pushes(in.script, in.script_len, pushes, plen, MAX_WIT_SPANS);
  if (np == 2 && (plen[1] == 33 || plen[1] == 65)) {
    // P2PKH
    t.kind = InTemplate::SINGLE;
    t.sig = pushes[0]; t.sig_len = plen[0];
    t.pub = pushes[1]; t.pub_len = plen[1];
    return;
  }
  if (np == 1 && plen[0] == 22 && pushes[0][0] == 0x00 &&
      pushes[0][1] == 0x14 && in.wit_count == 2) {
    // P2SH-P2WPKH
    t.kind = InTemplate::SINGLE;
    t.segwit = true;
    t.sig = in.wit[0]; t.sig_len = in.wit_len[0];
    t.pub = in.wit[1]; t.pub_len = in.wit_len[1];
    return;
  }
  if (np == 1 && plen[0] == 34 && pushes[0][0] == 0x00 &&
      pushes[0][1] == 0x20 && is_msig_witness(in, t)) {
    // P2SH-P2WSH multisig
    t.kind = InTemplate::MULTISIG;
    t.segwit = true;
    return;
  }
  if (np == 1 && plen[0] == 34 && pushes[0][0] == 0x00 &&
      pushes[0][1] == 0x20 && in.wit_count == 2) {
    size_t klen;
    const uint8_t *key = is_p2pk_script(in.wit[1], in.wit_len[1], &klen);
    if (key != nullptr) {
      // P2SH-P2WSH single-key
      t.kind = InTemplate::SINGLE;
      t.segwit = true;
      t.sig = in.wit[0]; t.sig_len = in.wit_len[0];
      t.pub = key; t.pub_len = klen;
      t.sc = in.wit[1]; t.sc_len = in.wit_len[1];
      return;
    }
  }
  if (np >= 2 && np <= 18 && plen[0] == 0 &&
      parse_multisig(pushes[np - 1], plen[np - 1], t.ms) &&
      np - 2 == t.ms.m) {
    // legacy P2SH multisig: OP_0 <sig>*m <redeemScript>
    t.kind = InTemplate::MULTISIG;
    for (int i = 0; i < t.ms.m; ++i) {
      t.sigs[i] = pushes[1 + i];
      t.sig_lens[i] = plen[1 + i];
    }
    t.sc = pushes[np - 1];
    t.sc_len = plen[np - 1];
    return;
  }
}

// ---------------------------------------------------------------------------
// Sighash computation (mirrors tpunode/sighash.py byte for byte).
// ---------------------------------------------------------------------------

const int SIGHASH_NONE = 2, SIGHASH_SINGLE = 3;
const int SIGHASH_ANYONECANPAY = 0x80, SIGHASH_FORKID = 0x40;

void put_varint(std::vector<uint8_t> &buf, uint64_t n) {
  if (n < 0xFD) {
    buf.push_back(uint8_t(n));
  } else if (n <= 0xFFFF) {
    buf.push_back(0xFD);
    buf.push_back(uint8_t(n));
    buf.push_back(uint8_t(n >> 8));
  } else if (n <= 0xFFFFFFFFULL) {
    buf.push_back(0xFE);
    for (int i = 0; i < 4; ++i) buf.push_back(uint8_t(n >> (8 * i)));
  } else {
    buf.push_back(0xFF);
    for (int i = 0; i < 8; ++i) buf.push_back(uint8_t(n >> (8 * i)));
  }
}

void put_u32(std::vector<uint8_t> &buf, uint32_t v) {
  for (int i = 0; i < 4; ++i) buf.push_back(uint8_t(v >> (8 * i)));
}

// Legacy (pre-segwit) digest -> out[32] big-endian (already the z bytes).
void legacy_sighash(const TxSpan &tx, size_t index, const uint8_t *script_code,
                    size_t sc_len, int hashtype, std::vector<uint8_t> &scratch,
                    uint8_t out[32]) {
  int base = hashtype & 0x1F;
  if (base == SIGHASH_SINGLE && index >= tx.outs.size()) {
    memset(out, 0, 32);
    out[31] = 1;  // the historical "hash = 1" quirk
    return;
  }
  scratch.clear();
  std::vector<uint8_t> &buf = scratch;
  buf.insert(buf.end(), tx.version, tx.version + 4);
  if (hashtype & SIGHASH_ANYONECANPAY) {
    put_varint(buf, 1);
    const InSpan &in = tx.ins[index];
    buf.insert(buf.end(), in.prevout, in.prevout + 36);
    put_varint(buf, sc_len);
    buf.insert(buf.end(), script_code, script_code + sc_len);
    put_u32(buf, in.sequence);
  } else {
    put_varint(buf, tx.ins.size());
    for (size_t i = 0; i < tx.ins.size(); ++i) {
      const InSpan &in = tx.ins[i];
      buf.insert(buf.end(), in.prevout, in.prevout + 36);
      if (i == index) {
        put_varint(buf, sc_len);
        buf.insert(buf.end(), script_code, script_code + sc_len);
      } else {
        buf.push_back(0);
      }
      uint32_t seq = in.sequence;
      if (i != index && (base == SIGHASH_NONE || base == SIGHASH_SINGLE))
        seq = 0;
      put_u32(buf, seq);
    }
  }
  if (base == SIGHASH_NONE) {
    put_varint(buf, 0);
  } else if (base == SIGHASH_SINGLE) {
    put_varint(buf, index + 1);
    for (size_t i = 0; i < index; ++i) {
      for (int k = 0; k < 8; ++k) buf.push_back(0xFF);  // value = -1
      buf.push_back(0);                                 // empty script
    }
    const OutSpan &o = tx.outs[index];
    buf.insert(buf.end(), o.start, o.start + o.len);
  } else {
    put_varint(buf, tx.outs.size());
    buf.insert(buf.end(), tx.outputs_start, tx.outputs_start + tx.outputs_len);
  }
  buf.insert(buf.end(), tx.locktime, tx.locktime + 4);
  put_u32(buf, uint32_t(hashtype));
  dsha256(buf.data(), buf.size(), out);
}

// BIP143 (segwit v0 / BCH FORKID) digest -> out[32].
void bip143_sighash(TxSpan &tx, size_t index, const uint8_t *script_code,
                    size_t sc_len, int64_t amount, int hashtype,
                    std::vector<uint8_t> &scratch, uint8_t out[32]) {
  int base = hashtype & 0x1F;
  bool acp = (hashtype & SIGHASH_ANYONECANPAY) != 0;
  uint8_t zero32[32] = {0};
  const uint8_t *hash_prevouts = zero32, *hash_sequence = zero32,
                *hash_outputs = zero32;
  uint8_t single_out[32];
  if (!acp) {
    if (!tx.hp) {
      Sha256 h;
      for (const InSpan &in : tx.ins) h.update(in.prevout, 36);
      uint8_t t[32];
      h.final(t);
      sha256(t, 32, tx.hash_prevouts);
      tx.hp = true;
    }
    hash_prevouts = tx.hash_prevouts;
  }
  if (!acp && base != SIGHASH_NONE && base != SIGHASH_SINGLE) {
    if (!tx.hs) {
      Sha256 h;
      for (const InSpan &in : tx.ins) {
        uint8_t seq[4] = {uint8_t(in.sequence), uint8_t(in.sequence >> 8),
                          uint8_t(in.sequence >> 16),
                          uint8_t(in.sequence >> 24)};
        h.update(seq, 4);
      }
      uint8_t t[32];
      h.final(t);
      sha256(t, 32, tx.hash_sequence);
      tx.hs = true;
    }
    hash_sequence = tx.hash_sequence;
  }
  if (base != SIGHASH_NONE && base != SIGHASH_SINGLE) {
    if (!tx.ho) {
      dsha256(tx.outputs_start, tx.outputs_len, tx.hash_outputs);
      tx.ho = true;
    }
    hash_outputs = tx.hash_outputs;
  } else if (base == SIGHASH_SINGLE && index < tx.outs.size()) {
    dsha256(tx.outs[index].start, tx.outs[index].len, single_out);
    hash_outputs = single_out;
  }
  scratch.clear();
  std::vector<uint8_t> &buf = scratch;
  const InSpan &in = tx.ins[index];
  buf.insert(buf.end(), tx.version, tx.version + 4);
  buf.insert(buf.end(), hash_prevouts, hash_prevouts + 32);
  buf.insert(buf.end(), hash_sequence, hash_sequence + 32);
  buf.insert(buf.end(), in.prevout, in.prevout + 36);
  put_varint(buf, sc_len);
  buf.insert(buf.end(), script_code, script_code + sc_len);
  for (int i = 0; i < 8; ++i) buf.push_back(uint8_t(uint64_t(amount) >> (8 * i)));
  put_u32(buf, in.sequence);
  buf.insert(buf.end(), hash_outputs, hash_outputs + 32);
  buf.insert(buf.end(), tx.locktime, tx.locktime + 4);
  put_u32(buf, uint32_t(hashtype));
  dsha256(buf.data(), buf.size(), out);
}

// ---------------------------------------------------------------------------
// BIP341 (taproot) sighash — mirrors tpunode/sighash.py bip341_sighash.
// All hashes are SINGLE SHA-256 (unlike legacy/BIP143's double).
// ---------------------------------------------------------------------------

bool valid_taproot_hashtype(int ht) {
  return ht == 0x00 || ht == 0x01 || ht == 0x02 || ht == 0x03 ||
         ht == 0x81 || ht == 0x82 || ht == 0x83;
}

// Resolved prevout (amount, scriptPubKey) rows for one tx's inputs —
// BIP341 signs over the whole spent-output set.
struct TapPrevouts {
  std::vector<int64_t> amounts;
  std::vector<const uint8_t *> scripts;
  std::vector<uint32_t> script_lens;
  std::vector<bool> have;  // per input: both amount and script resolved
  bool built = false;
};

// Per-tx cache of the five whole-tx hashes (valid for one extract call:
// amounts/scripts depend on the call's ext_* resolution).
struct TapTxHashes {
  uint8_t prevouts[32], amounts[32], scriptpubkeys[32], sequences[32],
      outputs[32];
  bool pv = false, am = false, sp = false, sq = false, out = false;
};

// Signature message -> out[32]: keypath (ext_flag = 0) when `leaf_hash`
// is nullptr; script path (ext_flag = 1, BIP342 extension: tapleaf hash
// ∥ key_version 0 ∥ codesep 0xFFFFFFFF) otherwise.  `annex` is the full
// witness element (0x50-prefixed) or nullptr.  Requires tp.have[...]
// resolution per the hash_type (caller checks); returns false when the
// spend is structurally INVALID under BIP341 (bad hash_type,
// SIGHASH_SINGLE with no matching output) — the caller emits an
// auto-invalid item, not unsupported.
bool bip341_sighash(TxSpan &tx, size_t index, int hashtype,
                    const uint8_t *annex, size_t annex_len,
                    const TapPrevouts &tp, TapTxHashes &th,
                    std::vector<uint8_t> &scratch, uint8_t out[32],
                    const uint8_t *leaf_hash = nullptr) {
  if (!valid_taproot_hashtype(hashtype)) return false;
  int base = hashtype & 3;
  bool acp = (hashtype & SIGHASH_ANYONECANPAY) != 0;
  if (base == SIGHASH_SINGLE && index >= tx.outs.size()) return false;

  scratch.clear();
  std::vector<uint8_t> &buf = scratch;
  buf.push_back(uint8_t(hashtype));
  buf.insert(buf.end(), tx.version, tx.version + 4);
  buf.insert(buf.end(), tx.locktime, tx.locktime + 4);
  if (!acp) {
    if (!th.pv) {
      Sha256 h;
      for (const InSpan &in : tx.ins) h.update(in.prevout, 36);
      h.final(th.prevouts);
      th.pv = true;
    }
    if (!th.am) {
      Sha256 h;
      for (size_t i = 0; i < tx.ins.size(); ++i) {
        uint64_t a = uint64_t(tp.amounts[i]);
        uint8_t le[8];
        for (int k = 0; k < 8; ++k) le[k] = uint8_t(a >> (8 * k));
        h.update(le, 8);
      }
      h.final(th.amounts);
      th.am = true;
    }
    if (!th.sp) {
      Sha256 h;
      std::vector<uint8_t> vs;
      for (size_t i = 0; i < tx.ins.size(); ++i) {
        vs.clear();
        put_varint(vs, tp.script_lens[i]);
        h.update(vs.data(), vs.size());
        h.update(tp.scripts[i], tp.script_lens[i]);
      }
      h.final(th.scriptpubkeys);
      th.sp = true;
    }
    if (!th.sq) {
      Sha256 h;
      for (const InSpan &in : tx.ins) {
        uint8_t seq[4] = {uint8_t(in.sequence), uint8_t(in.sequence >> 8),
                          uint8_t(in.sequence >> 16),
                          uint8_t(in.sequence >> 24)};
        h.update(seq, 4);
      }
      h.final(th.sequences);
      th.sq = true;
    }
    buf.insert(buf.end(), th.prevouts, th.prevouts + 32);
    buf.insert(buf.end(), th.amounts, th.amounts + 32);
    buf.insert(buf.end(), th.scriptpubkeys, th.scriptpubkeys + 32);
    buf.insert(buf.end(), th.sequences, th.sequences + 32);
  }
  if (base != SIGHASH_NONE && base != SIGHASH_SINGLE) {
    if (!th.out) {
      sha256(tx.outputs_start, tx.outputs_len, th.outputs);
      th.out = true;
    }
    buf.insert(buf.end(), th.outputs, th.outputs + 32);
  }
  int ext_flag = leaf_hash != nullptr ? 1 : 0;
  buf.push_back(uint8_t(ext_flag * 2 + (annex != nullptr ? 1 : 0)));
  const InSpan &in = tx.ins[index];
  if (acp) {
    buf.insert(buf.end(), in.prevout, in.prevout + 36);
    uint64_t a = uint64_t(tp.amounts[index]);
    for (int k = 0; k < 8; ++k) buf.push_back(uint8_t(a >> (8 * k)));
    put_varint(buf, tp.script_lens[index]);
    buf.insert(buf.end(), tp.scripts[index],
               tp.scripts[index] + tp.script_lens[index]);
    put_u32(buf, in.sequence);
  } else {
    put_u32(buf, uint32_t(index));
  }
  if (annex != nullptr) {
    std::vector<uint8_t> va;
    put_varint(va, annex_len);
    va.insert(va.end(), annex, annex + annex_len);
    uint8_t ah[32];
    sha256(va.data(), va.size(), ah);
    buf.insert(buf.end(), ah, ah + 32);
  }
  if (base == SIGHASH_SINGLE) {
    uint8_t oh[32];
    sha256(tx.outs[index].start, tx.outs[index].len, oh);
    buf.insert(buf.end(), oh, oh + 32);
  }
  if (leaf_hash != nullptr) {
    // BIP342 extension: tapleaf ∥ key_version 0 ∥ codesep "none" sentinel
    buf.insert(buf.end(), leaf_hash, leaf_hash + 32);
    buf.push_back(0x00);
    for (int k = 0; k < 4; ++k) buf.push_back(0xFF);
  }
  Sha256 h;
  tagged_hash_init(h, tap_sighash_tag());
  uint8_t epoch = 0x00;
  h.update(&epoch, 1);
  h.update(buf.data(), buf.size());
  h.final(out);
  return true;
}

// The canonical single-key tapscript: <32-byte x-only key> OP_CHECKSIG.
bool is_single_key_tapscript(const uint8_t *s, uint32_t len) {
  return len == 34 && s[0] == 0x20 && s[33] == 0xAC;
}

// BIP341 control block: leaf version 0xC0, internal key, 0-128 path nodes.
bool valid_control_block(const uint8_t *cb, uint32_t len) {
  return len >= 33 && len <= 33 + 128 * 32 && (len - 33) % 32 == 0 &&
         (cb[0] & 0xFE) == 0xC0;
}

// Locate an output's scriptPubKey inside its raw span (value(8) +
// varstr(script)).
bool out_script(const OutSpan &o, const uint8_t **script, uint32_t *len) {
  Cursor c{o.start + 8, o.start + o.len};
  uint64_t slen = c.varint();
  if (!c.ok || slen > c.remaining()) return false;
  *script = c.p;
  *len = uint32_t(slen);
  return true;
}

bool is_p2tr_script(const uint8_t *s, uint32_t len) {
  return len == 34 && s[0] == 0x51 && s[1] == 0x20;
}

// Per-extract-call decoded-pubkey cache: decompression costs a field sqrt
// (~a modexp), and real workloads reuse keys heavily (one wallet key funds
// many inputs; multisig windows retry the same keys).  Bounded so a block
// full of distinct garbage keys cannot balloon memory.
struct PubkeyEntry {
  uint8_t px[32], py[32];
  bool ok;
};
using PubkeyCache = std::unordered_map<std::string, PubkeyEntry>;
const size_t PUBKEY_CACHE_MAX = 1 << 17;

bool decode_pubkey_cached(PubkeyCache &cache, const uint8_t *data, size_t len,
                          uint8_t px[32], uint8_t py[32]) {
  if (cache.size() >= PUBKEY_CACHE_MAX)
    return decode_pubkey(data, len, px, py);
  std::string key(reinterpret_cast<const char *>(data), len);
  auto it = cache.find(key);
  if (it == cache.end()) {
    PubkeyEntry e;
    e.ok = decode_pubkey(data, len, e.px, e.py);
    if (!e.ok) {
      memset(e.px, 0, 32);
      memset(e.py, 0, 32);
    }
    it = cache.emplace(std::move(key), e).first;
  }
  if (!it->second.ok) return false;
  memcpy(px, it->second.px, 32);
  memcpy(py, it->second.py, 32);
  return true;
}

// lift_x through a bounded cache of ITS OWN (a field sqrt per call; real
// taproot workloads reuse output/leaf keys through address reuse).  The
// cache object must be separate from the SEC1 decode cache: any in-band
// namespace tag can be forged by an attacker-controlled scriptSig pubkey
// blob of the right shape, poisoning one lane's entries with the other's
// verdicts (review r5 finding, confirmed by repro).
bool lift_x_cached(PubkeyCache &cache, const uint8_t x32[32], uint8_t px[32],
                   uint8_t py[32], bool *hit) {
  *hit = false;
  if (cache.size() >= PUBKEY_CACHE_MAX) return lift_x(x32, px, py);
  std::string key(reinterpret_cast<const char *>(x32), 32);
  auto it = cache.find(key);
  *hit = it != cache.end();
  if (it == cache.end()) {
    PubkeyEntry e;
    e.ok = lift_x(x32, e.px, e.py);
    if (!e.ok) {
      memset(e.px, 0, 32);
      memset(e.py, 0, 32);
    }
    it = cache.emplace(std::move(key), e).first;
  }
  if (!it->second.ok) return false;
  memcpy(px, it->second.px, 32);
  memcpy(py, it->second.py, 32);
  return true;
}

// ---------------------------------------------------------------------------
// Intra-block prevout amount map: (txid, vout) -> satoshis.
// ---------------------------------------------------------------------------

struct OutpointKey {
  uint8_t b[36];
  bool operator==(const OutpointKey &o) const {
    return memcmp(b, o.b, 36) == 0;
  }
};

struct OutpointHash {
  size_t operator()(const OutpointKey &k) const {
    uint64_t h;  // txids are uniform: first 8 bytes are a fine hash, mix vout
    memcpy(&h, k.b, 8);
    uint32_t vout;
    memcpy(&vout, k.b + 32, 4);
    return size_t(h ^ (uint64_t(vout) * 0x9E3779B97F4A7C15ULL));
  }
};

// Intra-block prevout (amount, scriptPubKey) value; the map lives on the
// parse handle so tx-range shard extractions share ONE build (read-only
// after txx_build_intra_h) instead of each rebuilding it per range.
struct PrevoutInfo {
  int64_t value;
  const uint8_t *script;
  uint32_t script_len;
};
using PrevoutMap = std::unordered_map<OutpointKey, PrevoutInfo, OutpointHash>;

void build_prevout_map(const std::vector<TxSpan> &txs, PrevoutMap &map) {
  size_t total_outs = 0;
  for (const TxSpan &tx : txs) total_outs += tx.outs.size();
  map.reserve(total_outs * 2);
  for (const TxSpan &tx : txs) {
    for (size_t vout = 0; vout < tx.outs.size(); ++vout) {
      OutpointKey key;
      memcpy(key.b, tx.txid, 32);
      uint32_t v32 = uint32_t(vout);
      memcpy(key.b + 32, &v32, 4);
      PrevoutInfo info{tx.outs[vout].value, nullptr, 0};
      out_script(tx.outs[vout], &info.script, &info.script_len);
      map[key] = info;
    }
  }
}

// What an extract call says of its own phases (ISSUE 42): inputs and
// accumulated nanoseconds by digest kind, and the x-only lifts.  One
// monotonic clock read where a phase begins and one where it ends (a phase
// that follows another takes the other's end as its beginning): ~25 ns a
// read against >= 1 us of hashing or a field square root.  A caller that
// passes no `stats` pays no read.
enum ExtractStat {
  ST_LEGACY_N, ST_LEGACY_NS, ST_BIP143_N, ST_BIP143_NS, ST_BIP341_N,
  ST_BIP341_NS, ST_LIFT_CALLS, ST_LIFT_HITS, ST_LIFT_NS, ST_COUNT
};

struct PhaseClock {
  int64_t *stats;
  int64_t last = 0;
  static int64_t now() {
    timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return int64_t(ts.tv_sec) * 1000000000LL + ts.tv_nsec;
  }
  void begin() {
    if (stats != nullptr) last = now();
  }
  void end(int slot) {
    if (stats == nullptr) return;
    int64_t t = now();
    stats[slot] += t - last;
    last = t;
  }
  void count(int slot) {
    if (stats != nullptr) ++stats[slot];
  }
};

}  // namespace

// ---------------------------------------------------------------------------
// C ABI
// ---------------------------------------------------------------------------

extern "C" {

// Pass 0: walk tx structure, return tx count parsed and the item-capacity
// upper bound for txx_extract (1 per input; m*(n-m+1) candidates for a
// multisig template input).  tx_count == -1 parses to end of buffer.
// Returns number of txs, or -1 on malformed data.
long txx_scan(const uint8_t *data, long len, long tx_count,
              long *capacity_out) {
  Cursor c{data, data + len};
  long txs = 0;
  long capacity = 0;
  while (c.ok && (tx_count < 0 ? c.remaining() > 0 : txs < tx_count)) {
    TxSpan tx;
    if (!parse_tx(c, tx, /*compute_txid=*/false)) return -1;
    for (const InSpan &in : tx.ins) {
      InTemplate t;
      classify_input(in, t);
      capacity += t.kind == InTemplate::MULTISIG
                      ? long(t.ms.m) * (t.ms.n - t.ms.m + 1)
                      : 1;
    }
    ++txs;
  }
  // exact consumption: trailing bytes after tx_count txs are malformed
  // (mirror of wire.LazyBlock/LazyTx, which raise on trailing bytes)
  if (tx_count >= 0 && (txs != tx_count || c.remaining() > 0)) return -1;
  if (capacity_out) *capacity_out = capacity;
  return txs;
}

// Per-input prevout listing for the embedder's amount oracle: one row per
// input in flat parse order (coinbase included, so indices align with
// txx_extract's ext_amounts), carrying the prevout txid+vout and whether
// the input could consume a BIP143 amount (bch: every non-coinbase input;
// otherwise any input with a >=2-item witness — mirror of
// txverify.wants_amount).  Lets block ingest resolve amounts through
// NodeConfig.prevout_lookup without ever parsing txs in Python.
// Returns total input count, or -1 malformed / -2 capacity exceeded.
long txx_prevouts(const uint8_t *data, long len, long tx_count, int bch,
                  long capacity, uint8_t *txids32, int64_t *vouts,
                  uint8_t *wants) {
  Cursor c{data, data + len};
  long n = 0, flat = 0;
  static const uint8_t ZERO_TXID[32] = {0};
  while (c.ok && (tx_count < 0 ? c.remaining() > 0 : n < tx_count)) {
    TxSpan tx;
    if (!parse_tx(c, tx, /*compute_txid=*/false)) return -1;
    // tx-LEVEL witness gate (mirror of txverify.wants_amount): a taproot
    // keypath input digests EVERY input's amount+script, so any witness
    // in the tx makes all of its inputs worth a lookup; a single-push
    // scriptSig (bare-P2PK shape) wants its own prevout script too
    bool tx_has_wit = false;
    for (const InSpan &in : tx.ins) tx_has_wit |= in.wit_count >= 1;
    for (const InSpan &in : tx.ins) {
      if (flat >= capacity) return -2;
      memcpy(txids32 + flat * 32, in.prevout, 32);
      uint32_t vout;  // wire is little-endian; so is every target we build on
      memcpy(&vout, in.prevout + 32, 4);
      // int64 out: a vout >= 2^31 (junk or hostile) must reach the Python
      // prevout_lookup as the true unsigned value, not a negative int
      vouts[flat] = int64_t(vout);
      bool cb = memcmp(in.prevout, ZERO_TXID, 32) == 0;
      wants[flat] =
          (!cb && (bch || tx_has_wit || single_push_script_sig(in))) ? 1 : 0;
      ++flat;
    }
    ++n;
  }
  if (tx_count >= 0 && (n != tx_count || c.remaining() > 0)) return -1;
  return flat;
}

// Extract verifiable signature items from `tx_count` serialized txs.
//
//   flags bit 0: BCH network (FORKID hashtype selects the BIP143-style digest
//                for legacy inputs; amounts required for those)
//   flags bit 1: build and consult the intra-block prevout amount map
//                (block ingest: in-block spends resolve without a UTXO set)
//   ext_amounts: optional per-input amounts, flattened across txs in parse
//                order, -1 = unknown; consulted after the intra-block map
//                (mirror of node._verify_txs's block_outs -> prevout_lookup
//                precedence).  NULL = none.
//
// Per-item outputs (capacity rows each): z/px/py/r/s are 32-byte big-endian
// rows; present[i]=0 marks an auto-invalid item (undecodable pubkey or
// unparseable multisig sig).  item_sig/item_key/item_nsigs/item_nkeys
// locate multisig candidates (0/0/1/1 for single-sig items) — mirror of
// SigItem's candidate fields; combine per-signature verdicts with
// txverify.msig_match.
// Per-tx outputs (tx_count rows): txids (32B), tx_n_inputs, tx_extracted
// (INPUTS extracted), tx_items (device items), tx_sigs (signatures),
// tx_coinbase, tx_unsupported.
//
// Returns the item count, or -1 malformed data / -2 capacity exceeded.
long txx_extract(const uint8_t *data, long len, long tx_count, int flags,
                 const int64_t *ext_amounts, long n_ext, long capacity,
                 uint8_t *z, uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                 uint8_t *present, int32_t *item_tx, int32_t *item_input,
                 int32_t *item_sig, int32_t *item_key, int32_t *item_nsigs,
                 int32_t *item_nkeys,
                 uint8_t *txids, int32_t *tx_n_inputs, int32_t *tx_extracted,
                 int32_t *tx_items, int32_t *tx_sigs,
                 int32_t *tx_coinbase, int32_t *tx_unsupported);

// Handle API: parse once, then run prevout listing and extraction (and any
// retries) over the SAME parsed spans — block ingest with the amount
// oracle previously parsed the region three times (scan for capacity,
// prevouts, extract).  The handle owns a copy of the wire bytes so spans
// stay valid independent of the caller's buffer lifetime.
struct TxxHandle {
  std::vector<uint8_t> data;
  std::vector<TxSpan> txs;
  long capacity = 0;  // candidate item bound
  long inputs = 0;    // total input count (ext_amounts row count)
  // Whole-region intra-block prevout map, built at most once
  // (txx_build_intra_h) and read-only afterwards — the seam that lets
  // tx-range shard extractions run concurrently on worker threads.
  PrevoutMap intra;
  bool intra_built = false;
};

void *txx_parse(const uint8_t *data, long len, long tx_count) {
  TxxHandle *h = new TxxHandle;
  h->data.assign(data, data + len);
  h->txs.reserve(tx_count > 0 ? size_t(tx_count) : 16);
  Cursor c{h->data.data(), h->data.data() + len};
  long n = 0;
  while (c.ok && (tx_count < 0 ? c.remaining() > 0 : n < tx_count)) {
    h->txs.emplace_back();
    if (!parse_tx(c, h->txs.back(), /*compute_txid=*/true)) {
      delete h;
      return nullptr;
    }
    ++n;
  }
  if (tx_count >= 0 && (n != tx_count || c.remaining() > 0)) {
    // exact consumption: trailing bytes after tx_count txs are malformed
    // (mirror of wire.LazyBlock/LazyTx, which raise on trailing bytes)
    delete h;
    return nullptr;
  }
  for (const TxSpan &tx : h->txs) {
    for (const InSpan &in : tx.ins) {
      InTemplate t;
      classify_input(in, t);
      h->capacity += t.kind == InTemplate::MULTISIG
                         ? long(t.ms.m) * (t.ms.n - t.ms.m + 1)
                         : 1;
      ++h->inputs;
    }
  }
  return h;
}

void txx_parse_free(void *h) { delete static_cast<TxxHandle *>(h); }

long txx_parsed_txs(void *h) {
  return long(static_cast<TxxHandle *>(h)->txs.size());
}
long txx_parsed_capacity(void *h) {
  return static_cast<TxxHandle *>(h)->capacity;
}
long txx_parsed_inputs(void *h) {
  return static_cast<TxxHandle *>(h)->inputs;
}

// txx_prevouts' rows off the handle, each outpoint also as it stands on the
// wire (txid ++ vout_le32, 36 bytes a row: the tail of the UTXO set's key, so
// a batch read builds no key from parts; the txid is written twice so that
// what asks by txid and what asks by outpoint each take a column whole and
// neither is cut out of the other a row), for the whole region (subset ==
// nullptr) or for the txs `subset` names, in its order (ISSUE 30: the rows
// extract_subset takes, selected here instead of by three fancy indexings).
// Returns the row count, -2 on capacity overflow, -3 on a bad tx index.
long txx_outpoints_h(void *hp, int bch, const int32_t *subset, long n_subset,
                     long capacity, uint8_t *txids32, uint8_t *outpoints36,
                     int64_t *vouts, uint8_t *wants) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  long flat = 0;
  static const uint8_t ZERO_TXID[32] = {0};
  const long n = subset ? n_subset : long(h->txs.size());
  for (long k = 0; k < n; ++k) {
    const long ti = subset ? long(subset[k]) : k;
    if (ti < 0 || ti >= long(h->txs.size())) return -3;
    const TxSpan &tx = h->txs[size_t(ti)];
    bool tx_has_wit = false;  // tx-level gate, see txx_prevouts
    for (const InSpan &in : tx.ins) tx_has_wit |= in.wit_count >= 1;
    for (const InSpan &in : tx.ins) {
      if (flat >= capacity) return -2;
      memcpy(txids32 + flat * 32, in.prevout, 32);
      memcpy(outpoints36 + flat * 36, in.prevout, 36);
      uint32_t vout;
      memcpy(&vout, in.prevout + 32, 4);
      vouts[flat] = int64_t(vout);
      bool cb = memcmp(in.prevout, ZERO_TXID, 32) == 0;
      wants[flat] =
          (!cb && (bch || tx_has_wit || single_push_script_sig(in))) ? 1 : 0;
      ++flat;
    }
  }
  return flat;
}

long txx_extract_h(void *hp, int flags, const int64_t *ext_amounts,
                   long n_ext, long capacity, uint8_t *z, uint8_t *px,
                   uint8_t *py, uint8_t *r, uint8_t *s, uint8_t *present,
                   int32_t *item_tx, int32_t *item_input, int32_t *item_sig,
                   int32_t *item_key, int32_t *item_nsigs,
                   int32_t *item_nkeys, uint8_t *txids,
                   int32_t *tx_n_inputs, int32_t *tx_extracted,
                   int32_t *tx_items, int32_t *tx_sigs, int32_t *tx_coinbase,
                   int32_t *tx_unsupported);

long txx_extract_h2(void *hp, int flags, const int64_t *ext_amounts,
                    long n_ext, const uint8_t *ext_scripts,
                    const int64_t *ext_script_off, long capacity, uint8_t *z,
                    uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                    uint8_t *present, int32_t *item_tx, int32_t *item_input,
                    int32_t *item_sig, int32_t *item_key, int32_t *item_nsigs,
                    int32_t *item_nkeys, uint8_t *txids,
                    int32_t *tx_n_inputs, int32_t *tx_extracted,
                    int32_t *tx_items, int32_t *tx_sigs, int32_t *tx_coinbase,
                    int32_t *tx_unsupported);

// Legacy one-shot entry: parse + extract in one call.
long txx_extract(const uint8_t *data, long len, long tx_count, int flags,
                 const int64_t *ext_amounts, long n_ext, long capacity,
                 uint8_t *z, uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                 uint8_t *present, int32_t *item_tx, int32_t *item_input,
                 int32_t *item_sig, int32_t *item_key, int32_t *item_nsigs,
                 int32_t *item_nkeys,
                 uint8_t *txids, int32_t *tx_n_inputs, int32_t *tx_extracted,
                 int32_t *tx_items, int32_t *tx_sigs,
                 int32_t *tx_coinbase, int32_t *tx_unsupported) {
  void *h = txx_parse(data, len, tx_count);
  if (h == nullptr) return -1;
  long out = txx_extract_h(h, flags, ext_amounts, n_ext, capacity, z, px, py,
                           r, s, present, item_tx, item_input, item_sig,
                           item_key, item_nsigs, item_nkeys, txids,
                           tx_n_inputs, tx_extracted, tx_items, tx_sigs,
                           tx_coinbase, tx_unsupported);
  txx_parse_free(h);
  return out;
}

// Back-compat shim: extraction without prevout scripts (no taproot).
long txx_extract_h(void *hp, int flags, const int64_t *ext_amounts,
                   long n_ext, long capacity, uint8_t *z, uint8_t *px,
                   uint8_t *py, uint8_t *r, uint8_t *s, uint8_t *present,
                   int32_t *item_tx, int32_t *item_input, int32_t *item_sig,
                   int32_t *item_key, int32_t *item_nsigs,
                   int32_t *item_nkeys, uint8_t *txids,
                   int32_t *tx_n_inputs, int32_t *tx_extracted,
                   int32_t *tx_items, int32_t *tx_sigs, int32_t *tx_coinbase,
                   int32_t *tx_unsupported) {
  return txx_extract_h2(hp, flags, ext_amounts, n_ext, nullptr, nullptr,
                        capacity, z, px, py, r, s, present, item_tx,
                        item_input, item_sig, item_key, item_nsigs,
                        item_nkeys, txids, tx_n_inputs, tx_extracted,
                        tx_items, tx_sigs, tx_coinbase, tx_unsupported);
}

// Extraction body over an already-parsed handle.
//
// ext_scripts/ext_script_off extend the external prevout oracle with
// scriptPubKeys (VERDICT r4 item 3 — BIP341 digests sign over every
// input's amount AND script): ext_script_off has n_ext+1 entries; row i's
// script is ext_scripts[off[i]:off[i+1]], empty = unknown.  Rows align
// with ext_amounts (flat input order).  NULL = no scripts (no taproot
// extraction).
// Extraction body over a parsed handle, restricted to txs [tx_lo, tx_hi).
//
// The ext_amounts/ext_scripts oracle rows are RANGE-RELATIVE: row 0 is the
// first input of tx_lo, in flat parse order (the Python binding slices the
// whole-region rows with the tx-layout offsets).  Per-tx output arrays are
// sized/indexed for the range (row 0 = tx_lo) and item_tx is range-relative
// too, so a shard's RawSigItems is self-contained.
//
// Intra-map precedence: the handle's shared map (txx_build_intra_h) when
// built, else — one-shot back-compat — a local map over the whole region.
// Range callers MUST build the shared map first: ranges are extracted on
// concurrent worker threads and only the pre-built map is read-only.
//
// With `subset` (ISSUE 27) the txs are subset[tx_lo..tx_hi) instead of the
// contiguous range, and every "range-relative" above reads subset-relative.
static long extract_body(TxxHandle *h, int flags, const int64_t *ext_amounts,
                         long n_ext, const uint8_t *ext_scripts,
                         const int64_t *ext_script_off, long tx_lo, long tx_hi,
                         const int32_t *subset, long capacity, uint8_t *z,
                         uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                         uint8_t *present, int32_t *item_tx, int32_t *item_input,
                         int32_t *item_sig, int32_t *item_key, int32_t *item_nsigs,
                         int32_t *item_nkeys, uint8_t *txids,
                         int32_t *tx_n_inputs, int32_t *tx_extracted,
                         int32_t *tx_items, int32_t *tx_sigs, int32_t *tx_coinbase,
                         int32_t *tx_unsupported, int64_t *stats = nullptr) {
  std::vector<TxSpan> &txs = h->txs;
  PhaseClock clk{stats};
  if (subset != nullptr) {
    // [tx_lo, tx_hi) are then positions in `subset`, each a tx index
    if (tx_lo < 0 || tx_lo > tx_hi) return -1;
    for (long k = tx_lo; k < tx_hi; ++k)
      if (subset[k] < 0 || subset[k] >= long(txs.size())) return -1;
  } else if (tx_lo < 0 || tx_hi > long(txs.size()) || tx_lo > tx_hi) {
    return -1;
  }
  bool bch = (flags & 1) != 0;
  bool intra = (flags & 2) != 0;
  PrevoutMap local_map;
  const PrevoutMap *prevout_map = nullptr;
  if (intra) {
    if (h->intra_built) {
      prevout_map = &h->intra;
    } else {
      build_prevout_map(txs, local_map);
      prevout_map = &local_map;
    }
  }

  // Resolve one input's prevout (amount, script): intra-block map first,
  // then the external oracle rows.  Returns a bitmask: 1 amount, 2 script.
  auto resolve = [&](const InSpan &in, long flat, int64_t *amt,
                     const uint8_t **scr, uint32_t *slen) -> int {
    int got = 0;
    if (intra) {
      OutpointKey key;
      memcpy(key.b, in.prevout, 36);
      auto it = prevout_map->find(key);
      if (it != prevout_map->end()) {
        *amt = it->second.value;
        got |= 1;
        if (it->second.script != nullptr) {
          *scr = it->second.script;
          *slen = it->second.script_len;
          got |= 2;
        }
      }
    }
    if (!(got & 1) && ext_amounts != nullptr && flat < n_ext &&
        ext_amounts[flat] >= 0) {
      *amt = ext_amounts[flat];
      got |= 1;
    }
    if (!(got & 2) && ext_scripts != nullptr && ext_script_off != nullptr &&
        flat < n_ext && ext_script_off[flat + 1] > ext_script_off[flat]) {
      *scr = ext_scripts + ext_script_off[flat];
      *slen = uint32_t(ext_script_off[flat + 1] - ext_script_off[flat]);
      got |= 2;
    }
    return got;
  };

  // pass 2: extract items
  static const uint8_t ZERO_TXID[32] = {0};
  std::vector<uint8_t> scratch;
  scratch.reserve(4096);
  PubkeyCache pubcache;   // SEC1 decode results, keyed by raw blob
  PubkeyCache liftcache;  // x-only lift results, keyed by x32 — separate
                          // object, so no cross-lane key collisions exist
  long item = 0;
  long flat_input = 0;  // RANGE-RELATIVE index into ext_amounts/ext_script_off
  for (long pos = tx_lo; pos < tx_hi; ++pos) {
    size_t ti = subset != nullptr ? size_t(subset[pos]) : size_t(pos);
    size_t oti = size_t(pos - tx_lo);  // range-relative output row
    TxSpan &tx = txs[ti];
    memcpy(txids + oti * 32, tx.txid, 32);
    int32_t n_inputs = 0, extracted = 0, coinbase = 0, unsupported = 0;
    int32_t sigs = 0;
    long tx_item_start = item;
    long tx_flat_base = flat_input;
    TapPrevouts tap;      // whole-tx prevout rows, built on first taproot use
    TapTxHashes taphash;  // per-tx BIP341 hash cache
    for (size_t idx = 0; idx < tx.ins.size(); ++idx, ++flat_input) {
      const InSpan &in = tx.ins[idx];
      ++n_inputs;
      if (memcmp(in.prevout, ZERO_TXID, 32) == 0) {
        ++coinbase;
        continue;
      }

      // prevout resolution (shared by every template; scripts matter only
      // for taproot detection + BIP341)
      int64_t amount = 0;
      const uint8_t *pscript = nullptr;
      uint32_t pscript_len = 0;
      int got = resolve(in, flat_input, &amount, &pscript, &pscript_len);
      bool have_amount = (got & 1) != 0;

      if (!bch && (got & 2) && is_p2tr_script(pscript, pscript_len)) {
        // Taproot spend (mirror of txverify._taproot_item): KEYPATH
        // witness = [sig] (+annex); SCRIPT path with the canonical
        // single-key tapscript = [sig, <32B key> OP_CHECKSIG, control]
        // (+annex).  Other tapscripts are unsupported — this is a
        // signature pre-verifier, not a tapscript interpreter.
        uint32_t wn = in.wit_count;
        const uint8_t *annex = nullptr;
        size_t annex_len = 0;
        if (wn > MAX_WIT_SPANS) {
          ++unsupported;  // can't even see the trailing spans: script path
          continue;
        }
        if (wn >= 2 && in.wit_len[wn - 1] >= 1 &&
            in.wit[wn - 1][0] == 0x50) {
          annex = in.wit[wn - 1];
          annex_len = in.wit_len[wn - 1];
          --wn;
        }
        uint8_t leaf_buf[32];
        const uint8_t *leaf_hash = nullptr;
        const uint8_t *key_ptr;  // 32-byte x-only key for this spend
        if (wn == 1) {
          key_ptr = pscript + 2;  // keypath: the output key
        } else if (wn == 3 &&
                   is_single_key_tapscript(in.wit[1], in.wit_len[1]) &&
                   valid_control_block(in.wit[2], in.wit_len[2])) {
          key_ptr = in.wit[1] + 1;  // the leaf's key
          Sha256 lh;
          tagged_hash_init(lh, tap_leaf_tag());
          uint8_t hdr[2] = {uint8_t(in.wit[2][0] & 0xFE),
                            uint8_t(in.wit_len[1])};
          lh.update(hdr, 2);  // leaf version ∥ varstr length (34 < 0xFD)
          lh.update(in.wit[1], in.wit_len[1]);
          lh.final(leaf_buf);
          leaf_hash = leaf_buf;
        } else {
          ++unsupported;
          continue;
        }
        const uint8_t *sig = in.wit[0];
        uint32_t sig_len = in.wit_len[0];
        // Consensus-invalid shapes emit an AUTO-INVALID item (present=0):
        // the spend is invalid, not unsupported.
        auto emit_invalid = [&](const uint8_t *rb, const uint8_t *sb) -> bool {
          if (item >= capacity) return false;
          memset(z + item * 32, 0, 32);
          memset(px + item * 32, 0, 32);
          memset(py + item * 32, 0, 32);
          if (rb != nullptr) memcpy(r + item * 32, rb, 32);
          else memset(r + item * 32, 0, 32);
          if (sb != nullptr) memcpy(s + item * 32, sb, 32);
          else memset(s + item * 32, 0, 32);
          present[item] = 0;
          item_tx[item] = int32_t(oti);
          item_input[item] = int32_t(idx);
          item_sig[item] = 0;
          item_key[item] = 0;
          item_nsigs[item] = 1;
          item_nkeys[item] = 1;
          ++item;
          ++extracted;
          ++sigs;
          return true;
        };
        int hashtype;
        if (sig_len == 64) {
          hashtype = 0x00;
        } else if (sig_len == 65) {
          hashtype = sig[64];
          if (hashtype == 0x00) {
            // 65-byte sig must carry an explicit type (zero r/s, mirror
            // of txverify's bare invalid())
            if (!emit_invalid(nullptr, nullptr)) return -2;
            continue;
          }
        } else {
          if (!emit_invalid(nullptr, nullptr)) return -2;
          continue;
        }
        // ACP bit decides WHICH prevouts are required even when the
        // hash_type is invalid (parity with txverify._taproot_item's
        // `need` computation; the invalid type then fails in the digest)
        bool acp = (hashtype & SIGHASH_ANYONECANPAY) != 0;
        if (!tap.built) {
          size_t n_in = tx.ins.size();
          tap.amounts.assign(n_in, 0);
          tap.scripts.assign(n_in, nullptr);
          tap.script_lens.assign(n_in, 0);
          tap.have.assign(n_in, false);
          for (size_t i = 0; i < n_in; ++i) {
            int64_t a = 0;
            const uint8_t *sc = nullptr;
            uint32_t sl = 0;
            int g = resolve(tx.ins[i], tx_flat_base + long(i), &a, &sc, &sl);
            if ((g & 3) == 3) {
              tap.amounts[i] = a;
              tap.scripts[i] = sc;
              tap.script_lens[i] = sl;
              tap.have[i] = true;
            }
          }
          tap.built = true;
        }
        bool have_prevouts = acp ? bool(tap.have[idx])
                                 : std::all_of(tap.have.begin(),
                                               tap.have.end(),
                                               [](bool b) { return b; });
        if (!have_prevouts) {
          ++unsupported;  // digest uncomputable: missing prevout info
          continue;
        }
        uint8_t digest[32];
        clk.begin();
        bool digested = bip341_sighash(tx, idx, hashtype, annex, annex_len,
                                       tap, taphash, scratch, digest,
                                       leaf_hash);
        clk.end(ST_BIP341_NS);
        clk.count(ST_BIP341_N);
        if (!digested) {
          if (!emit_invalid(sig, sig + 32)) return -2;
          continue;
        }
        uint8_t pxb[32], pyb[32];
        bool lift_hit;
        bool lifted = lift_x_cached(liftcache, key_ptr, pxb, pyb, &lift_hit);
        clk.end(ST_LIFT_NS);
        clk.count(ST_LIFT_CALLS);
        if (lift_hit) clk.count(ST_LIFT_HITS);
        if (!lifted) {
          // off-curve key: invalid spend
          if (!emit_invalid(sig, sig + 32)) return -2;
          continue;
        }
        if (item >= capacity) return -2;
        // challenge e = tagged(BIP0340/challenge, r ∥ px ∥ m) mod n —
        // extraction precomputes it, like the BCH Schnorr lane
        uint8_t e32[32];
        Sha256 h;
        tagged_hash_init(h, bip340_challenge_tag());
        h.update(sig, 32);       // r
        h.update(pxb, 32);       // x-only pubkey
        h.update(digest, 32);    // m
        h.final(e32);
        reduce_mod_n(e32);
        memcpy(z + item * 32, e32, 32);
        memcpy(px + item * 32, pxb, 32);
        memcpy(py + item * 32, pyb, 32);
        memcpy(r + item * 32, sig, 32);
        memcpy(s + item * 32, sig + 32, 32);
        present[item] = 3;
        item_tx[item] = int32_t(oti);
        item_input[item] = int32_t(idx);
        item_sig[item] = 0;
        item_key[item] = 0;
        item_nsigs[item] = 1;
        item_nkeys[item] = 1;
        ++item;
        ++extracted;
        ++sigs;
        continue;
      }

      InTemplate t;
      classify_input(in, t);
      if (t.kind == InTemplate::UNSUPPORTED && (got & 2) &&
          in.wit_count == 0 && single_push_script_sig(in)) {
        // bare P2PK: scriptSig = <sig>, key in the prevout script — only
        // the oracle's script makes this classifiable
        size_t klen;
        const uint8_t *key = is_p2pk_script(pscript, pscript_len, &klen);
        if (key != nullptr) {
          t.kind = InTemplate::SINGLE;
          t.sig = in.script + 1;
          t.sig_len = in.script_len - 1;
          t.pub = key;
          t.pub_len = klen;
          t.sc = pscript;
          t.sc_len = pscript_len;
        }
      }
      if (t.kind == InTemplate::UNSUPPORTED) {
        ++unsupported;
        continue;
      }

      if (t.kind == InTemplate::SINGLE) {
        if (t.sig_len < 9) {
          ++unsupported;
          continue;
        }
        int hashtype = t.sig[t.sig_len - 1];
        // BCH consensus: a 65-byte signature blob (64 + hashtype) IS
        // Schnorr (2019-05 upgrade) — r ∥ s raw, no DER.
        bool is_schnorr = bch && t.sig_len == 65;
        uint8_t rbuf[32], sbuf[32];
        if (is_schnorr) {
          memcpy(rbuf, t.sig, 32);
          memcpy(sbuf, t.sig + 32, 32);
        } else if (!parse_der(t.sig, t.sig_len - 1, rbuf, sbuf)) {
          ++unsupported;
          continue;
        }
        // script_code: the template's own script when set (P2WSH
        // single-key witness script, bare P2PK prevout script), else the
        // P2PKH template over hash160(pubkey)
        uint8_t p2pkh_code[25];
        const uint8_t *script_code = t.sc;
        size_t sc_len = t.sc_len;
        if (script_code == nullptr) {
          p2pkh_code[0] = 0x76; p2pkh_code[1] = 0xA9; p2pkh_code[2] = 0x14;
          hash160(t.pub, t.pub_len, p2pkh_code + 3);
          p2pkh_code[23] = 0x88; p2pkh_code[24] = 0xAC;
          script_code = p2pkh_code;
          sc_len = 25;
        }
        uint8_t digest[32];
        if (t.segwit || (bch && (hashtype & SIGHASH_FORKID))) {
          if (!have_amount) {
            ++unsupported;
            continue;
          }
          clk.begin();
          bip143_sighash(tx, idx, script_code, sc_len, amount, hashtype,
                         scratch, digest);
          clk.end(ST_BIP143_NS);
          clk.count(ST_BIP143_N);
        } else {
          clk.begin();
          legacy_sighash(tx, idx, script_code, sc_len, hashtype, scratch,
                         digest);
          clk.end(ST_LEGACY_NS);
          clk.count(ST_LEGACY_N);
        }
        if (item >= capacity) return -2;
        memcpy(r + item * 32, rbuf, 32);
        memcpy(s + item * 32, sbuf, 32);
        if (is_schnorr) {
          // challenge e = SHA256(r ∥ P_compressed ∥ m) mod n, hashed over
          // the UNREDUCED sighash (mirror of ecdsa_cpu.schnorr_challenge);
          // undecodable pubkey -> auto-invalid row with z = 0.
          uint8_t pxb[32], pyb[32];
          bool okp = decode_pubkey_cached(pubcache, t.pub, t.pub_len, pxb,
                                          pyb);
          if (okp) {
            uint8_t pre[97];
            memcpy(pre, rbuf, 32);
            pre[32] = uint8_t(0x02 | (pyb[31] & 1));
            memcpy(pre + 33, pxb, 32);
            memcpy(pre + 65, digest, 32);
            uint8_t e32[32];
            Sha256 h;
            h.update(pre, 97);
            h.final(e32);
            reduce_mod_n(e32);
            memcpy(z + item * 32, e32, 32);
            memcpy(px + item * 32, pxb, 32);
            memcpy(py + item * 32, pyb, 32);
            present[item] = 2;
          } else {
            memset(z + item * 32, 0, 32);
            memset(px + item * 32, 0, 32);
            memset(py + item * 32, 0, 32);
            present[item] = 0;
          }
        } else {
          reduce_mod_n(digest);
          memcpy(z + item * 32, digest, 32);
          present[item] =
              decode_pubkey_cached(pubcache, t.pub, t.pub_len, px + item * 32,
                                   py + item * 32)
                  ? 1
                  : 0;
          if (!present[item]) {
            memset(px + item * 32, 0, 32);
            memset(py + item * 32, 0, 32);
          }
        }
        item_tx[item] = int32_t(oti);
        item_input[item] = int32_t(idx);
        item_sig[item] = 0;
        item_key[item] = 0;
        item_nsigs[item] = 1;
        item_nkeys[item] = 1;
        ++item;
        ++extracted;
        ++sigs;
        continue;
      }

      // MULTISIG: emit m*(n-m+1) candidate (sig, key) pairs.  A missing
      // required amount mid-loop rolls the whole input back to unsupported
      // (same precedence as txverify._msig_items).
      int m = t.ms.m, n = t.ms.n;
      long input_start = item;
      // decode each key at most once per input
      uint8_t kx[16][32], ky[16][32];
      int kdec[16];
      for (int k = 0; k < 16; ++k) kdec[k] = -1;
      bool input_unsupported = false;
      for (int i = 0; i < m && !input_unsupported; ++i) {
        const uint8_t *sig_blob = t.sigs[i];
        size_t sig_len = t.sig_lens[i];
        uint8_t rbuf[32], sbuf[32], digest[32];
        bool have_sig = sig_len >= 9 &&
                        parse_der(sig_blob, sig_len - 1, rbuf, sbuf);
        if (have_sig) {
          int hashtype = sig_blob[sig_len - 1];
          if (t.segwit || (bch && (hashtype & SIGHASH_FORKID))) {
            if (!have_amount) {
              input_unsupported = true;
              break;
            }
            clk.begin();
            bip143_sighash(tx, idx, t.sc, t.sc_len, amount, hashtype, scratch,
                           digest);
            clk.end(ST_BIP143_NS);
            if (i == 0) clk.count(ST_BIP143_N);  // inputs, not signatures
          } else {
            clk.begin();
            legacy_sighash(tx, idx, t.sc, t.sc_len, hashtype, scratch, digest);
            clk.end(ST_LEGACY_NS);
            if (i == 0) clk.count(ST_LEGACY_N);
          }
          reduce_mod_n(digest);
        }
        for (int j = i; j <= n - m + i; ++j) {
          if (item >= capacity) return -2;
          if (!have_sig) {
            memset(z + item * 32, 0, 32);
            memset(r + item * 32, 0, 32);
            memset(s + item * 32, 0, 32);
            memset(px + item * 32, 0, 32);
            memset(py + item * 32, 0, 32);
            present[item] = 0;
          } else {
            memcpy(z + item * 32, digest, 32);
            memcpy(r + item * 32, rbuf, 32);
            memcpy(s + item * 32, sbuf, 32);
            if (kdec[j] < 0)
              kdec[j] = decode_pubkey_cached(pubcache, t.ms.keys[j],
                                             t.ms.key_len[j], kx[j], ky[j])
                            ? 1
                            : 0;
            present[item] = uint8_t(kdec[j]);
            if (kdec[j]) {
              memcpy(px + item * 32, kx[j], 32);
              memcpy(py + item * 32, ky[j], 32);
            } else {
              memset(px + item * 32, 0, 32);
              memset(py + item * 32, 0, 32);
            }
          }
          item_tx[item] = int32_t(oti);
          item_input[item] = int32_t(idx);
          item_sig[item] = i;
          item_key[item] = j;
          item_nsigs[item] = m;
          item_nkeys[item] = n;
          ++item;
        }
      }
      if (input_unsupported) {
        item = input_start;  // roll back any emitted candidates
        ++unsupported;
      } else {
        ++extracted;
        sigs += m;
      }
    }
    tx_n_inputs[oti] = n_inputs;
    tx_extracted[oti] = extracted;
    tx_items[oti] = int32_t(item - tx_item_start);
    tx_sigs[oti] = sigs;
    tx_coinbase[oti] = coinbase;
    tx_unsupported[oti] = unsupported;
  }
  return item;
}

long txx_extract_h2(void *hp, int flags, const int64_t *ext_amounts,
                    long n_ext, const uint8_t *ext_scripts,
                    const int64_t *ext_script_off, long capacity, uint8_t *z,
                    uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                    uint8_t *present, int32_t *item_tx, int32_t *item_input,
                    int32_t *item_sig, int32_t *item_key, int32_t *item_nsigs,
                    int32_t *item_nkeys, uint8_t *txids,
                    int32_t *tx_n_inputs, int32_t *tx_extracted,
                    int32_t *tx_items, int32_t *tx_sigs, int32_t *tx_coinbase,
                    int32_t *tx_unsupported) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  return extract_body(h, flags, ext_amounts, n_ext, ext_scripts,
                      ext_script_off, 0, long(h->txs.size()), nullptr, capacity,
                      z, px,
                      py, r, s, present, item_tx, item_input, item_sig,
                      item_key, item_nsigs, item_nkeys, txids, tx_n_inputs,
                      tx_extracted, tx_items, tx_sigs, tx_coinbase,
                      tx_unsupported);
}

// Build the handle's shared whole-region intra-block prevout map (at most
// once; idempotent).  MUST run before any txx_extract_range_h with the
// intra flag: ranges extract on concurrent threads and only the pre-built
// map is read-only.  Returns the map size.
long txx_build_intra_h(void *hp) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  if (!h->intra_built) {
    build_prevout_map(h->txs, h->intra);
    h->intra_built = true;
  }
  return long(h->intra.size());
}

// Per-tx layout rows (n_txs each): input counts and candidate-item
// capacities — the Python side derives range capacities and the flat
// oracle-row offsets (cumsum) for tx-range sharding from these.
long txx_tx_layout_h(void *hp, int32_t *n_inputs, int32_t *capacity) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  for (size_t ti = 0; ti < h->txs.size(); ++ti) {
    const TxSpan &tx = h->txs[ti];
    long cap = 0;
    for (const InSpan &in : tx.ins) {
      InTemplate t;
      classify_input(in, t);
      cap += t.kind == InTemplate::MULTISIG
                 ? long(t.ms.m) * (t.ms.n - t.ms.m + 1)
                 : 1;
    }
    n_inputs[ti] = int32_t(tx.ins.size());
    capacity[ti] = int32_t(cap);
  }
  return long(h->txs.size());
}

// Tx-range extraction over the shared handle (ISSUE 11): same result rows
// as txx_extract_h2 but only for txs [tx_lo, tx_hi), with range-relative
// oracle rows and output indices (see extract_body).  Thread-safe across
// DISJOINT ranges once txx_build_intra_h ran (or the intra flag is off).
long txx_extract_range_h(void *hp, int flags, const int64_t *ext_amounts,
                         long n_ext, const uint8_t *ext_scripts,
                         const int64_t *ext_script_off, long tx_lo, long tx_hi,
                         long capacity, uint8_t *z,
                         uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                         uint8_t *present, int32_t *item_tx, int32_t *item_input,
                         int32_t *item_sig, int32_t *item_key,
                         int32_t *item_nsigs, int32_t *item_nkeys,
                         uint8_t *txids, int32_t *tx_n_inputs,
                         int32_t *tx_extracted, int32_t *tx_items,
                         int32_t *tx_sigs, int32_t *tx_coinbase,
                         int32_t *tx_unsupported, int64_t *stats) {
  // `stats`: ST_COUNT int64 slots the call ADDS to (ExtractStat), or NULL
  return extract_body(static_cast<TxxHandle *>(hp), flags, ext_amounts, n_ext,
                      ext_scripts, ext_script_off, tx_lo, tx_hi, nullptr,
                      capacity, z, px, py, r, s, present, item_tx, item_input,
                      item_sig, item_key, item_nsigs, item_nkeys, txids,
                      tx_n_inputs, tx_extracted, tx_items, tx_sigs, tx_coinbase,
                      tx_unsupported, stats);
}

// Subset extraction (ISSUE 27): the txs `subset[0..n_subset)` (indices into
// the region, any order, each at most once) through the same body, so a
// block whose other txs are answered elsewhere extracts only these — still
// against the block's ONE intra-block prevout map.  Oracle rows and output
// rows are SUBSET-relative: row 0 is the first input of subset[0], output
// tx row k is subset[k].  Thread-safety as for ranges.
long txx_extract_subset_h(void *hp, int flags, const int64_t *ext_amounts,
                          long n_ext, const uint8_t *ext_scripts,
                          const int64_t *ext_script_off, const int32_t *subset,
                          long n_subset, long capacity, uint8_t *z,
                          uint8_t *px, uint8_t *py, uint8_t *r, uint8_t *s,
                          uint8_t *present, int32_t *item_tx,
                          int32_t *item_input, int32_t *item_sig,
                          int32_t *item_key, int32_t *item_nsigs,
                          int32_t *item_nkeys, uint8_t *txids,
                          int32_t *tx_n_inputs, int32_t *tx_extracted,
                          int32_t *tx_items, int32_t *tx_sigs,
                          int32_t *tx_coinbase, int32_t *tx_unsupported,
                          int64_t *stats) {
  if (subset == nullptr) return -1;
  return extract_body(static_cast<TxxHandle *>(hp), flags, ext_amounts, n_ext,
                      ext_scripts, ext_script_off, 0, n_subset, subset,
                      capacity, z, px, py, r, s, present, item_tx, item_input,
                      item_sig, item_key, item_nsigs, item_nkeys, txids,
                      tx_n_inputs, tx_extracted, tx_items, tx_sigs, tx_coinbase,
                      tx_unsupported, stats);
}

// ---------------------------------------------------------------------------
// Native UTXO block-connect (ISSUE 11): one pass over the parsed region
// emits the block's spend/create key-value delta as a ready-to-apply batch
// blob in the v1 record format (op u8, klen u32le, vlen u32le, key, value):
//
//   create: op=1, key = prefix ++ txid ++ vout_le32,
//           value = amount_le64 ++ scriptPubKey
//   spend:  op=2, key = prefix ++ prevout_txid ++ prevout_vout_le32
//
// Creates are emitted before spends per the WHOLE region and coinbase
// inputs spend nothing — exactly UtxoStore.apply_block's semantics, so the
// Python per-tx parse leaves block ingest entirely (node._apply_block_utxo).
// ---------------------------------------------------------------------------

// Exact byte size of the ops blob txx_utxo_ops_h would emit.
long txx_utxo_size_h(void *hp) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  static const uint8_t ZERO_TXID[32] = {0};
  const long REC = 9, KEY = 1 + 32 + 4;
  long total = 0;
  for (const TxSpan &tx : h->txs) {
    for (const OutSpan &o : tx.outs) {
      const uint8_t *script = nullptr;
      uint32_t slen = 0;
      out_script(o, &script, &slen);
      total += REC + KEY + 8 + long(slen);
    }
    for (const InSpan &in : tx.ins) {
      if (memcmp(in.prevout, ZERO_TXID, 32) != 0) total += REC + KEY;
    }
  }
  return total;
}

// Emit the delta blob into `out` (capacity `cap` bytes).  `created` /
// `spent` receive the op counts.  Returns bytes written, or -2 when cap
// is too small (use txx_utxo_size_h).
long txx_utxo_ops_h(void *hp, uint8_t prefix, long cap, uint8_t *out,
                    long *created, long *spent) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  static const uint8_t ZERO_TXID[32] = {0};
  long pos = 0, n_created = 0, n_spent = 0;
  auto put_hdr = [&](uint8_t op, uint32_t klen, uint32_t vlen) {
    out[pos] = op;
    memcpy(out + pos + 1, &klen, 4);  // little-endian on supported targets
    memcpy(out + pos + 5, &vlen, 4);
    pos += 9;
  };
  const uint32_t KEY = 1 + 32 + 4;
  for (const TxSpan &tx : h->txs) {
    for (size_t vout = 0; vout < tx.outs.size(); ++vout) {
      const OutSpan &o = tx.outs[vout];
      const uint8_t *script = nullptr;
      uint32_t slen = 0;
      out_script(o, &script, &slen);
      uint32_t vlen = 8 + slen;
      if (pos + 9 + long(KEY) + long(vlen) > cap) return -2;
      put_hdr(1, KEY, vlen);
      out[pos] = prefix;
      memcpy(out + pos + 1, tx.txid, 32);
      uint32_t v32 = uint32_t(vout);
      memcpy(out + pos + 33, &v32, 4);
      pos += KEY;
      uint64_t amt = uint64_t(o.value);
      memcpy(out + pos, &amt, 8);
      if (slen) memcpy(out + pos + 8, script, slen);
      pos += vlen;
      ++n_created;
    }
  }
  for (const TxSpan &tx : h->txs) {
    for (const InSpan &in : tx.ins) {
      if (memcmp(in.prevout, ZERO_TXID, 32) == 0) continue;
      if (pos + 9 + long(KEY) > cap) return -2;
      put_hdr(2, KEY, 0);
      out[pos] = prefix;
      memcpy(out + pos + 1, in.prevout, 36);  // txid ++ vout_le32 (wire order)
      pos += KEY;
      ++n_spent;
    }
  }
  if (created) *created = n_created;
  if (spent) *spent = n_spent;
  return pos;
}

// ---- the in-flight output view (ISSUE 44) --------------------------------
//
// outpoint -> (amount, scriptPubKey) for every output of a block that is
// parsed and not yet connected.  A block's rows are copied out of its parse
// handle (the handle goes when the block's extraction ends, the view's copy
// when it connects), so a publish is one call whatever the block's size and
// holds no interpreter lock; lookups are one call a batch.  Two blocks in
// flight may have made the same outpoint (the same tx on two branches):
// the newer one answers, and forgetting either leaves the other's rows
// indexed.

struct ViewBlock {
  std::vector<OutpointKey> keys;
  std::vector<int64_t> amounts;
  std::vector<int64_t> ends;  // script i: scripts[ends[i-1] .. ends[i])
  std::vector<uint8_t> scripts;
  void add(const uint8_t *txid, uint32_t vout, int64_t amount,
           const uint8_t *script, size_t slen) {
    OutpointKey key;
    memcpy(key.b, txid, 32);
    memcpy(key.b + 32, &vout, 4);
    keys.push_back(key);
    amounts.push_back(amount);
    scripts.insert(scripts.end(), script, script + slen);
    ends.push_back(int64_t(scripts.size()));
  }
};

struct ViewRef {
  const ViewBlock *block;
  uint32_t row;
};

struct OutputView {
  std::mutex mu;
  std::unordered_map<OutpointKey, ViewRef, OutpointHash> index;
  std::unordered_map<std::string, std::unique_ptr<ViewBlock>> blocks;
  bool overlap = false;

  void index_block(const ViewBlock *b) {
    for (size_t i = 0; i < b->keys.size(); ++i)
      index[b->keys[i]] = ViewRef{b, uint32_t(i)};
  }

  long forget(const std::string &hash) {
    auto it = blocks.find(hash);
    if (it == blocks.end()) return 0;
    std::unique_ptr<ViewBlock> b = std::move(it->second);
    blocks.erase(it);
    for (const OutpointKey &k : b->keys) {
      auto f = index.find(k);
      if (f != index.end() && f->second.block == b.get()) index.erase(f);
    }
    if (overlap) {
      size_t total = 0;
      for (const auto &kv : blocks) {
        index_block(kv.second.get());
        total += kv.second->keys.size();
      }
      overlap = index.size() != total;
    }
    return long(b->keys.size());
  }

  long install(const uint8_t *block_hash, std::unique_ptr<ViewBlock> b) {
    std::string hash(reinterpret_cast<const char *>(block_hash), 32);
    std::lock_guard<std::mutex> g(mu);
    forget(hash);  // delivered again: the newer parse's
    size_t before = index.size(), n = b->keys.size();
    index_block(b.get());
    if (index.size() - before != n) overlap = true;
    blocks[hash] = std::move(b);
    return long(n);
  }
};

void *txx_view_new() { return new OutputView; }

void txx_view_free(void *vp) { delete static_cast<OutputView *>(vp); }

long txx_view_size(void *vp) {
  OutputView *v = static_cast<OutputView *>(vp);
  std::lock_guard<std::mutex> g(v->mu);
  return long(v->index.size());
}

// Every output of the parsed region `hp` as block `block_hash`'s.  Returns
// the rows added.
long txx_view_publish_h(void *vp, void *hp, const uint8_t *block_hash) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  std::unique_ptr<ViewBlock> b(new ViewBlock);
  for (const TxSpan &tx : h->txs) {
    for (size_t vout = 0; vout < tx.outs.size(); ++vout) {
      const uint8_t *script = nullptr;
      uint32_t slen = 0;
      out_script(tx.outs[vout], &script, &slen);
      b->add(tx.txid, uint32_t(vout), tx.outs[vout].value, script, slen);
    }
  }
  return static_cast<OutputView *>(vp)->install(block_hash, std::move(b));
}

// The same from rows (a block parsed elsewhere): `keys36` n x (txid ++
// vout_le32), `ends[i]` where row i's script ends in `scripts`.
long txx_view_publish_rows(void *vp, const uint8_t *block_hash, long n,
                           const uint8_t *keys36, const int64_t *amounts,
                           const int64_t *ends, const uint8_t *scripts) {
  std::unique_ptr<ViewBlock> b(new ViewBlock);
  for (long i = 0; i < n; ++i) {
    uint32_t vout;
    memcpy(&vout, keys36 + i * 36 + 32, 4);
    int64_t lo = i ? ends[i - 1] : 0;
    b->add(keys36 + i * 36, vout, amounts[i], scripts + lo,
           size_t(ends[i] - lo));
  }
  return static_cast<OutputView *>(vp)->install(block_hash, std::move(b));
}

// The block is connected, or let go.  Returns the rows that left, 0 for a
// block that has none here.
long txx_view_forget(void *vp, const uint8_t *block_hash) {
  OutputView *v = static_cast<OutputView *>(vp);
  std::string hash(reinterpret_cast<const char *>(block_hash), 32);
  std::lock_guard<std::mutex> g(v->mu);
  return v->forget(hash);
}

// n outpoints (36 bytes each) in one hold: hit[i] 1 where the view has row
// i, then amounts[i] and its script at scripts[ends[i-1] .. ends[i]) (a
// miss is an empty span).  Returns the hits, or -(bytes needed) when `cap`
// bytes do not hold the scripts (nothing of `scripts` is then to be read).
long txx_view_lookup(void *vp, const uint8_t *keys36, long n, uint8_t *hit,
                     int64_t *amounts, int64_t *ends, uint8_t *scripts,
                     long cap) {
  OutputView *v = static_cast<OutputView *>(vp);
  std::lock_guard<std::mutex> g(v->mu);
  long pos = 0, hits = 0;
  for (long i = 0; i < n; ++i) {
    OutpointKey key;
    memcpy(key.b, keys36 + i * 36, 36);
    auto f = v->index.find(key);
    hit[i] = f != v->index.end();
    if (hit[i]) {
      const ViewBlock *b = f->second.block;
      uint32_t row = f->second.row;
      int64_t lo = row ? b->ends[row - 1] : 0, len = b->ends[row] - lo;
      if (pos + len <= cap) memcpy(scripts + pos, b->scripts.data() + lo, len);
      amounts[i] = b->amounts[row];
      pos += len;
      ++hits;
    }
    ends[i] = pos;
  }
  return pos > cap ? -pos : hits;
}

// All parsed txids, row-major (n_txs x 32) — block connect and mempool
// confirmation need the txid list without a Python parse OR an extract.
long txx_txids_h(void *hp, uint8_t *out) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  for (size_t ti = 0; ti < h->txs.size(); ++ti)
    memcpy(out + ti * 32, h->txs[ti].txid, 32);
  return long(h->txs.size());
}

// Every parsed tx's double-SHA over its FULL wire bytes as they stand in the
// region, row-major (n_txs x 32): the wtxid of a witness serialization, the
// txid (copied, not rehashed) of any other.  The key under which a relay
// verdict may answer for a block transaction (ISSUE 27): the same txid under
// another witness is another key.
long txx_wire_hashes_h(void *hp, uint8_t *out) {
  TxxHandle *h = static_cast<TxxHandle *>(hp);
  for (size_t ti = 0; ti < h->txs.size(); ++ti) {
    const TxSpan &tx = h->txs[ti];
    if (tx.inout_start == tx.version + 4)
      memcpy(out + ti * 32, tx.txid, 32);
    else  // marker + flag after the version: hash what was sent
      dsha256(tx.version, size_t(tx.locktime + 4 - tx.version), out + ti * 32);
  }
  return long(h->txs.size());
}

}  // extern "C"

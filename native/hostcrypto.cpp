// hostcrypto.cpp — single-core C++ verification path for the Praos header
// crypto: Ed25519 (cofactorless, RFC 8032), ECVRF-ed25519-sha512-ell2
// (draft-03) and CompactSum KES, plus SHA-512 and Blake2b-256.
//
// Purpose: (1) the HONEST measured CPU baseline for bench.py — the same
// role libsodium plays under the reference's db-analyser revalidation
// fold (ouroboros-consensus-protocol/.../Protocol/Praos.hs:543,580,582
// via cardano-crypto-{class,praos}); (2) a fast host fallback for
// db_analyser --backend native. Written from the curve/protocol specs to
// mirror ops/host/{ed25519,ecvrf,kes}.py bit-for-bit (differentially
// tested in tests/test_native_crypto.py).
//
// Build: g++ -O2 -shared -fPIC -o libhostcrypto.so hostcrypto.cpp

#include <cstdint>
#include <cstring>
#include <cstddef>

typedef unsigned __int128 u128;
typedef uint64_t u64;
typedef uint8_t u8;

// ===========================================================================
// SHA-512
// ===========================================================================

static const u64 SHA_K[80] = {
    0x428a2f98d728ae22ULL, 0x7137449123ef65cdULL, 0xb5c0fbcfec4d3b2fULL,
    0xe9b5dba58189dbbcULL, 0x3956c25bf348b538ULL, 0x59f111f1b605d019ULL,
    0x923f82a4af194f9bULL, 0xab1c5ed5da6d8118ULL, 0xd807aa98a3030242ULL,
    0x12835b0145706fbeULL, 0x243185be4ee4b28cULL, 0x550c7dc3d5ffb4e2ULL,
    0x72be5d74f27b896fULL, 0x80deb1fe3b1696b1ULL, 0x9bdc06a725c71235ULL,
    0xc19bf174cf692694ULL, 0xe49b69c19ef14ad2ULL, 0xefbe4786384f25e3ULL,
    0x0fc19dc68b8cd5b5ULL, 0x240ca1cc77ac9c65ULL, 0x2de92c6f592b0275ULL,
    0x4a7484aa6ea6e483ULL, 0x5cb0a9dcbd41fbd4ULL, 0x76f988da831153b5ULL,
    0x983e5152ee66dfabULL, 0xa831c66d2db43210ULL, 0xb00327c898fb213fULL,
    0xbf597fc7beef0ee4ULL, 0xc6e00bf33da88fc2ULL, 0xd5a79147930aa725ULL,
    0x06ca6351e003826fULL, 0x142929670a0e6e70ULL, 0x27b70a8546d22ffcULL,
    0x2e1b21385c26c926ULL, 0x4d2c6dfc5ac42aedULL, 0x53380d139d95b3dfULL,
    0x650a73548baf63deULL, 0x766a0abb3c77b2a8ULL, 0x81c2c92e47edaee6ULL,
    0x92722c851482353bULL, 0xa2bfe8a14cf10364ULL, 0xa81a664bbc423001ULL,
    0xc24b8b70d0f89791ULL, 0xc76c51a30654be30ULL, 0xd192e819d6ef5218ULL,
    0xd69906245565a910ULL, 0xf40e35855771202aULL, 0x106aa07032bbd1b8ULL,
    0x19a4c116b8d2d0c8ULL, 0x1e376c085141ab53ULL, 0x2748774cdf8eeb99ULL,
    0x34b0bcb5e19b48a8ULL, 0x391c0cb3c5c95a63ULL, 0x4ed8aa4ae3418acbULL,
    0x5b9cca4f7763e373ULL, 0x682e6ff3d6b2b8a3ULL, 0x748f82ee5defb2fcULL,
    0x78a5636f43172f60ULL, 0x84c87814a1f0ab72ULL, 0x8cc702081a6439ecULL,
    0x90befffa23631e28ULL, 0xa4506cebde82bde9ULL, 0xbef9a3f7b2c67915ULL,
    0xc67178f2e372532bULL, 0xca273eceea26619cULL, 0xd186b8c721c0c207ULL,
    0xeada7dd6cde0eb1eULL, 0xf57d4f7fee6ed178ULL, 0x06f067aa72176fbaULL,
    0x0a637dc5a2c898a6ULL, 0x113f9804bef90daeULL, 0x1b710b35131c471bULL,
    0x28db77f523047d84ULL, 0x32caab7b40c72493ULL, 0x3c9ebe0a15c9bebcULL,
    0x431d67c49c100d4cULL, 0x4cc5d4becb3e42b6ULL, 0x597f299cfc657e2aULL,
    0x5fcb6fab3ad6faecULL, 0x6c44198c4a475817ULL};

static inline u64 rotr64(u64 x, int n) { return (x >> n) | (x << (64 - n)); }

struct Sha512 {
    u64 h[8];
    u8 buf[128];
    u64 total;
    size_t fill;

    void init() {
        static const u64 H0[8] = {
            0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
            0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
            0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
        memcpy(h, H0, sizeof h);
        total = 0;
        fill = 0;
    }
    void block(const u8* p) {
        u64 w[80];
        for (int i = 0; i < 16; i++) {
            w[i] = 0;
            for (int j = 0; j < 8; j++) w[i] = (w[i] << 8) | p[8 * i + j];
        }
        for (int i = 16; i < 80; i++) {
            u64 s0 = rotr64(w[i - 15], 1) ^ rotr64(w[i - 15], 8) ^ (w[i - 15] >> 7);
            u64 s1 = rotr64(w[i - 2], 19) ^ rotr64(w[i - 2], 61) ^ (w[i - 2] >> 6);
            w[i] = w[i - 16] + s0 + w[i - 7] + s1;
        }
        u64 a = h[0], b = h[1], c = h[2], d = h[3], e = h[4], f = h[5], g = h[6],
            hh = h[7];
        for (int i = 0; i < 80; i++) {
            u64 S1 = rotr64(e, 14) ^ rotr64(e, 18) ^ rotr64(e, 41);
            u64 ch = (e & f) ^ (~e & g);
            u64 t1 = hh + S1 + ch + SHA_K[i] + w[i];
            u64 S0 = rotr64(a, 28) ^ rotr64(a, 34) ^ rotr64(a, 39);
            u64 maj = (a & b) ^ (a & c) ^ (b & c);
            u64 t2 = S0 + maj;
            hh = g; g = f; f = e; e = d + t1; d = c; c = b; b = a; a = t1 + t2;
        }
        h[0] += a; h[1] += b; h[2] += c; h[3] += d;
        h[4] += e; h[5] += f; h[6] += g; h[7] += hh;
    }
    void update(const u8* p, size_t n) {
        total += n;
        while (n) {
            size_t k = 128 - fill;
            if (k > n) k = n;
            memcpy(buf + fill, p, k);
            fill += k; p += k; n -= k;
            if (fill == 128) { block(buf); fill = 0; }
        }
    }
    void final(u8 out[64]) {
        u64 bits = total * 8;
        u8 pad = 0x80;
        update(&pad, 1);
        u8 z = 0;
        while (fill != 112) update(&z, 1);
        u8 len[16] = {0};
        for (int i = 0; i < 8; i++) len[15 - i] = (u8)(bits >> (8 * i));
        update(len, 16);
        for (int i = 0; i < 8; i++)
            for (int j = 0; j < 8; j++) out[8 * i + j] = (u8)(h[i] >> (56 - 8 * j));
    }
};

static void sha512(const u8* p, size_t n, u8 out[64]) {
    Sha512 s; s.init(); s.update(p, n); s.final(out);
}

// ===========================================================================
// Blake2b (RFC 7693), digest sizes 1..64
// ===========================================================================

static const u8 B2B_SIGMA[12][16] = {
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3},
    {11, 8, 12, 0, 5, 2, 15, 13, 10, 14, 3, 6, 7, 1, 9, 4},
    {7, 9, 3, 1, 13, 12, 11, 14, 2, 6, 5, 10, 4, 0, 15, 8},
    {9, 0, 5, 7, 2, 4, 10, 15, 14, 1, 11, 12, 6, 8, 3, 13},
    {2, 12, 6, 10, 0, 11, 8, 3, 4, 13, 7, 5, 15, 14, 1, 9},
    {12, 5, 1, 15, 14, 13, 4, 10, 0, 7, 6, 3, 9, 2, 8, 11},
    {13, 11, 7, 14, 12, 1, 3, 9, 5, 0, 15, 4, 8, 6, 2, 10},
    {6, 15, 14, 9, 11, 3, 0, 8, 12, 2, 13, 7, 1, 4, 10, 5},
    {10, 2, 8, 4, 7, 6, 1, 5, 15, 11, 9, 14, 3, 12, 13, 0},
    {0, 1, 2, 3, 4, 5, 6, 7, 8, 9, 10, 11, 12, 13, 14, 15},
    {14, 10, 4, 8, 9, 15, 13, 6, 1, 12, 0, 2, 11, 7, 5, 3}};

static const u64 B2B_IV[8] = {
    0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
    0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
    0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};

static void b2b_compress(u64 h[8], const u8 blk[128], u64 t, int last) {
    u64 v[16], m[16];
    for (int i = 0; i < 8; i++) { v[i] = h[i]; v[i + 8] = B2B_IV[i]; }
    v[12] ^= t;
    if (last) v[14] = ~v[14];
    for (int i = 0; i < 16; i++) {
        m[i] = 0;
        for (int j = 7; j >= 0; j--) m[i] = (m[i] << 8) | blk[8 * i + j];
    }
#define G(a, b, c, d, x, y)                                  \
    v[a] = v[a] + v[b] + (x); v[d] = rotr64(v[d] ^ v[a], 32); \
    v[c] = v[c] + v[d];       v[b] = rotr64(v[b] ^ v[c], 24); \
    v[a] = v[a] + v[b] + (y); v[d] = rotr64(v[d] ^ v[a], 16); \
    v[c] = v[c] + v[d];       v[b] = rotr64(v[b] ^ v[c], 63)
    for (int r = 0; r < 12; r++) {
        const u8* s = B2B_SIGMA[r];
        G(0, 4, 8, 12, m[s[0]], m[s[1]]);
        G(1, 5, 9, 13, m[s[2]], m[s[3]]);
        G(2, 6, 10, 14, m[s[4]], m[s[5]]);
        G(3, 7, 11, 15, m[s[6]], m[s[7]]);
        G(0, 5, 10, 15, m[s[8]], m[s[9]]);
        G(1, 6, 11, 12, m[s[10]], m[s[11]]);
        G(2, 7, 8, 13, m[s[12]], m[s[13]]);
        G(3, 4, 9, 14, m[s[14]], m[s[15]]);
    }
#undef G
    for (int i = 0; i < 8; i++) h[i] ^= v[i] ^ v[i + 8];
}

static void blake2b(const u8* p, size_t n, u8* out, int outlen) {
    u64 h[8];
    memcpy(h, B2B_IV, sizeof h);
    h[0] ^= 0x01010000ULL ^ (u64)outlen;  // no key
    u64 t = 0;
    u8 blk[128];
    while (n > 128) {
        memcpy(blk, p, 128);
        t += 128;
        b2b_compress(h, blk, t, 0);
        p += 128; n -= 128;
    }
    memset(blk, 0, 128);
    memcpy(blk, p, n);
    t += n;
    b2b_compress(h, blk, t, 1);
    for (int i = 0; i < outlen; i++) out[i] = (u8)(h[i / 8] >> (8 * (i % 8)));
}

// ===========================================================================
// GF(2^255-19), radix-51
// ===========================================================================

struct fe { u64 v[5]; };
static const u64 M51 = (1ULL << 51) - 1;

static inline u64 load64(const u8* p) {
    u64 r = 0;
    for (int i = 7; i >= 0; i--) r = (r << 8) | p[i];
    return r;
}

static void fe_frombytes(fe* o, const u8 b[32]) {
    // value mod 2^255 (top bit ignored by callers that mask it)
    o->v[0] = load64(b) & M51;
    o->v[1] = (load64(b + 6) >> 3) & M51;
    o->v[2] = (load64(b + 12) >> 6) & M51;
    o->v[3] = (load64(b + 19) >> 1) & M51;
    o->v[4] = (load64(b + 24) >> 12) & M51;
}

static void fe_carry(fe* f) {
    for (int pass = 0; pass < 2; pass++) {
        u64 c = 0;
        for (int i = 0; i < 5; i++) {
            u64 t = f->v[i] + c;
            f->v[i] = t & M51;
            c = t >> 51;
        }
        f->v[0] += 19 * c;
    }
}

static void fe_tobytes(u8 b[32], const fe* f0) {
    // canonical encoding: add 19 to detect g >= p, fold the would-be
    // carry back as +19, then drop bit 255
    fe g = *f0;
    fe_carry(&g);
    u64 q = (g.v[0] + 19) >> 51;
    q = (g.v[1] + q) >> 51;
    q = (g.v[2] + q) >> 51;
    q = (g.v[3] + q) >> 51;
    q = (g.v[4] + q) >> 51;  // q = 1 iff g >= p
    g.v[0] += 19 * q;
    u64 c = 0;
    for (int i = 0; i < 5; i++) {
        u64 t = g.v[i] + c;
        g.v[i] = t & M51;
        c = t >> 51;
    }
    g.v[4] &= M51;  // drop bit 255 (the wrapped 2^255 when g >= p)
    u64 w[4];
    w[0] = g.v[0] | (g.v[1] << 51);
    w[1] = (g.v[1] >> 13) | (g.v[2] << 38);
    w[2] = (g.v[2] >> 26) | (g.v[3] << 25);
    w[3] = (g.v[3] >> 39) | (g.v[4] << 12);
    for (int i = 0; i < 4; i++)
        for (int j = 0; j < 8; j++) b[8 * i + j] = (u8)(w[i] >> (8 * j));
}

// every op keeps limbs nearly normalized (< 2^51 + eps): add/sub run one
// light carry pass so their outputs are safe as subtrahends of the next
// fe_sub (whose 8p bias caps the subtrahend at ~2^54)
static inline void fe_lightcarry(fe* o) {
    u64 c = 0;
    for (int i = 0; i < 5; i++) {
        u64 t = o->v[i] + c;
        o->v[i] = t & M51;
        c = t >> 51;
    }
    o->v[0] += 19 * c;
}

static inline void fe_add(fe* o, const fe* a, const fe* b) {
    for (int i = 0; i < 5; i++) o->v[i] = a->v[i] + b->v[i];
    fe_lightcarry(o);
}

static inline void fe_sub(fe* o, const fe* a, const fe* b) {
    // a + 8p - b, limb-wise non-negative for operand limbs < 2^54
    o->v[0] = a->v[0] + 0x3FFFFFFFFFFF68ULL - b->v[0];
    for (int i = 1; i < 5; i++)
        o->v[i] = a->v[i] + 0x3FFFFFFFFFFFF8ULL - b->v[i];
    fe_lightcarry(o);
}

static void fe_mul(fe* o, const fe* a, const fe* b) {
    u64 a0 = a->v[0], a1 = a->v[1], a2 = a->v[2], a3 = a->v[3], a4 = a->v[4];
    u64 b0 = b->v[0], b1 = b->v[1], b2 = b->v[2], b3 = b->v[3], b4 = b->v[4];
    u64 b1_19 = b1 * 19, b2_19 = b2 * 19, b3_19 = b3 * 19, b4_19 = b4 * 19;
    u128 t0 = (u128)a0 * b0 + (u128)a1 * b4_19 + (u128)a2 * b3_19 +
              (u128)a3 * b2_19 + (u128)a4 * b1_19;
    u128 t1 = (u128)a0 * b1 + (u128)a1 * b0 + (u128)a2 * b4_19 +
              (u128)a3 * b3_19 + (u128)a4 * b2_19;
    u128 t2 = (u128)a0 * b2 + (u128)a1 * b1 + (u128)a2 * b0 +
              (u128)a3 * b4_19 + (u128)a4 * b3_19;
    u128 t3 = (u128)a0 * b3 + (u128)a1 * b2 + (u128)a2 * b1 +
              (u128)a3 * b0 + (u128)a4 * b4_19;
    u128 t4 = (u128)a0 * b4 + (u128)a1 * b3 + (u128)a2 * b2 +
              (u128)a3 * b1 + (u128)a4 * b0;
    // 128-bit carries: with lazy (< 2^55) operands the column sums reach
    // ~2^116 and a 64-bit carry would truncate
    u64 r0, r1, r2, r3, r4;
    r0 = (u64)t0 & M51; t1 += t0 >> 51;
    r1 = (u64)t1 & M51; t2 += t1 >> 51;
    r2 = (u64)t2 & M51; t3 += t2 >> 51;
    r3 = (u64)t3 & M51; t4 += t3 >> 51;
    r4 = (u64)t4 & M51;
    u128 f = (u128)r0 + (t4 >> 51) * 19;
    r0 = (u64)f & M51;
    r1 += (u64)(f >> 51);
    o->v[0] = r0; o->v[1] = r1; o->v[2] = r2; o->v[3] = r3; o->v[4] = r4;
}

static void fe_sq(fe* o, const fe* a) {
    u64 a0 = a->v[0], a1 = a->v[1], a2 = a->v[2], a3 = a->v[3], a4 = a->v[4];
    u64 d0 = 2 * a0, d1 = 2 * a1, d2 = 2 * a2, d3 = 2 * a3;
    u64 a3_19 = a3 * 19, a4_19 = a4 * 19;
    u128 t0 = (u128)a0 * a0 + (u128)d1 * a4_19 + (u128)d2 * a3_19;
    u128 t1 = (u128)d0 * a1 + (u128)d2 * a4_19 + (u128)a3 * a3_19;
    u128 t2 = (u128)d0 * a2 + (u128)a1 * a1 + (u128)d3 * a4_19;
    u128 t3 = (u128)d0 * a3 + (u128)d1 * a2 + (u128)a4 * a4_19;
    u128 t4 = (u128)d0 * a4 + (u128)d1 * a3 + (u128)a2 * a2;
    u64 r0, r1, r2, r3, r4;
    r0 = (u64)t0 & M51; t1 += t0 >> 51;
    r1 = (u64)t1 & M51; t2 += t1 >> 51;
    r2 = (u64)t2 & M51; t3 += t2 >> 51;
    r3 = (u64)t3 & M51; t4 += t3 >> 51;
    r4 = (u64)t4 & M51;
    u128 f = (u128)r0 + (t4 >> 51) * 19;
    r0 = (u64)f & M51;
    r1 += (u64)(f >> 51);
    o->v[0] = r0; o->v[1] = r1; o->v[2] = r2; o->v[3] = r3; o->v[4] = r4;
}

static void fe_powloop(fe* o, const fe* x, int k) {
    *o = *x;
    for (int i = 0; i < k; i++) fe_sq(o, o);
}

// x^(2^250-1) chain shared by inv / pow22523 / legendre
static void fe_chain250(fe* g, fe* x11, const fe* x) {
    fe t0, t1, t31, a, b, c, d, e, f2;
    fe_sq(&t0, x);                       // x^2
    fe tmp;
    fe_sq(&tmp, &t0); fe_sq(&tmp, &tmp); // x^8
    fe_mul(&t1, x, &tmp);                // x^9
    fe_mul(x11, &t0, &t1);               // x^11
    fe_sq(&tmp, x11);
    fe_mul(&t31, &t1, &tmp);             // x^31 = 2^5-1
    fe_powloop(&tmp, &t31, 5); fe_mul(&a, &tmp, &t31);   // 2^10-1
    fe_powloop(&tmp, &a, 10); fe_mul(&b, &tmp, &a);      // 2^20-1
    fe_powloop(&tmp, &b, 20); fe_mul(&c, &tmp, &b);      // 2^40-1
    fe_powloop(&tmp, &c, 10); fe_mul(&d, &tmp, &a);      // 2^50-1
    fe_powloop(&tmp, &d, 50); fe_mul(&e, &tmp, &d);      // 2^100-1
    fe_powloop(&tmp, &e, 100); fe_mul(&f2, &tmp, &e);    // 2^200-1
    fe_powloop(&tmp, &f2, 50); fe_mul(g, &tmp, &d);      // 2^250-1
}

static void fe_inv(fe* o, const fe* x) {
    fe g, x11, t;
    fe_chain250(&g, &x11, x);
    fe_powloop(&t, &g, 5);
    fe_mul(o, &t, &x11);  // 2^255-21
}

static void fe_pow22523(fe* o, const fe* x) {
    fe g, x11, t;
    fe_chain250(&g, &x11, x);
    fe_powloop(&t, &g, 2);
    fe_mul(o, &t, x);  // 2^252-3
}

static int fe_iszero(const fe* f) {
    u8 b[32];
    fe_tobytes(b, f);
    u8 acc = 0;
    for (int i = 0; i < 32; i++) acc |= b[i];
    return acc == 0;
}

static int fe_eq(const fe* a, const fe* b) {
    u8 x[32], y[32];
    fe_tobytes(x, a);
    fe_tobytes(y, b);
    return memcmp(x, y, 32) == 0;
}

static int fe_isodd(const fe* f) {
    u8 b[32];
    fe_tobytes(b, f);
    return b[0] & 1;
}

static void fe_neg(fe* o, const fe* a) {
    fe z = {{0, 0, 0, 0, 0}};
    fe_sub(o, &z, a);
}

static void fe_set(fe* o, u64 x) {
    o->v[0] = x;
    o->v[1] = o->v[2] = o->v[3] = o->v[4] = 0;
}

// constants
static const u8 K_D[32] = {163,120,89,19,202,77,235,117,171,216,65,65,77,10,112,0,152,232,121,119,121,64,199,140,115,254,111,43,238,108,3,82};
static const u8 K_SQRT_M1[32] = {176,160,14,74,39,27,238,196,120,228,47,173,6,24,67,47,167,215,251,61,153,0,77,43,11,223,193,79,128,36,131,43};
static const u8 K_SQRT_M486664[32] = {6,126,69,255,170,4,110,204,130,26,125,75,209,211,161,197,126,79,252,3,220,8,123,210,187,6,160,96,244,237,38,15};
static const u8 K_BX[32] = {26,213,37,143,96,45,86,201,178,167,37,149,96,199,44,105,92,220,214,253,49,226,164,192,254,83,110,205,211,54,105,33};
static const u8 K_BY[32] = {88,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102,102};
static const u8 K_L[32] = {237,211,245,92,26,99,18,88,214,156,247,162,222,249,222,20,0,0,0,0,0,0,0,0,0,0,0,0,0,0,0,16};

static fe FE_D, FE_SQRT_M1, FE_SQRT_M486664;
static int consts_ready = 0;
static void init_consts();

// sqrt with even-root convention (ops/host/ed25519.fe_sqrt): returns 0 on
// failure, 1 on success
static int fe_sqrt_even(fe* o, const fe* x) {
    init_consts();
    fe r, r2;
    fe_pow22523(&r, x);
    fe x3, x7;  // r = x^((p+3)/8) = x * x^((p-5)/8)?  No: compute directly
    // x^((p+3)/8) = x^(2^252-2) = (x^(2^252-3)) * x
    fe_mul(&r, &r, x);
    fe_sq(&r2, &r);
    if (!fe_eq(&r2, x)) {
        fe_mul(&r, &r, &FE_SQRT_M1);
        fe_sq(&r2, &r);
        if (!fe_eq(&r2, x)) return 0;
    }
    if (fe_isodd(&r)) fe_neg(&r, &r);
    *o = r;
    (void)x3; (void)x7;
    return 1;
}

// legendre symbol via x^((p-1)/2); returns 1 if square or zero
static int fe_issquare(const fe* x) {
    if (fe_iszero(x)) return 1;
    // (p-1)/2 = 2^254 - 10
    fe g, x11, t, x4, x6, acc;
    fe_chain250(&g, &x11, x);      // 2^250-1
    fe_powloop(&t, &g, 4);         // 2^254-16
    fe_sq(&x4, x); fe_sq(&x4, &x4);      // x^4
    fe_sq(&x6, x); fe_mul(&x6, &x4, &x6); // x^6
    fe_mul(&acc, &t, &x6);         // 2^254-10
    fe one;
    fe_set(&one, 1);
    return fe_eq(&acc, &one);
}

// ===========================================================================
// Edwards points (extended coordinates)
// ===========================================================================

struct ge { fe x, y, z, t; };

static ge GE_B;

static void ge_ident(ge* o) {
    fe_set(&o->x, 0);
    fe_set(&o->y, 1);
    fe_set(&o->z, 1);
    fe_set(&o->t, 0);
}

static void init_consts() {
    if (consts_ready) return;
    consts_ready = 1;
    fe_frombytes(&FE_D, K_D);
    fe_frombytes(&FE_SQRT_M1, K_SQRT_M1);
    fe_frombytes(&FE_SQRT_M486664, K_SQRT_M486664);
    fe_frombytes(&GE_B.x, K_BX);
    fe_frombytes(&GE_B.y, K_BY);
    fe_set(&GE_B.z, 1);
    fe_mul(&GE_B.t, &GE_B.x, &GE_B.y);
}

static void ge_add(ge* o, const ge* p, const ge* q) {
    init_consts();
    fe a, b, c, d, e, f, g, h, t0, t1;
    fe_sub(&t0, &p->y, &p->x);
    fe_sub(&t1, &q->y, &q->x);
    fe_mul(&a, &t0, &t1);
    fe_add(&t0, &p->y, &p->x);
    fe_add(&t1, &q->y, &q->x);
    fe_mul(&b, &t0, &t1);
    fe_mul(&c, &p->t, &q->t);
    fe_mul(&c, &c, &FE_D);
    fe_add(&c, &c, &c);
    fe_mul(&d, &p->z, &q->z);
    fe_add(&d, &d, &d);
    fe_sub(&e, &b, &a);
    fe_sub(&f, &d, &c);
    fe_add(&g, &d, &c);
    fe_add(&h, &b, &a);
    fe_mul(&o->x, &e, &f);
    fe_mul(&o->y, &g, &h);
    fe_mul(&o->z, &f, &g);
    fe_mul(&o->t, &e, &h);
}

static void ge_double(ge* o, const ge* p) {
    fe a, b, c, e, f, g, h, t0;
    fe_sq(&a, &p->x);
    fe_sq(&b, &p->y);
    fe_sq(&c, &p->z);
    fe_add(&c, &c, &c);
    fe_add(&h, &a, &b);
    fe_add(&t0, &p->x, &p->y);
    fe_sq(&t0, &t0);
    fe_sub(&e, &h, &t0);
    fe_sub(&g, &a, &b);
    fe_add(&f, &c, &g);
    fe_mul(&o->x, &e, &f);
    fe_mul(&o->y, &g, &h);
    fe_mul(&o->z, &f, &g);
    fe_mul(&o->t, &e, &h);
}

static void ge_neg(ge* o, const ge* p) {
    fe_neg(&o->x, &p->x);
    o->y = p->y;
    o->z = p->z;
    fe_neg(&o->t, &p->t);
}

static int ge_eq(const ge* p, const ge* q) {
    fe a, b;
    fe_mul(&a, &p->x, &q->z);
    fe_mul(&b, &q->x, &p->z);
    if (!fe_eq(&a, &b)) return 0;
    fe_mul(&a, &p->y, &q->z);
    fe_mul(&b, &q->y, &p->z);
    return fe_eq(&a, &b);
}

static void ge_tobytes(u8 b[32], const ge* p) {
    fe zi, x, y;
    fe_inv(&zi, &p->z);
    fe_mul(&x, &p->x, &zi);
    fe_mul(&y, &p->y, &zi);
    fe_tobytes(b, &y);
    b[31] |= (u8)(fe_isodd(&x) << 7);
}

// decompress with the exact ops/host/ed25519.point_decompress semantics:
// reject y >= p (non-canonical), non-residue, x=0 with sign bit
static int ge_frombytes(ge* o, const u8 b[32]) {
    init_consts();
    int sign = b[31] >> 7;
    u8 yb[32];
    memcpy(yb, b, 32);
    yb[31] &= 0x7F;
    // canonical check: y < p
    u8 canon[32];
    fe ytmp;
    fe_frombytes(&ytmp, yb);
    fe_tobytes(canon, &ytmp);
    if (memcmp(canon, yb, 32) != 0) return 0;
    fe y = ytmp, y2, num, den, x;
    fe one;
    fe_set(&one, 1);
    fe_sq(&y2, &y);
    fe_sub(&num, &y2, &one);
    fe_mul(&den, &y2, &FE_D);
    fe_add(&den, &den, &one);
    // x = sqrt(num/den): r = num * den^3 * (num * den^7)^((p-5)/8)
    fe den2, den3, den7, u, r, r2, chk;
    fe_sq(&den2, &den);
    fe_mul(&den3, &den2, &den);
    fe_mul(&den7, &den3, &den2);
    fe_mul(&den7, &den7, &den2);
    fe_mul(&u, &num, &den7);
    fe_pow22523(&r, &u);
    fe_mul(&r, &r, &num);
    fe_mul(&r, &r, &den3);
    fe_sq(&r2, &r);
    fe_mul(&chk, &r2, &den);
    if (!fe_eq(&chk, &num)) {
        fe negnum;
        fe_neg(&negnum, &num);
        if (!fe_eq(&chk, &negnum)) return 0;
        fe_mul(&r, &r, &FE_SQRT_M1);
    }
    x = r;
    int xz = fe_iszero(&x);
    if (xz && sign) return 0;
    if (!xz && fe_isodd(&x) != sign) fe_neg(&x, &x);
    o->x = x;
    o->y = y;
    fe_set(&o->z, 1);
    fe_mul(&o->t, &x, &y);
    return 1;
}

// variable-base scalar mult, 4-bit windows (scalar: 32 LE bytes)
static void ge_scalarmult(ge* o, const u8 s[32], const ge* p) {
    ge tbl[16];
    ge_ident(&tbl[0]);
    tbl[1] = *p;
    for (int i = 2; i < 16; i++) ge_add(&tbl[i], &tbl[i - 1], p);
    ge q;
    ge_ident(&q);
    for (int i = 63; i >= 0; i--) {
        for (int k = 0; k < 4; k++) ge_double(&q, &q);
        int d = (s[i / 2] >> (4 * ((i & 1)))) & 0xF;
        if (d) ge_add(&q, &q, &tbl[d]);
    }
    *o = q;
}

// a*P + b*Q with one shared doubling chain (Strauss, 4-bit windows)
static void ge_double_scalarmult(ge* o, const u8 a[32], const ge* p,
                                 const u8 b[32], const ge* q) {
    ge tp[16], tq[16];
    ge_ident(&tp[0]);
    tp[1] = *p;
    for (int i = 2; i < 16; i++) ge_add(&tp[i], &tp[i - 1], p);
    ge_ident(&tq[0]);
    tq[1] = *q;
    for (int i = 2; i < 16; i++) ge_add(&tq[i], &tq[i - 1], q);
    ge r;
    ge_ident(&r);
    for (int i = 63; i >= 0; i--) {
        for (int k = 0; k < 4; k++) ge_double(&r, &r);
        int da = (a[i / 2] >> (4 * (i & 1))) & 0xF;
        int db = (b[i / 2] >> (4 * (i & 1))) & 0xF;
        if (da) ge_add(&r, &r, &tp[da]);
        if (db) ge_add(&r, &r, &tq[db]);
    }
    *o = r;
}

static void ge_scalarmult_small(ge* o, u64 k, const ge* p) {
    ge q;
    ge_ident(&q);
    ge base = *p;
    while (k) {
        if (k & 1) ge_add(&q, &q, &base);
        ge_double(&base, &base);
        k >>= 1;
    }
    *o = q;
}

// ===========================================================================
// Scalars mod L
// ===========================================================================

// 320-bit accumulator as 5x64
struct sc320 { u64 v[5]; };

static int sc_geq(const sc320* a, const sc320* b) {
    for (int i = 4; i >= 0; i--) {
        if (a->v[i] != b->v[i]) return a->v[i] > b->v[i];
    }
    return 1;
}

static void sc_sub(sc320* a, const sc320* b) {
    u64 borrow = 0;
    for (int i = 0; i < 5; i++) {
        u64 bi = b->v[i] + borrow;
        borrow = (bi < borrow) || (a->v[i] < bi);
        a->v[i] = a->v[i] - bi;
    }
}

static void sc_shl(sc320* a, int k) {  // k < 64
    if (!k) return;
    for (int i = 4; i > 0; i--)
        a->v[i] = (a->v[i] << k) | (a->v[i - 1] >> (64 - k));
    a->v[0] <<= k;
}

// r = bytes (LE, any length) mod L -> 32 LE bytes
static void sc_reduce(u8 out[32], const u8* in, size_t len) {
    sc320 L = {{0}};
    for (int i = 0; i < 32; i++) L.v[i / 8] |= (u64)K_L[i] << (8 * (i % 8));
    sc320 r = {{0}};
    for (size_t i = 0; i < len; i++) {
        // r = r*256 + in[len-1-i]
        sc_shl(&r, 8);
        r.v[0] |= in[len - 1 - i];
        // r < 256*L < 2^261: subtract L<<k for k = 8..0
        for (int k = 8; k >= 0; k--) {
            sc320 lk = L;
            sc_shl(&lk, k);
            if (sc_geq(&r, &lk)) sc_sub(&r, &lk);
        }
    }
    for (int i = 0; i < 32; i++) out[i] = (u8)(r.v[i / 8] >> (8 * (i % 8)));
}

static int sc_is_canonical(const u8 s[32]) {
    for (int i = 31; i >= 0; i--) {
        if (s[i] != K_L[i]) return s[i] < K_L[i];
    }
    return 0;  // s == L
}

// ===========================================================================
// Ed25519 verify (cofactorless) — mirrors ops/host/ed25519.verify
// ===========================================================================

extern "C" int oc_ed25519_verify(const u8 pk[32], const u8 sig[64],
                                 const u8* msg, size_t len) {
    init_consts();
    ge A, R;
    if (!ge_frombytes(&A, pk)) return 0;
    if (!ge_frombytes(&R, sig)) return 0;
    if (!sc_is_canonical(sig + 32)) return 0;
    Sha512 h;
    h.init();
    h.update(sig, 32);
    h.update(pk, 32);
    h.update(msg, len);
    u8 digest[64], hred[32];
    h.final(digest);
    sc_reduce(hred, digest, 64);
    // s*B - h*A must equal R (shared-doubling Strauss with -A)
    ge nA, P;
    ge_neg(&nA, &A);
    ge_double_scalarmult(&P, sig + 32, &GE_B, hred, &nA);
    return ge_eq(&P, &R);
}

// ===========================================================================
// ECVRF draft-03 verify — mirrors ops/host/ecvrf.py
// ===========================================================================

static const u8 VRF_SUITE = 0x04;
static const u64 MONT_A = 486662;

static void elligator2(ge* o, const fe* r) {
    init_consts();
    fe one, monta, t, denom, u, w, u2, tmp;
    fe_set(&one, 1);
    fe_set(&monta, MONT_A);
    fe_sq(&t, r);
    fe_add(&t, &t, &t);  // 2r^2
    fe_add(&denom, &t, &one);
    if (fe_iszero(&denom)) fe_set(&denom, 1);
    fe_inv(&tmp, &denom);
    fe_mul(&u, &monta, &tmp);
    fe_neg(&u, &u);  // -A/(1+2r^2)
    // w = u(u^2+Au+1)
    fe_sq(&w, &u);
    fe_mul(&tmp, &monta, &u);
    fe_add(&w, &w, &tmp);
    fe_add(&w, &w, &one);
    fe_mul(&w, &w, &u);
    if (!fe_issquare(&w)) {
        fe_neg(&u2, &u);
        fe_sub(&u, &u2, &monta);  // -u - A
        fe_sq(&w, &u);
        fe_mul(&tmp, &monta, &u);
        fe_add(&w, &w, &tmp);
        fe_add(&w, &w, &one);
        fe_mul(&w, &w, &u);
    }
    fe v, x, y, up1;
    int ok = fe_sqrt_even(&v, &w);
    (void)ok;  // w is square by construction
    if (fe_iszero(&v)) {
        fe_set(&x, 0);
    } else {
        fe_inv(&tmp, &v);
        fe_mul(&x, &FE_SQRT_M486664, &u);
        fe_mul(&x, &x, &tmp);
    }
    fe_add(&up1, &u, &one);
    if (fe_iszero(&up1)) {
        fe_set(&y, 0);
    } else {
        fe_inv(&tmp, &up1);
        fe_sub(&y, &u, &one);
        fe_mul(&y, &y, &tmp);
    }
    if (fe_isodd(&x)) fe_neg(&x, &x);
    o->x = x;
    o->y = y;
    fe_set(&o->z, 1);
    fe_mul(&o->t, &x, &y);
}

static void vrf_hash_to_curve(ge* o, const u8 pk[32], const u8* alpha,
                              size_t alen) {
    Sha512 h;
    h.init();
    u8 pre[2] = {VRF_SUITE, 0x01};
    h.update(pre, 2);
    h.update(pk, 32);
    h.update(alpha, alen);
    u8 d[64];
    h.final(d);
    u8 rb[32];
    memcpy(rb, d, 32);
    rb[31] &= 0x7F;
    fe r;
    fe_frombytes(&r, rb);  // < 2^255; elligator works mod p
    ge e;
    elligator2(&e, &r);
    ge_double(&e, &e);
    ge_double(&e, &e);
    ge_double(&e, &e);  // *8
    *o = e;
}

// returns 1 and writes beta[64] on success
extern "C" int oc_ecvrf_verify(const u8 pk[32], const u8 pi[80],
                               const u8* alpha, size_t alen, u8 beta[64]) {
    init_consts();
    ge Y, Gamma;
    if (!ge_frombytes(&Y, pk)) return 0;
    if (!ge_frombytes(&Gamma, pi)) return 0;
    const u8* c16 = pi + 32;
    const u8* s32 = pi + 48;
    if (!sc_is_canonical(s32)) return 0;
    ge H;
    vrf_hash_to_curve(&H, pk, alpha, alen);
    u8 c32[32] = {0};
    memcpy(c32, c16, 16);
    ge U, V, nY, nG;
    ge_neg(&nY, &Y);
    ge_double_scalarmult(&U, s32, &GE_B, c32, &nY);
    ge_neg(&nG, &Gamma);
    ge_double_scalarmult(&V, s32, &H, c32, &nG);
    u8 henc[32], genc[32], uenc[32], venc[32];
    ge_tobytes(henc, &H);
    ge_tobytes(genc, &Gamma);
    ge_tobytes(uenc, &U);
    ge_tobytes(venc, &V);
    Sha512 ch;
    ch.init();
    u8 pre[2] = {VRF_SUITE, 0x02};
    ch.update(pre, 2);
    ch.update(henc, 32);
    ch.update(genc, 32);
    ch.update(uenc, 32);
    ch.update(venc, 32);
    u8 cd[64];
    ch.final(cd);
    if (memcmp(cd, c16, 16) != 0) return 0;
    ge G8;
    ge_double(&G8, &Gamma);
    ge_double(&G8, &G8);
    ge_double(&G8, &G8);
    u8 g8enc[32];
    ge_tobytes(g8enc, &G8);
    Sha512 bh;
    bh.init();
    u8 pre3[2] = {VRF_SUITE, 0x03};
    bh.update(pre3, 2);
    bh.update(g8enc, 32);
    bh.final(beta);
    return 1;
}

// Batch-compatible ECVRF (PraosBatchCompat shape): pi = Gamma || U || V || s
// (128 bytes). The challenge is DERIVED from the announced U, V and the two
// group equations are checked — mirrors ops/host/ecvrf.verify_batch_compat.
extern "C" int oc_ecvrf_verify_bc(const u8 pk[32], const u8 pi[128],
                                  const u8* alpha, size_t alen, u8 beta[64]) {
    init_consts();
    ge Y, Gamma, U, V;
    if (!ge_frombytes(&Y, pk)) return 0;
    if (!ge_frombytes(&Gamma, pi)) return 0;
    if (!ge_frombytes(&U, pi + 32)) return 0;
    if (!ge_frombytes(&V, pi + 64)) return 0;
    const u8* s32 = pi + 96;
    if (!sc_is_canonical(s32)) return 0;
    ge H;
    vrf_hash_to_curve(&H, pk, alpha, alen);
    u8 henc[32];
    ge_tobytes(henc, &H);
    Sha512 ch;
    ch.init();
    u8 pre[2] = {VRF_SUITE, 0x02};
    ch.update(pre, 2);
    ch.update(henc, 32);
    ch.update(pi, 96);  // Gamma || U || V announced bytes
    u8 cd[64];
    ch.final(cd);
    u8 c32[32] = {0};
    memcpy(c32, cd, 16);
    // s*B - c*Y must equal U; s*H - c*Gamma must equal V
    ge nY, nG, P;
    ge_neg(&nY, &Y);
    ge_double_scalarmult(&P, s32, &GE_B, c32, &nY);
    if (!ge_eq(&P, &U)) return 0;
    ge_neg(&nG, &Gamma);
    ge_double_scalarmult(&P, s32, &H, c32, &nG);
    if (!ge_eq(&P, &V)) return 0;
    ge G8;
    ge_double(&G8, &Gamma);
    ge_double(&G8, &G8);
    ge_double(&G8, &G8);
    u8 g8enc[32];
    ge_tobytes(g8enc, &G8);
    Sha512 bh;
    bh.init();
    u8 pre3[2] = {VRF_SUITE, 0x03};
    bh.update(pre3, 2);
    bh.update(g8enc, 32);
    bh.final(beta);
    return 1;
}

// ===========================================================================
// CompactSum KES verify — mirrors ops/host/kes.py
// ===========================================================================

extern "C" int oc_kes_verify(const u8 vk[32], int depth, u64 period,
                             const u8* msg, size_t len, const u8* sig,
                             size_t siglen) {
    if (depth < 0 || depth > 20) return 0;
    size_t expect = 96 + 32 * (size_t)depth;
    if (siglen != expect) return 0;
    if (period >= (1ULL << depth)) return 0;
    const u8* ed_sig = sig;
    const u8* vk_leaf = sig + 64;
    if (!oc_ed25519_verify(vk_leaf, ed_sig, msg, len)) return 0;
    u8 cur[32];
    memcpy(cur, vk_leaf, 32);
    for (int i = 0; i < depth; i++) {
        const u8* sib = sig + 96 + 32 * i;
        u8 data[64];
        if ((period >> i) & 1) {
            memcpy(data, sib, 32);
            memcpy(data + 32, cur, 32);
        } else {
            memcpy(data, cur, 32);
            memcpy(data + 32, sib, 32);
        }
        blake2b(data, 64, cur, 32);
    }
    return memcmp(cur, vk, 32) == 0;
}

// ===========================================================================
// Hash helpers + the Praos per-header fold driver
// ===========================================================================

extern "C" void oc_sha512(const u8* p, size_t n, u8 out[64]) { sha512(p, n, out); }
extern "C" void oc_blake2b(const u8* p, size_t n, u8* out, int outlen) {
    blake2b(p, n, out, outlen);
}

// ---------------------------------------------------------------------------
// CRC32 (zlib polynomial 0xEDB88320, reflected) — the sidecar probe's
// seal check. PCLMULQDQ 4-way folding where the CPU has it (runtime
// detected; ~10x zlib's slicing tables), slicing-by-8 otherwise. Both
// produce values bit-identical to zlib.crc32 — the seals on disk were
// written with zlib and MUST keep verifying.
// ---------------------------------------------------------------------------

static uint32_t crc32_tab[8][256];
static int crc32_tab_ready = 0;

static void crc32_tab_init(void) {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c >> 1) ^ (0xEDB88320u & (0u - (c & 1u)));
        crc32_tab[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++)
        for (int j = 1; j < 8; j++)
            crc32_tab[j][i] = (crc32_tab[j - 1][i] >> 8)
                ^ crc32_tab[0][crc32_tab[j - 1][i] & 0xffu];
    crc32_tab_ready = 1;
}

static uint32_t crc32_sw(const u8* p, size_t n, uint32_t crc) {
    if (!crc32_tab_ready) crc32_tab_init();
    crc = ~crc;
    while (n && ((uintptr_t)p & 7)) {
        crc = (crc >> 8) ^ crc32_tab[0][(crc ^ *p++) & 0xffu];
        n--;
    }
    while (n >= 8) {
        u64 v;
        memcpy(&v, p, 8);
        v ^= crc;
        crc = crc32_tab[7][v & 0xff] ^ crc32_tab[6][(v >> 8) & 0xff]
            ^ crc32_tab[5][(v >> 16) & 0xff] ^ crc32_tab[4][(v >> 24) & 0xff]
            ^ crc32_tab[3][(v >> 32) & 0xff] ^ crc32_tab[2][(v >> 40) & 0xff]
            ^ crc32_tab[1][(v >> 48) & 0xff] ^ crc32_tab[0][(v >> 56) & 0xff];
        p += 8;
        n -= 8;
    }
    while (n--) crc = (crc >> 8) ^ crc32_tab[0][(crc ^ *p++) & 0xffu];
    return ~crc;
}

#if defined(__x86_64__) || defined(__i386__)
#include <immintrin.h>

// Reflected CRC-32 by 4x128-bit carry-less folding (the classic Intel
// PCLMULQDQ scheme; constants are x^K mod P for the zlib polynomial).
// Takes and returns the RAW (pre/post-inverted) crc register; requires
// len >= 64 and len % 16 == 0 — the caller folds the tail with tables.
__attribute__((target("pclmul,sse4.1")))
static uint32_t crc32_clmul(const u8* buf, size_t len, uint32_t crc) {
    const __m128i k1k2 = _mm_set_epi64x(0x00000001c6e41596ll,
                                        0x0000000154442bd4ll);
    const __m128i k3k4 = _mm_set_epi64x(0x00000000ccaa009ell,
                                        0x00000001751997d0ll);
    const __m128i k5k0 = _mm_set_epi64x(0x0000000000000000ll,
                                        0x0000000163cd6124ll);
    const __m128i poly = _mm_set_epi64x(0x00000001f7011641ll,
                                        0x00000001db710641ll);
    __m128i x0, x1, x2, x3, x4, x5, x6, x7, x8, y5, y6, y7, y8;

    x1 = _mm_loadu_si128((const __m128i*)(buf + 0x00));
    x2 = _mm_loadu_si128((const __m128i*)(buf + 0x10));
    x3 = _mm_loadu_si128((const __m128i*)(buf + 0x20));
    x4 = _mm_loadu_si128((const __m128i*)(buf + 0x30));
    x1 = _mm_xor_si128(x1, _mm_cvtsi32_si128((int)crc));
    x0 = k1k2;
    buf += 64;
    len -= 64;

    while (len >= 64) {
        x5 = _mm_clmulepi64_si128(x1, x0, 0x00);
        x6 = _mm_clmulepi64_si128(x2, x0, 0x00);
        x7 = _mm_clmulepi64_si128(x3, x0, 0x00);
        x8 = _mm_clmulepi64_si128(x4, x0, 0x00);
        x1 = _mm_clmulepi64_si128(x1, x0, 0x11);
        x2 = _mm_clmulepi64_si128(x2, x0, 0x11);
        x3 = _mm_clmulepi64_si128(x3, x0, 0x11);
        x4 = _mm_clmulepi64_si128(x4, x0, 0x11);
        y5 = _mm_loadu_si128((const __m128i*)(buf + 0x00));
        y6 = _mm_loadu_si128((const __m128i*)(buf + 0x10));
        y7 = _mm_loadu_si128((const __m128i*)(buf + 0x20));
        y8 = _mm_loadu_si128((const __m128i*)(buf + 0x30));
        x1 = _mm_xor_si128(_mm_xor_si128(x1, x5), y5);
        x2 = _mm_xor_si128(_mm_xor_si128(x2, x6), y6);
        x3 = _mm_xor_si128(_mm_xor_si128(x3, x7), y7);
        x4 = _mm_xor_si128(_mm_xor_si128(x4, x8), y8);
        buf += 64;
        len -= 64;
    }

    // fold the four lanes down to one
    x0 = k3k4;
    x5 = _mm_clmulepi64_si128(x1, x0, 0x00);
    x1 = _mm_clmulepi64_si128(x1, x0, 0x11);
    x1 = _mm_xor_si128(_mm_xor_si128(x1, x2), x5);
    x5 = _mm_clmulepi64_si128(x1, x0, 0x00);
    x1 = _mm_clmulepi64_si128(x1, x0, 0x11);
    x1 = _mm_xor_si128(_mm_xor_si128(x1, x3), x5);
    x5 = _mm_clmulepi64_si128(x1, x0, 0x00);
    x1 = _mm_clmulepi64_si128(x1, x0, 0x11);
    x1 = _mm_xor_si128(_mm_xor_si128(x1, x4), x5);

    while (len >= 16) {
        x2 = _mm_loadu_si128((const __m128i*)buf);
        x5 = _mm_clmulepi64_si128(x1, x0, 0x00);
        x1 = _mm_clmulepi64_si128(x1, x0, 0x11);
        x1 = _mm_xor_si128(_mm_xor_si128(x1, x2), x5);
        buf += 16;
        len -= 16;
    }

    // 128 -> 64 -> 32 reduction, then Barrett
    x2 = _mm_clmulepi64_si128(x1, x0, 0x10);
    x3 = _mm_setr_epi32(~0, 0, ~0, 0);
    x1 = _mm_srli_si128(x1, 8);
    x1 = _mm_xor_si128(x1, x2);

    x0 = k5k0;
    x2 = _mm_srli_si128(x1, 4);
    x1 = _mm_and_si128(x1, x3);
    x1 = _mm_clmulepi64_si128(x1, x0, 0x00);
    x1 = _mm_xor_si128(x1, x2);

    x0 = poly;
    x2 = _mm_and_si128(x1, x3);
    x2 = _mm_clmulepi64_si128(x2, x0, 0x10);
    x2 = _mm_and_si128(x2, x3);
    x2 = _mm_clmulepi64_si128(x2, x0, 0x00);
    x1 = _mm_xor_si128(x1, x2);

    return (uint32_t)_mm_extract_epi32(x1, 1);
}
#endif

extern "C" uint32_t oc_crc32(const u8* p, size_t n, uint32_t crc) {
#if defined(__x86_64__) || defined(__i386__)
    if (n >= 64 && __builtin_cpu_supports("pclmul")
            && __builtin_cpu_supports("sse4.1")) {
        size_t chunk = n & ~(size_t)15;
        crc = ~crc32_clmul(p, chunk, ~crc);
        p += chunk;
        n -= chunk;
    }
#endif
    return crc32_sw(p, n, crc);
}

// Batch blake2b over n spans data[starts[i]:ends[i]) → out[i*outlen ..).
// The columnar-sidecar body-hash sweep: one C loop over the whole chunk
// instead of n Python-side hashlib round-trips.
extern "C" void oc_blake2b_spans(const u8* data, long n,
                                 const long long* starts,
                                 const long long* ends, u8* out,
                                 int outlen) {
    for (long i = 0; i < n; i++) {
        long long s = starts[i], e = ends[i];
        if (e < s) e = s;
        blake2b(data + s, (size_t)(e - s), out + (size_t)i * outlen, outlen);
    }
}

// The full per-header crypto of Praos updateChainDepState
// (Praos.hs:441-606): OCert DSIGN verify + CompactSum KES verify + ECVRF
// verify + declared-output compare. State bookkeeping (nonces, counters,
// leader threshold rationals) stays in the Python fold — it is O(ns) per
// header next to ~0.5ms of crypto. Returns the first failing header
// index (with *fail_kind in {1:ocert, 2:kes, 3:vrf}), or -1 when all n
// verify. Emits per-header blake2b("L" ‖ beta) leader values and the
// vrfNonceValue eta = blake2b(blake2b("N" ‖ beta)) for the nonce fold
// (Praos/VRF.hs:103,116).
// v2: vrf_proof_len selects the proof format (80 = draft-03, 128 =
// batch-compatible); oc_validate_praos below keeps the original 80-byte ABI.
extern "C" long oc_validate_praos2(
    long n,
    const u8* cold_vk,        // n*32
    const u8* ocert_sig,      // n*64
    const u8* ocert_msg,      // n*48 (vk_hot || counter_be8 || period_be8)
    const u8* kes_vk,         // n*32
    const long* kes_t,        // n (evolution = period(slot) - c0)
    const u8* kes_sig,        // n*kes_siglen
    long kes_depth,
    const u8* body,           // flattened signed_bytes
    const long* body_off,     // n+1
    const u8* vrf_vk,         // n*32
    const u8* vrf_proof,      // n*vrf_proof_len
    long vrf_proof_len,       // 80 (draft-03) or 128 (batch-compatible)
    const u8* vrf_alpha,      // n*32
    const u8* vrf_output,     // n*64 (declared beta)
    u8* leader_value,         // out: n*32 blake2b("L" || beta), or NULL
    u8* eta_out,              // out: n*32 vrfNonceValue, or NULL
    long* fail_kind           // out: failure class at the returned index
) {
    size_t kes_siglen = 96 + 32 * (size_t)kes_depth;
    if (fail_kind) *fail_kind = 0;
    if (vrf_proof_len != 80 && vrf_proof_len != 128) {
        if (fail_kind) *fail_kind = 3;
        return n ? 0 : -1;
    }
    for (long i = 0; i < n; i++) {
        if (!oc_ed25519_verify(cold_vk + 32 * i, ocert_sig + 64 * i,
                               ocert_msg + 48 * i, 48)) {
            if (fail_kind) *fail_kind = 1;
            return i;
        }
        const u8* b = body + body_off[i];
        size_t blen = (size_t)(body_off[i + 1] - body_off[i]);
        if (!oc_kes_verify(kes_vk + 32 * i, (int)kes_depth, (u64)kes_t[i], b,
                           blen, kes_sig + kes_siglen * i, kes_siglen)) {
            if (fail_kind) *fail_kind = 2;
            return i;
        }
        u8 beta[64];
        const u8* pi = vrf_proof + vrf_proof_len * i;
        int vrf_ok = (vrf_proof_len == 128)
            ? oc_ecvrf_verify_bc(vrf_vk + 32 * i, pi, vrf_alpha + 32 * i, 32,
                                 beta)
            : oc_ecvrf_verify(vrf_vk + 32 * i, pi, vrf_alpha + 32 * i, 32,
                              beta);
        if (!vrf_ok || memcmp(beta, vrf_output + 64 * i, 64) != 0) {
            if (fail_kind) *fail_kind = 3;
            return i;
        }
        if (leader_value) {
            u8 lin[65];
            lin[0] = 'L';
            memcpy(lin + 1, beta, 64);
            blake2b(lin, 65, leader_value + 32 * i, 32);
        }
        if (eta_out) {
            u8 nin[65], eta1[32];
            nin[0] = 'N';
            memcpy(nin + 1, beta, 64);
            blake2b(nin, 65, eta1, 32);
            blake2b(eta1, 32, eta_out + 32 * i, 32);
        }
    }
    return -1;
}

// TPraos (Shelley..Alonzo): two 80-byte draft-03 proofs a header under
// the one VRF key, each with its own input and declared output; the nonce
// contribution is blake2b-256(beta_eta) and the leader value the raw
// beta_L, which the caller already holds (it is compared here). fail_kind
// in {1:ocert, 2:kes, 3:nonce proof, 4:leader proof}.
extern "C" long oc_validate_tpraos(
    long n, const u8* cold_vk, const u8* ocert_sig, const u8* ocert_msg,
    const u8* kes_vk, const long* kes_t, const u8* kes_sig, long kes_depth,
    const u8* body, const long* body_off, const u8* vrf_vk,
    const u8* eta_proof, const u8* eta_alpha, const u8* eta_output,
    const u8* l_proof, const u8* l_alpha, const u8* l_output,
    u8* eta_out,              // out: n*32 blake2b-256(beta_eta), or NULL
    long* fail_kind) {
    size_t kes_siglen = 96 + 32 * (size_t)kes_depth;
    if (fail_kind) *fail_kind = 0;
    for (long i = 0; i < n; i++) {
        if (!oc_ed25519_verify(cold_vk + 32 * i, ocert_sig + 64 * i,
                               ocert_msg + 48 * i, 48)) {
            if (fail_kind) *fail_kind = 1;
            return i;
        }
        const u8* b = body + body_off[i];
        size_t blen = (size_t)(body_off[i + 1] - body_off[i]);
        if (!oc_kes_verify(kes_vk + 32 * i, (int)kes_depth, (u64)kes_t[i], b,
                           blen, kes_sig + kes_siglen * i, kes_siglen)) {
            if (fail_kind) *fail_kind = 2;
            return i;
        }
        u8 beta[64];
        if (!oc_ecvrf_verify(vrf_vk + 32 * i, eta_proof + 80 * i,
                             eta_alpha + 32 * i, 32, beta) ||
            memcmp(beta, eta_output + 64 * i, 64) != 0) {
            if (fail_kind) *fail_kind = 3;
            return i;
        }
        if (eta_out) blake2b(beta, 64, eta_out + 32 * i, 32);
        if (!oc_ecvrf_verify(vrf_vk + 32 * i, l_proof + 80 * i,
                             l_alpha + 32 * i, 32, beta) ||
            memcmp(beta, l_output + 64 * i, 64) != 0) {
            if (fail_kind) *fail_kind = 4;
            return i;
        }
    }
    return -1;
}

// legacy ABI: fixed 80-byte draft-03 proofs
extern "C" long oc_validate_praos(
    long n, const u8* cold_vk, const u8* ocert_sig, const u8* ocert_msg,
    const u8* kes_vk, const long* kes_t, const u8* kes_sig, long kes_depth,
    const u8* body, const long* body_off, const u8* vrf_vk,
    const u8* vrf_proof, const u8* vrf_alpha, const u8* vrf_output,
    u8* leader_value, u8* eta_out, long* fail_kind) {
    return oc_validate_praos2(
        n, cold_vk, ocert_sig, ocert_msg, kes_vk, kes_t, kes_sig, kes_depth,
        body, body_off, vrf_vk, vrf_proof, 80, vrf_alpha, vrf_output,
        leader_value, eta_out, fail_kind);
}

// ===========================================================================
// Debug/test exports (differential testing of the internals)
// ===========================================================================

extern "C" void oc_fe_test(const u8 a[32], const u8 b[32], u8 mul_out[32],
                           u8 chain_out[32], u8 inv_out[32], u8 sqrt_out[32],
                           int* sqrt_ok, int* issq) {
    fe fa, fb, fm, t1, t2, t3, fi, fs;
    fe_frombytes(&fa, a);
    fe_frombytes(&fb, b);
    fe_mul(&fm, &fa, &fb);
    fe_tobytes(mul_out, &fm);
    // lazy chain: ((a+b)*(a-b) + a*a) doubled, squared
    fe_add(&t1, &fa, &fb);
    fe_sub(&t2, &fa, &fb);
    fe_mul(&t3, &t1, &t2);
    fe sq;
    fe_sq(&sq, &fa);
    fe_add(&t3, &t3, &sq);
    fe_add(&t3, &t3, &t3);
    fe_sq(&t3, &t3);
    fe_tobytes(chain_out, &t3);
    fe_inv(&fi, &fa);
    fe_tobytes(inv_out, &fi);
    *sqrt_ok = fe_sqrt_even(&fs, &fa);
    fe_tobytes(sqrt_out, &fs);
    *issq = fe_issquare(&fa);
}

extern "C" int oc_ge_test(const u8 enc[32], const u8 s[32], u8 rt_out[32],
                          u8 mul_out[32], u8 dbl_out[32]) {
    ge p, q, d;
    if (!ge_frombytes(&p, enc)) return 0;
    ge_tobytes(rt_out, &p);
    ge_scalarmult(&q, s, &p);
    ge_tobytes(mul_out, &q);
    ge_double(&d, &p);
    ge_tobytes(dbl_out, &d);
    return 1;
}

extern "C" void oc_sc_reduce_test(const u8* in, size_t len, u8 out[32]) {
    sc_reduce(out, in, len);
}

extern "C" int oc_dsmul_test(const u8 a[32], const u8 penc[32], const u8 b[32],
                             const u8 qenc[32], u8 out[32]) {
    ge p, q, r;
    if (!ge_frombytes(&p, penc) || !ge_frombytes(&q, qenc)) return 0;
    ge_double_scalarmult(&r, a, &p, b, &q);
    ge_tobytes(out, &r);
    return 1;
}

// ===========================================================================
// Sign side: Ed25519 sign + ECVRF prove — mirrors ops/host/{ed25519,ecvrf}.py
// (deterministic; byte-identical to the Python reference signers). Used by
// db_synthesizer / fixtures so benchmark chains forge at C speed.
// ===========================================================================

// s_out = (r + c*a) mod L ; all scalars 32-byte LE
static void sc_muladd(u8 s_out[32], const u8 c[32], const u8 a[32],
                      const u8 r[32]) {
    // 512-bit product c*a in 64 LE bytes, + r
    u8 buf[64] = {0};
    uint32_t prod[16] = {0};
    for (int i = 0; i < 8; i++) {
        u64 ci = ((u64)c[4 * i]) | ((u64)c[4 * i + 1] << 8) |
                 ((u64)c[4 * i + 2] << 16) | ((u64)c[4 * i + 3] << 24);
        u64 carry = 0;
        for (int j = 0; j < 8; j++) {
            u64 aj = ((u64)a[4 * j]) | ((u64)a[4 * j + 1] << 8) |
                     ((u64)a[4 * j + 2] << 16) | ((u64)a[4 * j + 3] << 24);
            unsigned __int128 t = (unsigned __int128)ci * aj + prod[i + j] + carry;
            prod[i + j] = (uint32_t)t;
            carry = (u64)(t >> 32);
        }
        int k = i + 8;
        while (carry && k < 16) {
            u64 t = (u64)prod[k] + (carry & 0xFFFFFFFFu);
            prod[k] = (uint32_t)t;
            carry = (carry >> 32) + (t >> 32);
            k++;
        }
    }
    for (int i = 0; i < 16; i++) {
        buf[4 * i] = (u8)prod[i];
        buf[4 * i + 1] = (u8)(prod[i] >> 8);
        buf[4 * i + 2] = (u8)(prod[i] >> 16);
        buf[4 * i + 3] = (u8)(prod[i] >> 24);
    }
    // + r with carry
    uint32_t carry2 = 0;
    for (int i = 0; i < 32; i++) {
        uint32_t t = (uint32_t)buf[i] + r[i] + carry2;
        buf[i] = (u8)t;
        carry2 = t >> 8;
    }
    for (int i = 32; i < 64 && carry2; i++) {
        uint32_t t = (uint32_t)buf[i] + carry2;
        buf[i] = (u8)t;
        carry2 = t >> 8;
    }
    sc_reduce(s_out, buf, 64);
}

static void clamp_scalar(u8 a[32]) {
    a[0] &= 248;
    a[31] &= 127;
    a[31] |= 64;
}

extern "C" void oc_ed25519_public(const u8 seed[32], u8 pk[32]) {
    init_consts();
    u8 h[64];
    sha512(seed, 32, h);
    clamp_scalar(h);
    ge A;
    ge_scalarmult(&A, h, &GE_B);
    ge_tobytes(pk, &A);
}

extern "C" void oc_ed25519_sign(const u8 seed[32], const u8* msg, size_t len,
                                u8 sig[64]) {
    init_consts();
    u8 h[64];
    sha512(seed, 32, h);
    u8 a[32];
    memcpy(a, h, 32);
    clamp_scalar(a);
    ge A;
    ge_scalarmult(&A, a, &GE_B);
    u8 aenc[32];
    ge_tobytes(aenc, &A);
    // r = SHA512(prefix || msg) mod L
    Sha512 hr;
    hr.init();
    hr.update(h + 32, 32);
    hr.update(msg, len);
    u8 rd[64];
    hr.final(rd);
    u8 r[32];
    sc_reduce(r, rd, 64);
    ge R;
    ge_scalarmult(&R, r, &GE_B);
    ge_tobytes(sig, &R);
    // k = SHA512(R || A || msg) mod L ; s = (r + k*a) mod L
    Sha512 hk;
    hk.init();
    hk.update(sig, 32);
    hk.update(aenc, 32);
    hk.update(msg, len);
    u8 kd[64];
    hk.final(kd);
    u8 k[32];
    sc_reduce(k, kd, 64);
    sc_muladd(sig + 32, k, a, r);
}

extern "C" void oc_ecvrf_prove(const u8 seed[32], const u8* alpha, size_t alen,
                               u8 pi[80]) {
    init_consts();
    u8 h[64];
    sha512(seed, 32, h);
    u8 x[32];
    memcpy(x, h, 32);
    clamp_scalar(x);
    ge A;
    ge_scalarmult(&A, x, &GE_B);
    u8 pk[32];
    ge_tobytes(pk, &A);
    ge H;
    vrf_hash_to_curve(&H, pk, alpha, alen);
    u8 henc[32];
    ge_tobytes(henc, &H);
    ge Gamma;
    ge_scalarmult(&Gamma, x, &H);
    // nonce k = SHA512(prefix || H_enc) mod L (draft-03 5.4.2.2)
    Sha512 hn;
    hn.init();
    hn.update(h + 32, 32);
    hn.update(henc, 32);
    u8 nd[64];
    hn.final(nd);
    u8 k[32];
    sc_reduce(k, nd, 64);
    ge U, V;
    ge_scalarmult(&U, k, &GE_B);
    ge_scalarmult(&V, k, &H);
    u8 genc[32], uenc[32], venc[32];
    ge_tobytes(genc, &Gamma);
    ge_tobytes(uenc, &U);
    ge_tobytes(venc, &V);
    Sha512 ch;
    ch.init();
    u8 pre[2] = {VRF_SUITE, 0x02};
    ch.update(pre, 2);
    ch.update(henc, 32);
    ch.update(genc, 32);
    ch.update(uenc, 32);
    ch.update(venc, 32);
    u8 cd[64];
    ch.final(cd);
    u8 c32[32] = {0};
    memcpy(c32, cd, 16);
    memcpy(pi, genc, 32);
    memcpy(pi + 32, cd, 16);
    sc_muladd(pi + 48, c32, x, k);
}

// batch-compatible prove: pi = Gamma || U || V || s (128 bytes); same
// transcript as oc_ecvrf_prove, announced points instead of the challenge
extern "C" void oc_ecvrf_prove_bc(const u8 seed[32], const u8* alpha,
                                  size_t alen, u8 pi[128]) {
    init_consts();
    u8 h[64];
    sha512(seed, 32, h);
    u8 x[32];
    memcpy(x, h, 32);
    clamp_scalar(x);
    ge A;
    ge_scalarmult(&A, x, &GE_B);
    u8 pk[32];
    ge_tobytes(pk, &A);
    ge H;
    vrf_hash_to_curve(&H, pk, alpha, alen);
    u8 henc[32];
    ge_tobytes(henc, &H);
    ge Gamma;
    ge_scalarmult(&Gamma, x, &H);
    Sha512 hn;
    hn.init();
    hn.update(h + 32, 32);
    hn.update(henc, 32);
    u8 nd[64];
    hn.final(nd);
    u8 k[32];
    sc_reduce(k, nd, 64);
    ge U, V;
    ge_scalarmult(&U, k, &GE_B);
    ge_scalarmult(&V, k, &H);
    u8 genc[32], uenc[32], venc[32];
    ge_tobytes(genc, &Gamma);
    ge_tobytes(uenc, &U);
    ge_tobytes(venc, &V);
    Sha512 ch;
    ch.init();
    u8 pre[2] = {VRF_SUITE, 0x02};
    ch.update(pre, 2);
    ch.update(henc, 32);
    ch.update(genc, 32);
    ch.update(uenc, 32);
    ch.update(venc, 32);
    u8 cd[64];
    ch.final(cd);
    u8 c32[32] = {0};
    memcpy(c32, cd, 16);
    memcpy(pi, genc, 32);
    memcpy(pi + 32, uenc, 32);
    memcpy(pi + 64, venc, 32);
    sc_muladd(pi + 96, c32, x, k);
}

extern "C" int oc_ecvrf_proof_to_hash(const u8 pi[80], u8 beta[64]) {
    init_consts();
    ge Gamma;
    if (!ge_frombytes(&Gamma, pi)) return 0;
    ge G8;
    ge_double(&G8, &Gamma);
    ge_double(&G8, &G8);
    ge_double(&G8, &G8);
    u8 g8enc[32];
    ge_tobytes(g8enc, &G8);
    Sha512 bh;
    bh.init();
    u8 pre3[2] = {VRF_SUITE, 0x03};
    bh.update(pre3, 2);
    bh.update(g8enc, 32);
    bh.final(beta);
    return 1;
}

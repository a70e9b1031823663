/* Native host-side batch preparation for the TPU ed25519 verifier.
 *
 * The device graph (tmtpu/tpu/verify.py, kernel.py) consumes per-lane
 *   h = SHA-512(R || A || msg) mod L        (32 bytes, little-endian)
 * plus the canonical-s check s < L. Computing h in a Python loop over
 * hashlib costs more than the entire device budget at 10k-lane batches
 * (VERDICT r1 weak #3), so this C library does the whole sweep in one
 * call: batched SHA-512, Barrett mod-L, the s < L compare, and the
 * transposition of the four 32-byte fields of every lane into the byte
 * planes of the flush's one operand (tmtpu_prep_ed25519). Semantics
 * mirror the spec oracle tmtpu/crypto/ed25519_ref.py (h mod L) and Go's
 * scMinimal (s < L); reference behavior:
 * crypto/ed25519/ed25519.go:148-155.
 *
 * C99 + POSIX threads, nothing to link against: the system libcrypto is
 * looked up at run time (its SHA-512, its ed25519 verify) and everything
 * has a path without it. Built by tmtpu/native/__init__.py (cc -O2
 * -shared); loaded via ctypes with a numpy/hashlib fallback when no
 * toolchain is available.
 */

#include <stdint.h>
#include <stddef.h>
#include <string.h>
#include <pthread.h>
#include <dlfcn.h>

/* ------------------------------------------------------------------ */
/* SHA-512 (FIPS 180-4).                                               */

static const uint64_t K[80] = {
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

#define ROTR(x, n) (((x) >> (n)) | ((x) << (64 - (n))))

typedef struct {
    uint64_t h[8];
    uint8_t buf[128];
    size_t buflen;   /* bytes currently in buf */
    uint64_t total;  /* total message bytes so far */
} sha512_ctx;

static void sha512_init(sha512_ctx *c) {
    static const uint64_t iv[8] = {
        0x6a09e667f3bcc908ULL, 0xbb67ae8584caa73bULL, 0x3c6ef372fe94f82bULL,
        0xa54ff53a5f1d36f1ULL, 0x510e527fade682d1ULL, 0x9b05688c2b3e6c1fULL,
        0x1f83d9abfb41bd6bULL, 0x5be0cd19137e2179ULL};
    memcpy(c->h, iv, sizeof iv);
    c->buflen = 0;
    c->total = 0;
}

static void sha512_block(sha512_ctx *c, const uint8_t *p) {
    uint64_t w[80];
    for (int i = 0; i < 16; i++) {
        w[i] = ((uint64_t)p[8 * i] << 56) | ((uint64_t)p[8 * i + 1] << 48) |
               ((uint64_t)p[8 * i + 2] << 40) | ((uint64_t)p[8 * i + 3] << 32) |
               ((uint64_t)p[8 * i + 4] << 24) | ((uint64_t)p[8 * i + 5] << 16) |
               ((uint64_t)p[8 * i + 6] << 8) | (uint64_t)p[8 * i + 7];
    }
    for (int i = 16; i < 80; i++) {
        uint64_t s0 = ROTR(w[i - 15], 1) ^ ROTR(w[i - 15], 8) ^ (w[i - 15] >> 7);
        uint64_t s1 = ROTR(w[i - 2], 19) ^ ROTR(w[i - 2], 61) ^ (w[i - 2] >> 6);
        w[i] = w[i - 16] + s0 + w[i - 7] + s1;
    }
    uint64_t a = c->h[0], b = c->h[1], d = c->h[3], e = c->h[4];
    uint64_t f = c->h[5], g = c->h[6], hh = c->h[7], cc = c->h[2];
    for (int i = 0; i < 80; i++) {
        uint64_t S1 = ROTR(e, 14) ^ ROTR(e, 18) ^ ROTR(e, 41);
        uint64_t ch = (e & f) ^ (~e & g);
        uint64_t t1 = hh + S1 + ch + K[i] + w[i];
        uint64_t S0 = ROTR(a, 28) ^ ROTR(a, 34) ^ ROTR(a, 39);
        uint64_t maj = (a & b) ^ (a & cc) ^ (b & cc);
        uint64_t t2 = S0 + maj;
        hh = g; g = f; f = e; e = d + t1;
        d = cc; cc = b; b = a; a = t1 + t2;
    }
    c->h[0] += a; c->h[1] += b; c->h[2] += cc; c->h[3] += d;
    c->h[4] += e; c->h[5] += f; c->h[6] += g; c->h[7] += hh;
}

static void sha512_update(sha512_ctx *c, const uint8_t *p, size_t n) {
    c->total += n;
    if (c->buflen) {
        size_t take = 128 - c->buflen;
        if (take > n) take = n;
        memcpy(c->buf + c->buflen, p, take);
        c->buflen += take;
        p += take;
        n -= take;
        if (c->buflen == 128) {
            sha512_block(c, c->buf);
            c->buflen = 0;
        }
    }
    while (n >= 128) {
        sha512_block(c, p);
        p += 128;
        n -= 128;
    }
    if (n) {
        memcpy(c->buf, p, n);
        c->buflen = n;
    }
}

static void sha512_final(sha512_ctx *c, uint8_t out[64]) {
    uint64_t bits = c->total * 8;
    uint8_t pad = 0x80;
    sha512_update(c, &pad, 1);
    c->total -= 1; /* padding doesn't count (total is frozen below anyway) */
    static const uint8_t zeros[128] = {0};
    size_t padlen = (c->buflen <= 112) ? 112 - c->buflen : 240 - c->buflen;
    sha512_update(c, zeros, padlen);
    uint8_t lenb[16] = {0};
    for (int i = 0; i < 8; i++) lenb[15 - i] = (uint8_t)(bits >> (8 * i));
    sha512_update(c, lenb, 16);
    for (int i = 0; i < 8; i++)
        for (int j = 0; j < 8; j++)
            out[8 * i + j] = (uint8_t)(c->h[i] >> (56 - 8 * j));
}

/* ------------------------------------------------------------------ */
/* Reduction mod L = 2^252 + c, c = 27742317777372353535851937790883648493. */

/* L as four 64-bit little-endian limbs. */
static const uint64_t L_LIMBS[4] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL,
                                    0x0000000000000000ULL, 0x1000000000000000ULL};
/* c = L - 2^252 as two 64-bit limbs. */
static const uint64_t C_LIMBS[2] = {0x5812631a5cf5d3edULL, 0x14def9dea2f79cd6ULL};

typedef unsigned __int128 u128;

/* Barrett reduction of a 512-bit value mod L (b = 2^64, k = 4):
 *   mu = floor(2^512 / L)                        (5 limbs, precomputed)
 *   q  = floor( (x >> 192) * mu / 2^320 )
 *   r  = x - q*L, then at most 2 conditional subtracts (empirically 1).
 * Validated against x % L over random and edge 512-bit inputs. */
static const uint64_t MU[5] = {0xed9ce5a30a2c131bULL, 0x2106215d086329a7ULL,
                               0xffffffffffffffebULL, 0xffffffffffffffffULL,
                               0x000000000000000fULL};

static int geq(const uint64_t *a, const uint64_t *b, int n) {
    for (int i = n - 1; i >= 0; i--) {
        if (a[i] > b[i]) return 1;
        if (a[i] < b[i]) return 0;
    }
    return 1;
}

static void sub_n(uint64_t *a, const uint64_t *b, int n) {
    u128 borrow = 0;
    for (int i = 0; i < n; i++) {
        u128 d = (u128)a[i] - b[i] - (uint64_t)borrow;
        a[i] = (uint64_t)d;
        borrow = (d >> 64) ? 1 : 0;
    }
}

/* out[na+nb] = a[na] * b[nb], schoolbook with u128 accumulation. */
static void mul_nm(const uint64_t *a, int na, const uint64_t *b, int nb,
                   uint64_t *out) {
    for (int i = 0; i < na + nb; i++) out[i] = 0;
    for (int i = 0; i < na; i++) {
        uint64_t carry = 0;
        for (int j = 0; j < nb; j++) {
            u128 t = (u128)a[i] * b[j] + out[i + j] + carry;
            out[i + j] = (uint64_t)t;
            carry = (uint64_t)(t >> 64);
        }
        out[i + nb] += carry;
    }
}

static void mod_l(const uint64_t x[8], uint64_t out[4]) {
    /* t2 = (x >> 192) * mu : 5 x 5 -> 10 limbs; q = t2 >> 320 (5 limbs) */
    uint64_t t2[10], ql[9];
    mul_nm(x + 3, 5, MU, 5, t2);
    /* q*L: 5 x 4 -> 9 limbs */
    mul_nm(t2 + 5, 5, L_LIMBS, 4, ql);
    /* r = x - q*L over 8 limbs (r < 3L < 2^255, so high limbs cancel) */
    uint64_t r[8];
    for (int i = 0; i < 8; i++) r[i] = x[i];
    sub_n(r, ql, 8);
    for (int iter = 0; iter < 3 && geq(r, L_LIMBS, 4); iter++) {
        uint64_t l8[8] = {L_LIMBS[0], L_LIMBS[1], L_LIMBS[2], L_LIMBS[3],
                          0, 0, 0, 0};
        sub_n(r, l8, 8);
    }
    out[0] = r[0]; out[1] = r[1]; out[2] = r[2]; out[3] = r[3];
}

/* ------------------------------------------------------------------ */
/* Batch driver.                                                       */

/* libcrypto's SHA-512 (AVX2 where the CPU has it: about twice the code
 * above), found at run time beside its ed25519 verify further down. */
static int libcrypto_sha_ready(void);
static int libcrypto_sha512(const uint8_t ra[64], const uint8_t *m,
                            size_t mlen, uint8_t digest[64]);

#define PLANE_ROWS 128 /* pk | R | s | h, 32 byte rows each */
#define LANE_TILE 64   /* lanes transposed at a time: one cache line a row */

typedef struct {
    size_t lo, hi;
    const uint8_t *pks, *sigs, *msgs;
    const uint64_t *moff;
    uint8_t *plane;
    size_t stride;
    uint8_t *s_ok;
    int libcrypto;
} job_t;

static void run_range(job_t *j) {
    /* a tile of the plane, filled a lane (a column) at a time while it
     * sits in L1, then copied out a whole cache line a row */
    uint8_t tile[PLANE_ROWS][LANE_TILE];
    for (size_t base = j->lo; base < j->hi; base += LANE_TILE) {
        size_t nb = j->hi - base < LANE_TILE ? j->hi - base : LANE_TILE;
        for (size_t t = 0; t < nb; t++) {
            size_t i = base + t;
            const uint8_t *sig = j->sigs + 64 * i; /* R || s */
            const uint8_t *m = j->msgs + j->moff[i];
            size_t mlen = (size_t)(j->moff[i + 1] - j->moff[i]);
            uint8_t ra[64], digest[64];
            memcpy(ra, sig, 32);
            memcpy(ra + 32, j->pks + 32 * i, 32);
            if (!j->libcrypto || !libcrypto_sha512(ra, m, mlen, digest)) {
                sha512_ctx c;
                sha512_init(&c);
                sha512_update(&c, ra, 64);
                sha512_update(&c, m, mlen);
                sha512_final(&c, digest);
            }
            uint64_t limbs[8], red[4];
            for (int k = 0; k < 8; k++) {
                uint64_t v = 0;
                for (int b = 7; b >= 0; b--) v = (v << 8) | digest[8 * k + b];
                limbs[k] = v;
            }
            mod_l(limbs, red);
            /* s < L (Go scMinimal): lexicographic compare, 32-byte LE */
            uint64_t s4[4];
            for (int k = 0; k < 4; k++) {
                uint64_t v = 0;
                for (int b = 7; b >= 0; b--) v = (v << 8) | sig[32 + 8 * k + b];
                s4[k] = v;
            }
            int ok = !geq(s4, L_LIMBS, 4);
            j->s_ok[i] = (uint8_t)ok;
            for (int b = 0; b < 32; b++) {
                tile[b][t] = ra[32 + b];
                tile[32 + b][t] = ra[b];
                /* the device is promised s < L: a refused lane carries 0 */
                tile[64 + b][t] = ok ? sig[32 + b] : 0;
                tile[96 + b][t] = (uint8_t)(red[b >> 3] >> (8 * (b & 7)));
            }
        }
        for (int r = 0; r < PLANE_ROWS; r++)
            memcpy(j->plane + (size_t)r * j->stride + base, tile[r], nb);
    }
}

static void *worker(void *arg) {
    run_range((job_t *)arg);
    return NULL;
}

/* ------------------------------------------------------------------ */
/* keccak-f[1600] + STROBE-128 + merlin transcript — the sr25519
 * challenge-scalar host prep. The Python merlin (tmtpu/crypto/merlin.py,
 * KAT-verified) costs ~1.3 ms per transcript; at 10k-lane batches that is
 * ~13 s of host work dwarfing the device step, so the verify transcript
 * walk (sr25519.PubKeySr25519.verify_signature) runs here instead.
 * Lane layout assumption: little-endian host (x86-64/aarch64 — the lane
 * bytes at offset 8*(x+5y) are the uint64 lane value LE, so the state can
 * be permuted in place as uint64[25]). */

static const uint64_t KRC[24] = {
    0x0000000000000001ULL, 0x0000000000008082ULL, 0x800000000000808aULL,
    0x8000000080008000ULL, 0x000000000000808bULL, 0x0000000080000001ULL,
    0x8000000080008081ULL, 0x8000000000008009ULL, 0x000000000000008aULL,
    0x0000000000000088ULL, 0x0000000080008009ULL, 0x000000008000000aULL,
    0x000000008000808bULL, 0x800000000000008bULL, 0x8000000000008089ULL,
    0x8000000000008003ULL, 0x8000000000008002ULL, 0x8000000000000080ULL,
    0x000000000000800aULL, 0x800000008000000aULL, 0x8000000080008081ULL,
    0x8000000000008080ULL, 0x0000000080000001ULL, 0x8000000080008008ULL};

#define ROL64(x, n) (((x) << (n)) | ((x) >> (64 - (n))))

static void keccakf(uint64_t st[25]) {
    static const int rotc[24] = {1, 3, 6, 10, 15, 21, 28, 36, 45, 55, 2,
                                 14, 27, 41, 56, 8, 25, 43, 62, 18, 39,
                                 61, 20, 44};
    static const int piln[24] = {10, 7, 11, 17, 18, 3, 5, 16, 8, 21, 24,
                                 4, 15, 23, 19, 13, 12, 2, 20, 14, 22, 9,
                                 6, 1};
    uint64_t bc[5], t;
    for (int round = 0; round < 24; round++) {
        for (int i = 0; i < 5; i++)
            bc[i] = st[i] ^ st[i + 5] ^ st[i + 10] ^ st[i + 15] ^ st[i + 20];
        for (int i = 0; i < 5; i++) {
            t = bc[(i + 4) % 5] ^ ROL64(bc[(i + 1) % 5], 1);
            for (int j = 0; j < 25; j += 5) st[j + i] ^= t;
        }
        t = st[1];
        for (int i = 0; i < 24; i++) {
            int j = piln[i];
            bc[0] = st[j];
            st[j] = ROL64(t, rotc[i]);
            t = bc[0];
        }
        for (int j = 0; j < 25; j += 5) {
            for (int i = 0; i < 5; i++) bc[i] = st[j + i];
            for (int i = 0; i < 5; i++)
                st[j + i] = bc[i] ^ ((~bc[(i + 1) % 5]) & bc[(i + 2) % 5]);
        }
        st[0] ^= KRC[round];
    }
}

#define STROBE_R 166
#define SFLAG_I 1
#define SFLAG_A (1 << 1)
#define SFLAG_C (1 << 2)
#define SFLAG_K (1 << 5)
#define SFLAG_M (1 << 4)

typedef struct {
    union {
        uint8_t b[200];
        uint64_t w[25]; /* LE lanes at 8*(x+5y) — alignment via union */
    } st;
    uint8_t pos, pos_begin, cur_flags;
} strobe_t;

static void strobe_run_f(strobe_t *s) {
    s->st.b[s->pos] ^= s->pos_begin;
    s->st.b[s->pos + 1] ^= 0x04;
    s->st.b[STROBE_R + 1] ^= 0x80;
    keccakf(s->st.w);
    s->pos = 0;
    s->pos_begin = 0;
}

static void strobe_absorb(strobe_t *s, const uint8_t *d, size_t n) {
    for (size_t i = 0; i < n; i++) {
        s->st.b[s->pos++] ^= d[i];
        if (s->pos == STROBE_R) strobe_run_f(s);
    }
}

static void strobe_squeeze(strobe_t *s, uint8_t *out, size_t n) {
    for (size_t i = 0; i < n; i++) {
        out[i] = s->st.b[s->pos];
        s->st.b[s->pos] = 0;
        s->pos++;
        if (s->pos == STROBE_R) strobe_run_f(s);
    }
}

static void strobe_begin_op(strobe_t *s, uint8_t flags) { /* more=false */
    uint8_t hdr[2];
    hdr[0] = s->pos_begin;
    hdr[1] = flags;
    s->pos_begin = s->pos + 1;
    s->cur_flags = flags;
    strobe_absorb(s, hdr, 2);
    if ((flags & (SFLAG_C | SFLAG_K)) && s->pos != 0) strobe_run_f(s);
}

static void strobe_meta_ad(strobe_t *s, const uint8_t *d, size_t n) {
    strobe_begin_op(s, SFLAG_M | SFLAG_A);
    strobe_absorb(s, d, n);
}

static void strobe_ad(strobe_t *s, const uint8_t *d, size_t n) {
    strobe_begin_op(s, SFLAG_A);
    strobe_absorb(s, d, n);
}

static void strobe_prf(strobe_t *s, uint8_t *out, size_t n) {
    strobe_begin_op(s, SFLAG_I | SFLAG_A | SFLAG_C);
    strobe_squeeze(s, out, n);
}

static void strobe_init(strobe_t *s, const uint8_t *label, size_t n) {
    memset(s->st.b, 0, 200);
    const uint8_t hdr[6] = {1, STROBE_R + 2, 1, 0, 1, 96};
    memcpy(s->st.b, hdr, 6);
    memcpy(s->st.b + 6, "STROBEv1.0.2", 12);
    keccakf(s->st.w);
    s->pos = 0;
    s->pos_begin = 0;
    s->cur_flags = 0;
    strobe_meta_ad(s, label, n);
}

/* merlin Transcript.append_message: meta_ad(label || le32(len)); ad(msg) */
static void tr_append(strobe_t *s, const char *label, const uint8_t *msg,
                      size_t mlen) {
    uint8_t meta[64];
    size_t ll = strlen(label);
    if (ll > sizeof(meta) - 4) /* transcript labels are short constants */
        ll = sizeof(meta) - 4;
    memcpy(meta, label, ll);
    meta[ll] = (uint8_t)mlen;
    meta[ll + 1] = (uint8_t)(mlen >> 8);
    meta[ll + 2] = (uint8_t)(mlen >> 16);
    meta[ll + 3] = (uint8_t)(mlen >> 24);
    strobe_meta_ad(s, meta, ll + 4);
    strobe_ad(s, msg, mlen);
}

typedef struct {
    size_t lo, hi;
    const strobe_t *base;
    const uint8_t *pks, *rs, *msgs;
    const uint64_t *moff;
    uint8_t *k_out;
} srjob_t;

static void sr_run_range(srjob_t *j) {
    for (size_t i = j->lo; i < j->hi; i++) {
        strobe_t s = *j->base; /* after SigningContext + empty-ctx append */
        tr_append(&s, "sign-bytes", j->msgs + j->moff[i],
                  (size_t)(j->moff[i + 1] - j->moff[i]));
        tr_append(&s, "proto-name", (const uint8_t *)"Schnorr-sig", 11);
        tr_append(&s, "sign:pk", j->pks + 32 * i, 32);
        tr_append(&s, "sign:R", j->rs + 32 * i, 32);
        /* challenge_bytes("sign:c", 64) */
        uint8_t meta[16] = {'s', 'i', 'g', 'n', ':', 'c', 64, 0, 0, 0};
        strobe_meta_ad(&s, meta, 10);
        uint8_t wide[64];
        strobe_prf(&s, wide, 64);
        uint64_t limbs[8], red[4];
        for (int k = 0; k < 8; k++) {
            uint64_t v = 0;
            for (int b = 7; b >= 0; b--) v = (v << 8) | wide[8 * k + b];
            limbs[k] = v;
        }
        mod_l(limbs, red);
        for (int k = 0; k < 4; k++)
            for (int b = 0; b < 8; b++)
                j->k_out[32 * i + 8 * k + b] = (uint8_t)(red[k] >> (8 * b));
    }
}

static void *sr_worker(void *arg) {
    sr_run_range((srjob_t *)arg);
    return NULL;
}

/* Batched sr25519 (schnorrkel) verify challenges: per lane
 *   t = merlin("SigningContext"); t.append("", ""); t.append("sign-bytes",
 *   msg); t.append("proto-name", "Schnorr-sig"); t.append("sign:pk", pk);
 *   t.append("sign:R", r); k = challenge_bytes("sign:c", 64) mod L.
 * k_out: n*32 bytes little-endian. */
void tmtpu_sr_challenges(size_t n, const uint8_t *pks, const uint8_t *rs,
                         const uint8_t *msgs, const uint64_t *moff,
                         uint8_t *k_out, int nthreads) {
    strobe_t base;
    strobe_init(&base, (const uint8_t *)"Merlin v1.0", 11);
    tr_append(&base, "dom-sep", (const uint8_t *)"SigningContext", 14);
    tr_append(&base, "", (const uint8_t *)"", 0);
    if (nthreads < 1) nthreads = 1;
    if (nthreads > 16) nthreads = 16;
    if ((size_t)nthreads > n) nthreads = n ? (int)n : 1;
    pthread_t tids[16];
    srjob_t jobs[16];
    size_t chunk = (n + nthreads - 1) / nthreads;
    int started = 0;
    for (int t = 0; t < nthreads; t++) {
        size_t lo = (size_t)t * chunk;
        if (lo >= n) break;
        size_t hi = lo + chunk < n ? lo + chunk : n;
        jobs[t] = (srjob_t){lo, hi, &base, pks, rs, msgs, moff, k_out};
        if (t == nthreads - 1 || hi == n) {
            sr_run_range(&jobs[t]);
            break;
        }
        if (pthread_create(&tids[started], NULL, sr_worker, &jobs[t]) != 0) {
            sr_run_range(&jobs[t]); /* EAGAIN etc: run the chunk inline */
            continue;
        }
        started++;
    }
    for (int t = 0; t < started; t++) pthread_join(tids[t], NULL);
}

/* Entry point: one flush's lanes straight into its device operand.
 * pks n*32, sigs n*64 (R || s, read in place), msgs concatenated with
 * moff[n+1] offsets. plane: PLANE_ROWS rows of `stride` bytes, row-major;
 * lane i's pk, R, s and h = SHA-512(R || A || M) mod L go to column i of
 * rows 0-31, 32-63, 64-95, 96-127 (columns n.. are the caller's). s_ok: n
 * bytes, s < L; a lane that fails it gets s = 0 in the plane. Lanes are
 * cut over the threads (<= 16) in whole tiles, so no two write one cache
 * line. portable_sha != 0 keeps libcrypto out (the tests' way to the code
 * a host without it runs). Returns 1 when libcrypto hashed, else 0. */
int tmtpu_prep_ed25519(size_t n, const uint8_t *pks, const uint8_t *sigs,
                       const uint8_t *msgs, const uint64_t *moff,
                       uint8_t *plane, size_t stride, uint8_t *s_ok,
                       int nthreads, int portable_sha) {
    int libcrypto = !portable_sha && libcrypto_sha_ready();
    if (nthreads < 1) nthreads = 1;
    if (nthreads > 16) nthreads = 16;
    pthread_t tids[16];
    job_t jobs[16];
    size_t chunk = (n + nthreads - 1) / nthreads;
    chunk = (chunk + LANE_TILE - 1) / LANE_TILE * LANE_TILE;
    int started = 0;
    for (int t = 0; t < nthreads; t++) {
        size_t lo = (size_t)t * chunk;
        if (lo >= n) break;
        size_t hi = lo + chunk < n ? lo + chunk : n;
        jobs[t] = (job_t){lo, hi, pks, sigs, msgs, moff, plane, stride, s_ok,
                          libcrypto};
        if (t == nthreads - 1 || hi == n) {
            run_range(&jobs[t]); /* run last chunk inline */
            break;
        }
        if (pthread_create(&tids[started], NULL, worker, &jobs[t]) != 0) {
            run_range(&jobs[t]); /* EAGAIN etc: run the chunk inline */
            continue;
        }
        started++;
    }
    for (int t = 0; t < started; t++) pthread_join(tids[t], NULL);
    return libcrypto;
}

/* ---- batched ed25519 verification over the system libcrypto ----------
 *
 * The consensus CPU backend (crypto/batch.py CPUBatchVerifier) verifies
 * one signature per Python call through python-cryptography, paying
 * ~70 us of binding overhead on top of OpenSSL's ~55 us verify. This
 * entry point takes the whole batch in one call and loops in C.
 *
 * libcrypto is resolved at RUNTIME via dlopen (this image ships
 * libcrypto.so.3 but no OpenSSL headers or dev symlink, so neither
 * compile-time includes nor -lcrypto are available). If libcrypto or a
 * needed symbol is missing, the entry point returns -1 and the caller
 * keeps the pure-Python path. Reference semantics:
 * crypto/ed25519/ed25519.go:70 Verify (RFC 8032 via EVP_DigestVerify).
 */
#define TM_EVP_PKEY_ED25519 1087 /* NID_ED25519 (obj_mac.h) */

typedef void *(*fn_pkey_new_raw_t)(int, void *, const uint8_t *, size_t);
typedef void (*fn_pkey_free_t)(void *);
typedef void *(*fn_ctx_new_t)(void);
typedef void (*fn_ctx_free_t)(void *);
typedef int (*fn_ctx_reset_t)(void *);
typedef int (*fn_dv_init_t)(void *, void **, const void *, void *, void *);
typedef int (*fn_dv_t)(void *, const uint8_t *, size_t,
                       const uint8_t *, size_t);
typedef int (*fn_sha_init_t)(void *);
typedef int (*fn_sha_update_t)(void *, const void *, size_t);
typedef int (*fn_sha_final_t)(uint8_t *, void *);

static struct {
    void *handle;
    fn_pkey_new_raw_t pkey_new_raw;
    fn_pkey_free_t pkey_free;
    fn_ctx_new_t ctx_new;
    fn_ctx_free_t ctx_free;
    fn_ctx_reset_t ctx_reset;
    fn_dv_init_t dv_init;
    fn_dv_t dv;
    int ok;
    fn_sha_init_t sha_init;
    fn_sha_update_t sha_update;
    fn_sha_final_t sha_final;
    int sha_ok;
} evp;
static pthread_once_t evp_once = PTHREAD_ONCE_INIT;

static void evp_resolve(void) {
    const char *names[] = {"libcrypto.so.3", "libcrypto.so.1.1",
                           "libcrypto.so", 0};
    /* RTLD_LOCAL: symbols are only ever dlsym'd off this handle, and a
     * globally-promoted libcrypto could interpose onto other extensions
     * linked against a different OpenSSL major */
    for (int i = 0; names[i] && !evp.handle; i++)
        evp.handle = dlopen(names[i], RTLD_NOW | RTLD_LOCAL);
    if (!evp.handle) return;
    evp.pkey_new_raw =
        (fn_pkey_new_raw_t)dlsym(evp.handle, "EVP_PKEY_new_raw_public_key");
    evp.pkey_free = (fn_pkey_free_t)dlsym(evp.handle, "EVP_PKEY_free");
    evp.ctx_new = (fn_ctx_new_t)dlsym(evp.handle, "EVP_MD_CTX_new");
    evp.ctx_free = (fn_ctx_free_t)dlsym(evp.handle, "EVP_MD_CTX_free");
    evp.ctx_reset = (fn_ctx_reset_t)dlsym(evp.handle, "EVP_MD_CTX_reset");
    evp.dv_init = (fn_dv_init_t)dlsym(evp.handle, "EVP_DigestVerifyInit");
    evp.dv = (fn_dv_t)dlsym(evp.handle, "EVP_DigestVerify");
    evp.ok = evp.pkey_new_raw && evp.pkey_free && evp.ctx_new &&
             evp.ctx_free && evp.ctx_reset && evp.dv_init && evp.dv;
    /* the three calls behind the one-shot SHA512(): since OpenSSL 3.0 the
     * one-shot fetches the algorithm at every call and costs more than
     * the two blocks it then hashes */
    evp.sha_init = (fn_sha_init_t)dlsym(evp.handle, "SHA512_Init");
    evp.sha_update = (fn_sha_update_t)dlsym(evp.handle, "SHA512_Update");
    evp.sha_final = (fn_sha_final_t)dlsym(evp.handle, "SHA512_Final");
    evp.sha_ok = evp.sha_init && evp.sha_update && evp.sha_final;
}

static int libcrypto_sha_ready(void) {
    pthread_once(&evp_once, evp_resolve);
    return evp.sha_ok;
}

static int libcrypto_sha512(const uint8_t ra[64], const uint8_t *m,
                            size_t mlen, uint8_t digest[64]) {
    uint64_t ctx[64]; /* SHA512_CTX (216 bytes in sha.h), opaque here */
    return evp.sha_init(ctx) == 1 && evp.sha_update(ctx, ra, 64) == 1 &&
           evp.sha_update(ctx, m, mlen) == 1 &&
           evp.sha_final(digest, ctx) == 1;
}

/* 1 when tmtpu_prep_ed25519 hashes through libcrypto on this host. */
int tmtpu_sha512_libcrypto(void) { return libcrypto_sha_ready(); }

typedef struct {
    size_t lo, hi;
    const uint8_t *pks, *sigs, *msgs;
    const uint64_t *moff;
    uint8_t *ok_out;
    int failed; /* ctx allocation failed: lanes are UNKNOWN, not invalid */
} vjob_t;

static void verify_range(vjob_t *j) {
    void *ctx = evp.ctx_new();
    if (!ctx) {
        /* distinguish "could not verify" from "verified invalid": a
         * transient allocation failure must push the caller onto the
         * Python fallback, never reject valid signatures wholesale */
        j->failed = 1;
        return;
    }
    for (size_t i = j->lo; i < j->hi; i++) {
        j->ok_out[i] = 0;
        void *pk = evp.pkey_new_raw(TM_EVP_PKEY_ED25519, 0,
                                    j->pks + 32 * i, 32);
        if (!pk) continue; /* malformed key: lane stays invalid */
        if (evp.dv_init(ctx, 0, 0, 0, pk) == 1 &&
            evp.dv(ctx, j->sigs + 64 * i, 64, j->msgs + j->moff[i],
                   (size_t)(j->moff[i + 1] - j->moff[i])) == 1)
            j->ok_out[i] = 1;
        evp.pkey_free(pk);
        evp.ctx_reset(ctx);
    }
    evp.ctx_free(ctx);
}

static void *vworker(void *arg) {
    verify_range((vjob_t *)arg);
    return 0;
}

/* pks n*32; sigs n*64; msgs concatenated with moff[n+1] offsets;
 * ok_out n bytes (1 = valid); nthreads parallelizes over lanes (each
 * worker holds its own EVP_MD_CTX — OpenSSL contexts are not shareable
 * across threads). Returns 0 on success, -1 when libcrypto is
 * unavailable (caller falls back to Python). */
int tmtpu_ed25519_verify_batch(size_t n, const uint8_t *pks,
                               const uint8_t *sigs, const uint8_t *msgs,
                               const uint64_t *moff, uint8_t *ok_out,
                               int nthreads) {
    pthread_once(&evp_once, evp_resolve);
    if (!evp.ok) return -1;
    if (nthreads < 1) nthreads = 1;
    if ((size_t)nthreads > n) nthreads = (int)(n ? n : 1);
    vjob_t jobs[64];
    pthread_t tids[64];
    if (nthreads > 64) nthreads = 64;
    size_t per = (n + nthreads - 1) / nthreads;
    int spawned = 0;
    for (int t = 0; t < nthreads; t++) {
        size_t lo = t * per, hi = lo + per;
        if (lo >= n) break;
        if (hi > n) hi = n;
        jobs[t] = (vjob_t){lo, hi, pks, sigs, msgs, moff, ok_out, 0};
        if (hi < n && /* chunks remain: run this one on a worker */
            pthread_create(&tids[spawned], 0, vworker, &jobs[t]) == 0) {
            spawned++;
            continue;
        }
        verify_range(&jobs[t]); /* final chunk (or spawn failure): inline */
    }
    for (int t = 0; t < spawned; t++)
        pthread_join(tids[t], 0);
    for (int t = 0; t < nthreads; t++)
        if (t * per < n && jobs[t].failed)
            return -1; /* caller falls back to per-item Python verify */
    return 0;
}

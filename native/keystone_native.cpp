/**
 * keystone_tpu native host runtime (counterpart of the reference's
 * src/main/cpp native layer: the reference keeps its host-side hot loops
 * in C++ behind JNI; here the host-side hot loops are data decode and
 * text featurization, exposed to Python over a C ABI for ctypes).
 *
 * Components:
 *  - CIFAR binary record decode (record = 1 label byte + 3 channel
 *    planes; cifar_loader's layout, reference loaders/CifarLoader.scala)
 *  - JVM String.hashCode + MurmurHash3 ordered ngram hashing, the exact
 *    hash family of nodes/nlp/hashing.py, batched over a token stream
 *  - float32 CSV parsing
 *  - the seeded sample of nodes/stats/sampling.py: the first k entries
 *    of numpy's legacy RandomState.permutation(n), without the shuffle
 *
 * Build: make -C native   (g++ -O3 -fPIC -fopenmp -shared), or on
 * first use by keystone_tpu/native/__init__.py. Both stamp the library
 * with a hash of this file (NATIVE_SOURCE_ID), and the loader
 * rebuilds a library whose stamp is not this file's.
 */
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <cstdio>

#ifndef NATIVE_SOURCE_ID
#define NATIVE_SOURCE_ID "unstamped"
#endif

extern "C" {

/* "keystone-native-source:<hash of this file>": which source this
 * library was compiled from. The loader finds the string in the file
 * before it loads it. */
const char* keystone_native_source_id() {
    return "keystone-native-source:" NATIVE_SOURCE_ID;
}

/* ---------------- CIFAR binary decode ---------------- */

/* raw: n records of (1 + rows*cols*chans) bytes, channel-planar.
 * out_images: n*rows*cols*chans float32 (HWC), out_labels: n int32. */
void cifar_decode(const uint8_t* raw, int64_t n, int rows, int cols,
                  int chans, float* out_images, int32_t* out_labels) {
    const int64_t plane = (int64_t)rows * cols;
    const int64_t rec = 1 + plane * chans;
    #pragma omp parallel for
    for (int64_t i = 0; i < n; ++i) {
        const uint8_t* r = raw + i * rec;
        out_labels[i] = (int32_t)r[0];
        const uint8_t* px = r + 1;
        float* out = out_images + i * plane * chans;
        for (int c = 0; c < chans; ++c) {
            for (int64_t p = 0; p < plane; ++p) {
                /* planar (c, row, col) -> interleaved (row, col, c) */
                out[p * chans + c] = (float)px[c * plane + p];
            }
        }
    }
}

/* ---------------- text feature hashing ---------------- */

static inline int32_t rotl32(uint32_t x, int r) {
    return (int32_t)((x << r) | (x >> (32 - r)));
}

static inline uint32_t mmix(uint32_t h, uint32_t k) {
    k *= 0xcc9e2d51u;
    k = (uint32_t)rotl32(k, 15);
    k *= 0x1b873593u;
    h ^= k;
    h = (uint32_t)rotl32(h, 13);
    return h * 5u + 0xe6546b64u;
}

static inline int32_t mfinal(uint32_t h, uint32_t len) {
    h ^= len;
    h ^= h >> 16; h *= 0x85ebca6bu;
    h ^= h >> 13; h *= 0xc2b2ae35u;
    h ^= h >> 16;
    return (int32_t)h;
}

/* JVM String.hashCode over UTF-16 code units of a UTF-8 input string. */
int32_t java_string_hash(const char* s, int64_t len) {
    uint32_t h = 0;  /* unsigned: wraparound is defined (JVM semantics) */
    int64_t i = 0;
    while (i < len) {
        uint32_t cp;
        uint8_t b = (uint8_t)s[i];
        if (b < 0x80) { cp = b; i += 1; }
        else if ((b >> 5) == 0x6) {
            cp = ((b & 0x1Fu) << 6) | ((uint8_t)s[i+1] & 0x3Fu); i += 2;
        } else if ((b >> 4) == 0xE) {
            cp = ((b & 0x0Fu) << 12) | (((uint8_t)s[i+1] & 0x3Fu) << 6)
                 | ((uint8_t)s[i+2] & 0x3Fu); i += 3;
        } else {
            cp = ((b & 0x07u) << 18) | (((uint8_t)s[i+1] & 0x3Fu) << 12)
                 | (((uint8_t)s[i+2] & 0x3Fu) << 6)
                 | ((uint8_t)s[i+3] & 0x3Fu); i += 4;
        }
        if (cp >= 0x10000) {  /* surrogate pair: two UTF-16 units */
            uint32_t v = cp - 0x10000;
            h = h * 31u + (0xD800u + (v >> 10));
            h = h * 31u + (0xDC00u + (v & 0x3FFu));
        } else {
            h = h * 31u + cp;
        }
    }
    return (int32_t)h;
}

static inline int32_t nonneg_mod(int32_t x, int32_t mod) {
    int32_t r = x % mod;
    return r < 0 ? r + mod : r;
}

/* Rolling murmur ngram hashing over one tokenized document
 * (the hot loop of NGramsHashingTF, nodes/nlp/hashing.py).
 * token_hashes: per-token JVM hashes; emits (feature index, count=1)
 * pairs into out_features (caller aggregates counts).
 * Returns number of features written (bounded by cap). */
int64_t ngram_hash_doc(const int32_t* token_hashes, int64_t n_tokens,
                       int32_t min_order, int32_t max_order,
                       int32_t num_features, int32_t seq_seed,
                       int32_t* out_features, int64_t cap) {
    int64_t out = 0;
    for (int64_t i = 0; i + min_order <= n_tokens; ++i) {
        uint32_t h = (uint32_t)seq_seed;
        int32_t order = 0;
        for (int64_t j = i; j < i + min_order; ++j) {
            h = mmix(h, (uint32_t)token_hashes[j]);
        }
        order = min_order;
        if (out >= cap) return out;
        out_features[out++] =
            nonneg_mod(mfinal(h, (uint32_t)order), num_features);
        for (order = min_order + 1;
             order <= max_order && i + order <= n_tokens; ++order) {
            h = mmix(h, (uint32_t)token_hashes[i + order - 1]);
            if (out >= cap) return out;
            out_features[out++] =
                nonneg_mod(mfinal(h, (uint32_t)order), num_features);
        }
    }
    return out;
}

/* Batch JVM hashing of a packed UTF-8 token arena:
 * offsets has n+1 entries delimiting each token in `arena`. */
void java_string_hash_batch(const char* arena, const int64_t* offsets,
                            int64_t n, int32_t* out) {
    #pragma omp parallel for
    for (int64_t i = 0; i < n; ++i) {
        out[i] = java_string_hash(arena + offsets[i],
                                  offsets[i + 1] - offsets[i]);
    }
}

/* ---------------- seeded sample ---------------- */

/* The first k entries of numpy.random.RandomState.permutation(n), bit
 * for bit, for a generator in the state (key, pos) that
 * RandomState.get_state() reports. tests/test_sample_indices.py holds
 * it to numpy's own call.
 *
 * The legacy shuffle is  x = arange(n); for i = n-1 .. 1: j_i =
 * random_interval(i); swap x[i], x[j_i].  The j_i do not depend on x:
 * stage 1 draws them all (MT19937 words masked to i's bit length,
 * those above i rejected) into J. Stage 2 undoes the swaps from the
 * last (i = 1) to the first, following only the k output slots: the
 * steps under k permute the slots among the first k positions, and a
 * step i >= k moves a slot from j_i to i when j_i holds one, which a
 * bitmap of n bits answers. Where a slot ends is its value. J's
 * consumed entries keep the slot a position holds.
 *
 * n - 1 must fit 32 bits (above that numpy draws 64-bit words) and
 * 0 <= k <= n. Returns 0, or -1 when the 4 n + n / 8 bytes of scratch
 * cannot be had. */

static inline void mt19937_twist(uint32_t* key) {
    const int N = 624, M = 397;
    const uint32_t A = 0x9908b0dfu, UP = 0x80000000u, LOW = 0x7fffffffu;
    int i = 0;
    uint32_t y;
    for (; i < N - M; ++i) {
        y = (key[i] & UP) | (key[i + 1] & LOW);
        key[i] = key[i + M] ^ (y >> 1) ^ ((0u - (y & 1u)) & A);
    }
    for (; i < N - 1; ++i) {
        y = (key[i] & UP) | (key[i + 1] & LOW);
        key[i] = key[i + (M - N)] ^ (y >> 1) ^ ((0u - (y & 1u)) & A);
    }
    y = (key[N - 1] & UP) | (key[0] & LOW);
    key[N - 1] = key[M - 1] ^ (y >> 1) ^ ((0u - (y & 1u)) & A);
}

static inline void mt19937_temper(const uint32_t* key, uint32_t* words) {
    for (int t = 0; t < 624; ++t) {
        uint32_t y = key[t];
        y ^= y >> 11;
        y ^= (y << 7) & 0x9d2c5680u;
        y ^= (y << 15) & 0xefc60000u;
        y ^= y >> 18;
        words[t] = y;
    }
}

int64_t permutation_head(const uint32_t* mt_key, int32_t mt_pos, int64_t n,
                         int64_t k, int64_t* out) {
    if (k <= 0) return 0;
    uint32_t* J = (uint32_t*)malloc((size_t)n * sizeof(uint32_t));
    uint64_t* held = (uint64_t*)calloc((size_t)(n + 63) / 64, 8);
    if (!J || !held) {
        free(J); free(held);
        return -1;
    }

    /* stage 1: J[i] for i = n-1 .. 1. Every word is written to J[i] and
     * i steps down only when the word is accepted: no branch on the
     * rejection, which is a coin toss just above a power of two. */
    uint32_t key[624], words[624];
    memcpy(key, mt_key, sizeof(key));
    mt19937_temper(key, words);
    int pos = mt_pos;
    int64_t i = n - 1;
    while (i >= 1) {
        const int bits = 64 - __builtin_clzll((unsigned long long)i);
        const uint32_t mask = (uint32_t)((1ull << bits) - 1);
        const int64_t lo = (int64_t)1 << (bits - 1);
        while (i >= lo) {
            if (pos >= 624) {
                mt19937_twist(key);
                mt19937_temper(key, words);
                pos = 0;
            }
            for (; pos < 624 && i >= lo; ++pos) {
                const uint32_t v = words[pos] & mask;
                J[i] = v;
                i -= (int64_t)v <= i;
            }
        }
    }

    /* stage 2, steps 1 .. k-1: the slots move among the first k
     * positions. In place: an entry of J under p already names the slot
     * its position holds, and position p holds slot p until step p. */
    J[0] = 0;
    for (int64_t p = 1; p < k; ++p) {
        const uint32_t j = J[p];
        J[p] = (uint32_t)p;
        const uint32_t s = J[j];
        J[j] = (uint32_t)p;
        J[p] = s;
    }
    for (int64_t p = 0; p < k; ++p) {
        out[J[p]] = p;
        held[p >> 6] |= 1ull << (p & 63);
    }
    /* steps k .. n-1: position i holds no slot before step i */
    for (i = k; i < n; ++i) {
        const uint32_t j = J[i];
        if ((held[j >> 6] >> (j & 63)) & 1ull) {
            const uint32_t s = J[j];
            J[i] = s;
            out[s] = i;
            held[j >> 6] &= ~(1ull << (j & 63));
            held[i >> 6] |= 1ull << (i & 63);
        }
    }
    free(J); free(held);
    return 0;
}

/* ---------------- CSV parsing ---------------- */

/* Parse newline-separated comma-separated floats. Strict about field
 * structure: an empty or non-numeric field returns -1 so the caller
 * falls back to a descriptive parser (consecutive delimiters must not
 * silently shift values across rows). */
int64_t csv_parse_f32(const char* buf, int64_t len, float* out, int64_t cap) {
    int64_t n = 0;
    const char* p = buf;
    const char* end = buf + len;
    while (p < end) {
        while (p < end && (*p == '\n' || *p == '\r')) ++p;  /* blank lines */
        if (p >= end) break;
        for (;;) {
            while (p < end && (*p == ' ' || *p == '\t')) ++p;
            /* strtof treats '\n' as skippable whitespace, which would let an
             * empty trailing field swallow the next row's first value. */
            if (p >= end || *p == '\n' || *p == '\r') return -1;
            char* next = nullptr;
            float v = strtof(p, &next);
            if (next == p || n >= cap) return -1;  /* empty/bad field */
            out[n++] = v;
            p = next;
            while (p < end && (*p == ' ' || *p == '\t')) ++p;
            if (p < end && *p == ',') { ++p; continue; }
            break;
        }
        if (p < end && *p != '\n' && *p != '\r') return -1;
    }
    return n;
}

}  /* extern "C" */

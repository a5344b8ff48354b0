// Host-side native helpers for the TPU scanning framework.
//
// The reference implements its entire runtime in Go (SURVEY.md notes no
// C++/CUDA anywhere in its tree); our equivalent of its tight host loops
// are these kernels, used by the Python orchestration layer through
// ctypes (see trivy_tpu/native/__init__.py):
//
//   - fnv1a64_batch: join-key hashing for package/advisory batches
//     (pkg/detector's per-package bucket lookups become hash-join keys);
//   - lower_pack_batch: lowercasing + fixed-size overlapped chunking of
//     every secret-scan candidate file of a call into one [B, L] uint8
//     tensor for the device shift-or prefilter, with a 64-bit
//     fingerprint of each row for the engine's dedup (the reference
//     lowercases per rule per file, pkg/fanal/secret/scanner.go:170).
//
// Build: g++ -O3 -shared -fPIC -std=c++17 (driven by the Python
// loader; no external dependencies).

#include <cstdint>
#include <cstring>

extern "C" {

// Hash n byte strings (concatenated in `data`, string i spanning
// [offsets[i], offsets[i+1])) with FNV-1a 64-bit into out[n].
void fnv1a64_batch(const uint8_t* data, const int64_t* offsets, int64_t n,
                   uint64_t* out) {
    const uint64_t kOffset = 0xCBF29CE484222325ULL;
    const uint64_t kPrime = 0x100000001B3ULL;
    for (int64_t i = 0; i < n; ++i) {
        uint64_t h = kOffset;
        const uint8_t* p = data + offsets[i];
        const uint8_t* end = data + offsets[i + 1];
        for (; p != end; ++p) {
            h ^= static_cast<uint64_t>(*p);
            h *= kPrime;
        }
        out[i] = h;
    }
}

// Lowercase and chunk n_files files (file i is data[i], lens[i] bytes,
// rows[i] rows) into `out`, row after row: row r of a file holds its
// bytes [r * stride, r * stride + chunk_len) with stride chunk_len -
// overlap (clamped to 1), zero past the file's end. The caller sizes
// `out` (and fp) for the sum of rows. When fp is not null, fp[row] is
// the row's fingerprint: the sum over its little-endian 64-bit words
// w_j of mix((w_j + j * G) * C), mix(x) = x ^ (x >> 31), modulo 2^64
// (chunk_len a multiple of 8). Equal rows have equal fingerprints; the
// caller compares the bytes of rows that share one.
void lower_pack_batch(const uint8_t* const* data, const int64_t* lens,
                      const int64_t* rows, int64_t n_files,
                      int32_t chunk_len, int32_t overlap, uint8_t* out,
                      uint64_t* fp) {
    const int64_t L = chunk_len;
    int64_t stride = L - overlap;
    if (stride < 1) stride = 1;
    const uint64_t kG = 0x9E3779B97F4A7C15ULL;
    const uint64_t kC = 0xBF58476D1CE4E5B9ULL;
    int64_t row = 0;
    for (int64_t f = 0; f < n_files; ++f) {
        for (int64_t r = 0; r < rows[f]; ++r, ++row) {
            const int64_t off = r * stride;
            int64_t piece = lens[f] - off;
            if (piece > L) piece = L;
            if (piece < 0) piece = 0;
            const uint8_t* src = data[f] + off;
            uint8_t* dst = out + row * L;
            for (int64_t j = 0; j < piece; ++j) {
                uint8_t c = src[j];
                dst[j] = (c >= 'A' && c <= 'Z') ? c + 32 : c;
            }
            if (piece < L) memset(dst + piece, 0, L - piece);
            if (fp != nullptr) {
                // the row is still in cache: one more pass over it
                uint64_t h = 0, k = 0;
                for (int64_t j = 0; j < L; j += 8, k += kG) {
                    uint64_t w;
                    memcpy(&w, dst + j, 8);
                    uint64_t x = (w + k) * kC;
                    h += x ^ (x >> 31);
                }
                fp[row] = h;
            }
        }
    }
}

// Case-insensitive memmem over a haystack for the host prefilter
// fallback: returns 1 if needle (already lowercase) occurs in haystack
// (lowercased on the fly).
int32_t contains_lower(const uint8_t* hay, int64_t hay_len,
                       const uint8_t* needle, int64_t needle_len) {
    if (needle_len == 0) return 1;
    if (needle_len > hay_len) return 0;
    uint8_t first = needle[0];
    for (int64_t i = 0; i + needle_len <= hay_len; ++i) {
        uint8_t c = hay[i];
        if (c >= 'A' && c <= 'Z') c += 32;
        if (c != first) continue;
        int64_t j = 1;
        for (; j < needle_len; ++j) {
            uint8_t h = hay[i + j];
            if (h >= 'A' && h <= 'Z') h += 32;
            if (h != needle[j]) break;
        }
        if (j == needle_len) return 1;
    }
    return 0;
}

}  // extern "C"

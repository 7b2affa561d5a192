// Native chunk scanner / columnar header extractor.
//
// The host-side data loader of the framework: parses ImmutableDB chunk
// files (concatenated CBOR blocks, layout defined by
// ouroboros_consensus_tpu/block/praos_block.py) directly into the
// struct-of-arrays columns the device staging layer consumes, without
// materializing Python objects. This is the C++ runtime component the
// reference keeps in external C packages (CBOR decode via cborg,
// libsodium hashing) — CBOR decode throughput is the host bottleneck at
// batch rates (SURVEY.md §7.3 items 5-6).
//
// Block layout (praos_block.py):
//   block  = [header, [tx, ...]]
//   header = [body, kes_sig]
//   body   = [block_no, slot, prev_hash|null, issuer_vk, vrf_vk,
//             [vrf_output, vrf_proof], body_size, body_hash,
//             [ocert_vk, counter, kes_period, sigma], [pv_maj, pv_min]]
//
// The KES-signed message is the body's exact CBOR span, which we return
// as (offset, len) into the chunk buffer — zero copies.
//
// Build: g++ -O2 -shared -fPIC -o libheaderscan.so headerscan.cpp

#include <cstdint>
#include <cstring>
#include <cstddef>

namespace {

struct Cursor {
    const uint8_t* p;
    size_t len;
    size_t off;
    bool ok;

    bool need(size_t n) {
        if (off + n > len) { ok = false; return false; }
        return true;
    }
    uint8_t peek() { return p[off]; }
    uint8_t take() { return p[off++]; }
};

// Read a CBOR head; returns major in *major and argument in *arg.
bool read_head(Cursor& c, int* major, uint64_t* arg) {
    if (!c.need(1)) return false;
    uint8_t b = c.take();
    *major = b >> 5;
    uint8_t info = b & 0x1f;
    if (info < 24) { *arg = info; return true; }
    int n;
    switch (info) {
        case 24: n = 1; break;
        case 25: n = 2; break;
        case 26: n = 4; break;
        case 27: n = 8; break;
        default: c.ok = false; return false;  // indefinite not emitted
    }
    if (!c.need((size_t)n)) return false;
    uint64_t v = 0;
    for (int i = 0; i < n; i++) v = (v << 8) | c.take();
    *arg = v;
    return true;
}

// Skip one complete CBOR item.
bool skip_item(Cursor& c) {
    int major; uint64_t arg;
    if (!read_head(c, &major, &arg)) return false;
    switch (major) {
        case 0: case 1: return true;                    // ints
        case 2: case 3:                                  // bytes/text
            if (!c.need(arg)) return false;
            c.off += arg; return true;
        case 4:                                          // array
            for (uint64_t i = 0; i < arg; i++)
                if (!skip_item(c)) return false;
            return true;
        case 5:                                          // map
            for (uint64_t i = 0; i < 2 * arg; i++)
                if (!skip_item(c)) return false;
            return true;
        case 6: return skip_item(c);                     // tag
        case 7:                                          // simple/float
            return true;       // read_head already consumed the payload
        default: return false;
    }
}

bool expect_array(Cursor& c, uint64_t* n) {
    int major; uint64_t arg;
    if (!read_head(c, &major, &arg) || major != 4) { c.ok = false; return false; }
    *n = arg;
    return true;
}

bool read_uint(Cursor& c, int64_t* out) {
    int major; uint64_t arg;
    if (!read_head(c, &major, &arg) || major != 0) { c.ok = false; return false; }
    *out = (int64_t)arg;
    return true;
}

// bytes of exactly `want` length copied to dst; or null (-> zero, *present=0)
bool read_bytes_fixed(Cursor& c, uint8_t* dst, size_t want, uint8_t* present) {
    int major; uint64_t arg;
    size_t save = c.off;
    if (!read_head(c, &major, &arg)) return false;
    if (major == 7 && arg == 22) {  // null
        memset(dst, 0, want);
        if (present) *present = 0;
        return true;
    }
    if (major != 2 || arg != want) { c.off = save; c.ok = false; return false; }
    if (!c.need(arg)) return false;
    memcpy(dst, c.p + c.off, want);
    c.off += arg;
    if (present) *present = 1;
    return true;
}

// bytes of one of two allowed lengths (the 80-byte draft-03 vs 128-byte
// batch-compatible VRF proof), copied into a want_max-wide zero-padded
// row; actual length recorded in *len_out
bool read_bytes_either(Cursor& c, uint8_t* dst, size_t want_a,
                       size_t want_b, size_t want_max, int64_t* len_out) {
    int major; uint64_t arg;
    size_t save = c.off;
    if (!read_head(c, &major, &arg)) return false;
    if (major != 2 || (arg != want_a && arg != want_b)) {
        c.off = save; c.ok = false; return false;
    }
    if (!c.need(arg)) return false;
    memset(dst, 0, want_max);
    memcpy(dst, c.p + c.off, arg);
    c.off += arg;
    *len_out = (int64_t)arg;
    return true;
}

// variable-length bytes: record (offset, len), no copy
bool read_bytes_span(Cursor& c, int64_t* off_out, int64_t* len_out) {
    int major; uint64_t arg;
    if (!read_head(c, &major, &arg) || major != 2) { c.ok = false; return false; }
    if (!c.need(arg)) return false;
    *off_out = (int64_t)c.off;
    *len_out = (int64_t)arg;
    c.off += arg;
    return true;
}

}  // namespace

extern "C" {

// Scan concatenated top-level CBOR items; fill offsets/sizes.
// Returns the count of COMPLETE items (stopping at max_items). A torn
// or malformed tail ends the scan: *bad_off is the offset where the
// good prefix ends (== len iff the whole buffer is well-formed) — the
// truncate-corrupted-tail recovery point (ImmutableDB/Impl/Validation).
int ocx_scan_items(const uint8_t* buf, size_t len,
                   int64_t* offsets, int64_t* sizes, int max_items,
                   int64_t* bad_off) {
    Cursor c{buf, len, 0, true};
    int n = 0;
    while (c.off < c.len && n < max_items) {
        size_t start = c.off;
        if (!skip_item(c) || !c.ok) {
            if (bad_off) *bad_off = (int64_t)start;
            return n;
        }
        offsets[n] = (int64_t)start;
        sizes[n] = (int64_t)(c.off - start);
        n++;
    }
    if (bad_off) *bad_off = (int64_t)c.off;
    return n;
}

// Extract header columns from n blocks located at offsets[] in buf.
// Fixed-width outputs are caller-allocated numpy arrays; variable-width
// fields (kes_sig, signed body span) come back as (offset, len) pairs
// into buf. Returns 0 on success, or 1-based index of first bad block.
int ocx_extract_headers(
    const uint8_t* buf, size_t len,
    const int64_t* offsets, int n,
    int64_t* block_no, int64_t* slot,
    uint8_t* prev_hash /* n*32 */, uint8_t* has_prev,
    uint8_t* issuer_vk /* n*32 */, uint8_t* vrf_vk /* n*32 */,
    uint8_t* vrf_output /* n*64 */,
    uint8_t* vrf_proof /* n*128, zero-padded */,
    int64_t* vrf_proof_len /* n: 80 (draft-03) or 128 (batch-compat) */,
    int64_t* body_size, uint8_t* body_hash /* n*32 */,
    uint8_t* ocert_vk /* n*32 */, int64_t* ocert_counter,
    int64_t* ocert_kes_period, int64_t* ocert_sigma_off,
    int64_t* ocert_sigma_len, int64_t* pv_major, int64_t* pv_minor,
    int64_t* kes_sig_off, int64_t* kes_sig_len,
    int64_t* signed_off, int64_t* signed_len,
    uint8_t* vrf_two /* n: 1 = a TPraos body, two VRF certificates */,
    uint8_t* vrf_leader_output /* n*64 */,
    uint8_t* vrf_leader_proof /* n*80 */) {
    for (int i = 0; i < n; i++) {
        Cursor c{buf, len, (size_t)offsets[i], true};
        uint64_t na;
        // block = [header, txs]
        if (!expect_array(c, &na) || na != 2) return i + 1;
        // header = [body, kes_sig]
        if (!expect_array(c, &na) || na != 2) return i + 1;
        size_t body_start = c.off;
        // body = [...10 fields...]; a TPraos (Shelley-era) body has 11:
        // the nonce certificate where Praos has its one, then the
        // leader certificate (both 64-byte output + 80-byte proof)
        if (!expect_array(c, &na) || (na != 10 && na != 11)) return i + 1;
        bool two = na == 11;
        vrf_two[i] = two ? 1 : 0;
        if (!read_uint(c, &block_no[i])) return i + 1;
        if (!read_uint(c, &slot[i])) return i + 1;
        if (!read_bytes_fixed(c, prev_hash + 32 * i, 32, &has_prev[i])) return i + 1;
        if (!read_bytes_fixed(c, issuer_vk + 32 * i, 32, nullptr)) return i + 1;
        if (!read_bytes_fixed(c, vrf_vk + 32 * i, 32, nullptr)) return i + 1;
        if (!expect_array(c, &na) || na != 2) return i + 1;
        if (!read_bytes_fixed(c, vrf_output + 64 * i, 64, nullptr)) return i + 1;
        if (!read_bytes_either(c, vrf_proof + 128 * i, 80, 128, 128,
                               &vrf_proof_len[i])) return i + 1;
        if (two) {
            if (vrf_proof_len[i] != 80) return i + 1;
            if (!expect_array(c, &na) || na != 2) return i + 1;
            if (!read_bytes_fixed(c, vrf_leader_output + 64 * i, 64, nullptr))
                return i + 1;
            if (!read_bytes_fixed(c, vrf_leader_proof + 80 * i, 80, nullptr))
                return i + 1;
        }
        if (!read_uint(c, &body_size[i])) return i + 1;
        if (!read_bytes_fixed(c, body_hash + 32 * i, 32, nullptr)) return i + 1;
        if (!expect_array(c, &na) || na != 4) return i + 1;
        if (!read_bytes_fixed(c, ocert_vk + 32 * i, 32, nullptr)) return i + 1;
        if (!read_uint(c, &ocert_counter[i])) return i + 1;
        if (!read_uint(c, &ocert_kes_period[i])) return i + 1;
        if (!read_bytes_span(c, &ocert_sigma_off[i], &ocert_sigma_len[i])) return i + 1;
        if (!expect_array(c, &na) || na != 2) return i + 1;
        if (!read_uint(c, &pv_major[i])) return i + 1;
        if (!read_uint(c, &pv_minor[i])) return i + 1;
        signed_off[i] = (int64_t)body_start;
        signed_len[i] = (int64_t)(c.off - body_start);
        if (!read_bytes_span(c, &kes_sig_off[i], &kes_sig_len[i])) return i + 1;
        // structurally walk the txs item too: the batched integrity
        // check hashes the txs SPAN without decoding it, so a block
        // whose declared body hash covers garbled (non-CBOR) txs bytes
        // must still be rejected here, matching the per-block decode
        // path (Block.from_bytes raises). skip_item is O(#cbor items).
        if (!skip_item(c) || !c.ok) return i + 1;
    }
    return 0;
}

// Batched CRC-32 (ISO-HDLC, the zlib.crc32 polynomial) over n spans of
// buf. Returns the 0-based index of the first span whose CRC differs
// from expected[], or -1 when all match. This is the ImmutableDB deep
// validation hot loop (validate_all at open): per-span Python
// zlib.crc32 calls cost ~25 us of interpreter overhead each, ~2.5 s on
// a 100k-block chain — one native walk is ~50 ms.
static uint32_t crc_table[256];
static bool crc_init_done = [] {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? 0xEDB88320u ^ (c >> 1) : c >> 1;
        crc_table[i] = c;
    }
    return true;
}();

int64_t ocx_crc32_first_bad(const uint8_t* buf, size_t len,
                            const int64_t* offsets, const int64_t* sizes,
                            const int64_t* expected, int64_t n) {
    for (int64_t i = 0; i < n; i++) {
        int64_t off = offsets[i], sz = sizes[i];
        // unsigned bounds math: off + sz as int64 is UB for huge values
        // from a corrupt index; each side-checked add is overflow-free
        if (off < 0 || sz < 0 || (uint64_t)off > len ||
            (uint64_t)sz > len - (uint64_t)off)
            return i;
        uint32_t c = 0xFFFFFFFFu;
        const uint8_t* p = buf + off;
        for (int64_t j = 0; j < sz; j++)
            c = crc_table[(c ^ p[j]) & 0xFF] ^ (c >> 8);
        if ((c ^ 0xFFFFFFFFu) != (uint32_t)expected[i]) return i;
    }
    return -1;
}

// Parse a concatenated-CBOR ImmutableDB index: entries are 6-element
// arrays [slot, block_no, hash(32B), offset, size, crc32]. Stops at the
// first malformed/torn entry (crash mid-append just ends the list —
// same contract as the Python loop). Returns the entry count. Python
// index loads cost ~9 us/entry of interpreter + decode overhead — 9 s
// on the 1M-header bench chain's open; this walk is ~20 ms.
int64_t ocx_parse_index(const uint8_t* buf, size_t len, int64_t max_items,
                        int64_t* slot, int64_t* block_no,
                        uint8_t* hash /* n*32 */, int64_t* offset,
                        int64_t* size, int64_t* crc32) {
    Cursor c{buf, len, 0, true};
    int64_t n = 0;
    while (c.off < c.len && n < max_items) {
        uint64_t na;
        Cursor save = c;
        // strict 32-byte hash read: read_bytes_fixed's null-acceptance
        // is a header-parsing (absent prev_hash) concession — an index
        // hash must be exactly bytes(32), like the Python loop's
        // IndexEntry.from_cbor_obj
        int hmaj; uint64_t harg;
        bool ok =
            expect_array(c, &na) && na == 6 &&
            read_uint(c, &slot[n]) && read_uint(c, &block_no[n]) &&
            read_head(c, &hmaj, &harg) && hmaj == 2 && harg == 32 &&
            c.need(32);
        if (ok) {
            memcpy(hash + 32 * n, c.p + c.off, 32);
            c.off += 32;
            ok = read_uint(c, &offset[n]) && read_uint(c, &size[n]) &&
                 read_uint(c, &crc32[n]) && c.ok;
        }
        if (!ok) {
            c = save;
            break;
        }
        n++;
    }
    return n;
}

}  // extern "C"

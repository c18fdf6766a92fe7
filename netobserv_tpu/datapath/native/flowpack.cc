// flowpack — native host-side hot path for the capture plane.
//
// Converts raw flow-event buffers (as drained from kernel maps / ring buffers)
// into the columnar tensors the TPU analytics plane consumes, and merges
// per-CPU feature-map partials. This is the native replacement for the
// reference's per-record decode loop (pkg/model/record.go:227, its hottest
// allocation site) — done as flat array passes instead.
//
// Layout contract: struct definitions come from ../bpf/records.h, the same
// header the eBPF datapath compiles; tests/test_layout_parity.py pins both
// sides against the numpy dtypes.
//
// C ABI only (consumed via ctypes). All output buffers are caller-allocated.

#include <algorithm>
#include <cstdint>
#include <cstdlib>
#include <cstring>

#if defined(__linux__)
#include <errno.h>
#include <pthread.h>
#include <sys/syscall.h>
#include <time.h>
#include <unistd.h>
#endif

#define NO_HOST_BUILD 1
#include "../bpf/records.h"

extern "C" {

// Column pointers for fp_pack. Each points at a caller-allocated array of
// capacity >= n rows (keys: n*10 u32, row-major).
struct fp_columns {
    uint32_t *keys;        // [n][10] packed key words
    uint64_t *bytes;       // [n]
    uint32_t *packets;     // [n]
    uint32_t *tcp_flags;   // [n]
    uint32_t *eth_protocol;// [n]
    uint32_t *direction;   // [n]
    uint32_t *if_index;    // [n]
    uint32_t *dscp;        // [n]
    uint32_t *sampling;    // [n]
    uint64_t *first_seen_ns; // [n]
    uint64_t *last_seen_ns;  // [n]
};

// Pack n contiguous no_flow_event records into columns. Returns n.
size_t fp_pack(const uint8_t *events, size_t n, struct fp_columns *out) {
    const struct no_flow_event *ev =
        reinterpret_cast<const struct no_flow_event *>(events);
    for (size_t i = 0; i < n; i++) {
        const struct no_flow_key *k = &ev[i].key;
        const struct no_flow_stats *s = &ev[i].stats;
        uint32_t *kw = out->keys + i * 10;
        std::memcpy(kw, k->src_ip, 16);      // words 0..3
        std::memcpy(kw + 4, k->dst_ip, 16);  // words 4..7
        kw[8] = (static_cast<uint32_t>(k->src_port) << 16) | k->dst_port;
        kw[9] = (static_cast<uint32_t>(k->proto) << 16) |
                (static_cast<uint32_t>(k->icmp_type) << 8) | k->icmp_code;
        out->bytes[i] = s->bytes;
        out->packets[i] = s->packets;
        out->tcp_flags[i] = s->tcp_flags;
        out->eth_protocol[i] = s->eth_protocol;
        out->direction[i] = s->direction_first;
        out->if_index[i] = s->if_index_first;
        out->dscp[i] = s->dscp;
        out->sampling[i] = s->sampling;
        out->first_seen_ns[i] = s->first_seen_ns;
        out->last_seen_ns[i] = s->last_seen_ns;
    }
    return n;
}

// Dense TPU feed: one (batch_size, FP_DENSE_WORDS) u32 row-major array per
// batch instead of six column arrays — a single host->device transfer
// instead of six round trips, and a single pass over the
// raw event bytes (no intermediate FlowBatch, no Python copies). Row layout
// (must match flowpack.py pack_dense/DENSE_WORDS and the device-side unpack
// in sketch/state.py dense_to_arrays):
//   words 0..9   packed key words (same packing as fp_pack)
//   word  10     bytes as float32 bitcast (sketch planes are f32)
//   word  11     packets
//   word  12     rtt_us        (from the extra record, else 0)
//   word  13     dns_latency_us (from the dns record, else 0)
//   word  14     valid flag (1 for live rows; padding rows are all-zero)
//   word  15     sampling
//   word  16     tcp_flags | dscp << 16 | markers << 24
//                (markers: bit0 QUIC seen, bit1 NAT translation observed,
//                 bit2 IPsec encrypted, bit3 IPsec error)
//   word  17     drop bytes | drop packets << 16   (from the drops record)
//   word  18     drop latest_cause (low u16) | latest_state << 16
//   word  19     reserved (0)
#define FP_DENSE_WORDS 20

static inline uint8_t feature_markers(const struct no_extra_rec *ex,
                                      const struct no_xlat_rec *xl,
                                      const struct no_quic_rec *qc,
                                      size_t i) {
    uint8_t m = 0;
    if (qc && (qc[i].version || qc[i].seen_long_hdr || qc[i].seen_short_hdr))
        m |= 1;
    if (xl) {
        // complete translation = both endpoints observed (fp_merge_xlat rule)
        bool src_set = false, dst_set = false;
        for (int b = 0; b < NO_IP_LEN; b++) {
            if (xl[i].src_ip[b]) src_set = true;
            if (xl[i].dst_ip[b]) dst_set = true;
        }
        if (src_set && dst_set) m |= 2;
    }
    if (ex && ex[i].ipsec_encrypted) m |= 4;
    if (ex && ex[i].ipsec_ret != 0) m |= 8;
    return m;
}

static inline void fill_feature_words(const struct no_flow_stats *s,
                                      const struct no_extra_rec *ex,
                                      const struct no_xlat_rec *xl,
                                      const struct no_quic_rec *qc,
                                      const struct no_drops_rec *dr,
                                      size_t i, uint32_t *w16) {
    w16[0] = (s->tcp_flags & 0xFFFFu) |
             (static_cast<uint32_t>(s->dscp & 0xFFu) << 16) |
             (static_cast<uint32_t>(feature_markers(ex, xl, qc, i)) << 24);
    w16[1] = dr ? (static_cast<uint32_t>(dr[i].bytes) |
                   (static_cast<uint32_t>(dr[i].packets) << 16))
                : 0;
    // saturate, don't mask: subsystem drop reasons (kernel >= 6.0) carry
    // the subsystem in bits 16+ — masking would alias them onto unrelated
    // core reasons; saturation lands them in the histogram's overflow bucket
    uint32_t cause = dr ? dr[i].latest_cause : 0;
    if (cause > 0xFFFFu) cause = 0xFFFFu;
    w16[2] = dr ? (cause | (static_cast<uint32_t>(dr[i].latest_state) << 16))
                : 0;
    w16[3] = 0;
}

void fp_pack_dense(const uint8_t *events, size_t n,
                   const uint8_t *extra, const uint8_t *dns,
                   const uint8_t *drops, const uint8_t *xlat,
                   const uint8_t *quic,
                   uint32_t *out, size_t batch_size) {
    const struct no_flow_event *ev =
        reinterpret_cast<const struct no_flow_event *>(events);
    const struct no_extra_rec *ex =
        reinterpret_cast<const struct no_extra_rec *>(extra);
    const struct no_dns_rec *dn =
        reinterpret_cast<const struct no_dns_rec *>(dns);
    const struct no_drops_rec *dr =
        reinterpret_cast<const struct no_drops_rec *>(drops);
    const struct no_xlat_rec *xl =
        reinterpret_cast<const struct no_xlat_rec *>(xlat);
    const struct no_quic_rec *qc =
        reinterpret_cast<const struct no_quic_rec *>(quic);
    for (size_t i = 0; i < n; i++) {
        const struct no_flow_key *k = &ev[i].key;
        const struct no_flow_stats *s = &ev[i].stats;
        uint32_t *row = out + i * FP_DENSE_WORDS;
        std::memcpy(row, k->src_ip, 16);      // words 0..3
        std::memcpy(row + 4, k->dst_ip, 16);  // words 4..7
        row[8] = (static_cast<uint32_t>(k->src_port) << 16) | k->dst_port;
        row[9] = (static_cast<uint32_t>(k->proto) << 16) |
                 (static_cast<uint32_t>(k->icmp_type) << 8) | k->icmp_code;
        float b = static_cast<float>(s->bytes);
        std::memcpy(&row[10], &b, 4);
        row[11] = s->packets;
        row[12] = ex ? static_cast<uint32_t>(ex[i].rtt_ns / 1000) : 0;
        row[13] = dn ? static_cast<uint32_t>(dn[i].latency_ns / 1000) : 0;
        row[14] = 1;
        row[15] = s->sampling;
        fill_feature_words(s, ex, xl, qc, dr, i, row + 16);
    }
    if (n < batch_size)
        std::memset(out + n * FP_DENSE_WORDS, 0,
                    (batch_size - n) * FP_DENSE_WORDS * sizeof(uint32_t));
}

// Compact TPU feed: the host->device link (not compute) bounds the host
// path, so shrink bytes/record. IPv4 flows (v4-in-v6 mapped keys, RFC 4038
// — the common case) collapse their 10 key words to 4; non-v4 rows — and
// rows carrying DROP data, which are rare outside drop storms — spill to a
// small full-width (FP_DENSE_WORDS) side lane. One flat buffer:
//   [batch_size * 10 compact words | spill_cap * 20 dense words]
// Compact row (must match sketch/state.py compact_to_arrays):
//   w0 src_v4 (key word 3)   w1 dst_v4 (key word 7)   w2 ports (src<<16|dst)
//   w3 bit31 = valid, low 24 = proto<<16|icmp_type<<8|icmp_code
//   w4 bytes f32 bitcast     w5 packets     w6 rtt_us     w7 dns_latency_us
//   w8 sampling              w9 tcp_flags | dscp << 16 | markers << 24
// Returns the number of spill rows used, or -1 if spill_cap would overflow
// (caller falls back to the full dense pack for that batch).
#define FP_COMPACT_WORDS 10
#define FP_V4_PREFIX_WORD2 0xffff0000u  // bytes 8..11 of a mapped address

static inline bool is_v4_mapped(const uint8_t *ip16) {
    uint32_t w0, w1, w2;
    std::memcpy(&w0, ip16, 4);
    std::memcpy(&w1, ip16 + 4, 4);
    std::memcpy(&w2, ip16 + 8, 4);
    return w0 == 0 && w1 == 0 && w2 == FP_V4_PREFIX_WORD2;
}

int fp_pack_compact(const uint8_t *events, size_t n,
                    const uint8_t *extra, const uint8_t *dns,
                    const uint8_t *drops, const uint8_t *xlat,
                    const uint8_t *quic,
                    uint32_t *out, size_t batch_size, size_t spill_cap) {
    const struct no_flow_event *ev =
        reinterpret_cast<const struct no_flow_event *>(events);
    const struct no_extra_rec *ex =
        reinterpret_cast<const struct no_extra_rec *>(extra);
    const struct no_dns_rec *dn =
        reinterpret_cast<const struct no_dns_rec *>(dns);
    const struct no_drops_rec *dr =
        reinterpret_cast<const struct no_drops_rec *>(drops);
    const struct no_xlat_rec *xl =
        reinterpret_cast<const struct no_xlat_rec *>(xlat);
    const struct no_quic_rec *qc =
        reinterpret_cast<const struct no_quic_rec *>(quic);
    uint32_t *spill = out + batch_size * FP_COMPACT_WORDS;
    size_t nc = 0, ns = 0;
    for (size_t i = 0; i < n; i++) {
        const struct no_flow_key *k = &ev[i].key;
        const struct no_flow_stats *s = &ev[i].stats;
        uint32_t rtt = ex ? static_cast<uint32_t>(ex[i].rtt_ns / 1000) : 0;
        uint32_t dlat = dn ? static_cast<uint32_t>(dn[i].latency_ns / 1000) : 0;
        bool has_drops = dr && (dr[i].bytes || dr[i].packets);
        if (!has_drops && is_v4_mapped(k->src_ip) && is_v4_mapped(k->dst_ip)) {
            uint32_t *row = out + nc * FP_COMPACT_WORDS;
            std::memcpy(&row[0], k->src_ip + 12, 4);
            std::memcpy(&row[1], k->dst_ip + 12, 4);
            row[2] = (static_cast<uint32_t>(k->src_port) << 16) | k->dst_port;
            row[3] = 0x80000000u | (static_cast<uint32_t>(k->proto) << 16) |
                     (static_cast<uint32_t>(k->icmp_type) << 8) | k->icmp_code;
            float b = static_cast<float>(s->bytes);
            std::memcpy(&row[4], &b, 4);
            row[5] = s->packets;
            row[6] = rtt;
            row[7] = dlat;
            row[8] = s->sampling;
            row[9] = (s->tcp_flags & 0xFFFFu) |
                     (static_cast<uint32_t>(s->dscp & 0xFFu) << 16) |
                     (static_cast<uint32_t>(feature_markers(ex, xl, qc, i))
                      << 24);
            nc++;
        } else {
            if (ns >= spill_cap)
                return -1;
            uint32_t *row = spill + ns * FP_DENSE_WORDS;
            std::memcpy(row, k->src_ip, 16);
            std::memcpy(row + 4, k->dst_ip, 16);
            row[8] = (static_cast<uint32_t>(k->src_port) << 16) | k->dst_port;
            row[9] = (static_cast<uint32_t>(k->proto) << 16) |
                     (static_cast<uint32_t>(k->icmp_type) << 8) | k->icmp_code;
            float b = static_cast<float>(s->bytes);
            std::memcpy(&row[10], &b, 4);
            row[11] = s->packets;
            row[12] = rtt;
            row[13] = dlat;
            row[14] = 1;
            row[15] = s->sampling;
            fill_feature_words(s, ex, xl, qc, dr, i, row + 16);
            ns++;
        }
    }
    if (nc < batch_size)
        std::memset(out + nc * FP_COMPACT_WORDS, 0,
                    (batch_size - nc) * FP_COMPACT_WORDS * sizeof(uint32_t));
    if (ns < spill_cap)
        std::memset(spill + ns * FP_DENSE_WORDS, 0,
                    (spill_cap - ns) * FP_DENSE_WORDS * sizeof(uint32_t));
    return static_cast<int>(ns);
}

// ---------------------------------------------------------------------------
// Resident-key feed: the lowest-bytes-per-record TPU feed. The host keeps a
// key -> slot dictionary (this file); the DEVICE keeps a (slot_cap, 10) u32
// key table in HBM, updated from the new-key lane and gathered by slot id —
// steady-state records ship as THREE words instead of ten (the transfer
// link, not compute, bounds the host path; see docs/tpu_sketch.md byte
// budget). Flat buffer layout (must match sketch/state.py
// resident_lane_arrays and flowpack.py pack_resident):
//   [0..3]    header: w0 default sampling, w1 n_newkey, w2 n_spill,
//             w3 n_dns | n_drop << 16   (w1..w3 diagnostic only)
//   hot lane    batch_size * 3 words:
//     w0  bit31 valid | bits 28..30 rtt exp | bits 20..27 rtt mant
//         | bits 0..19 slot id          (rtt_us ~= mant << (2*exp))
//     w1  bytes as float32 bitcast
//     w2  packets (bits 0..10) | tcp_flags (11..21) | dscp (22..27)
//         | markers (28..31)
//   dns lane    dns_cap words:  row_idx << 16 | dns code
//         (code: bits 12..15 exp e, bits 0..11 mant m; value_us = m << e)
//   drop lane   drop_cap * 2 words:
//     w0  row_idx << 16 | latest_cause (saturated u16)
//     w1  drop packets << 16 | drop bytes
//   newkey lane nk_cap * 11 words: w0 = bit31 | slot id, w1..w10 key words
//   spill lane  spill_cap * FP_DENSE_WORDS dense rows (anything the hot
//               row can't carry exactly: packets/flags overflow, sampling
//               mismatch, rtt beyond the code range, lane overflows)
//
// fp_pack_resident packs events[start..n) until the hot lane or the spill
// lane fills, and returns the number of rows CONSUMED — partial packing
// with continuation: the caller ships the (always self-consistent) prefix
// and packs the remainder into the next buffer, so the dictionary and the
// device table learn monotonically even under cold-start floods (no
// rollback, no dense fallback). A full dictionary is the caller's policy
// decision: reset it between calls — stale device-table rows are harmless
// because every live slot is redefined through the new-key lane before any
// hot row references it. Lane counts land in header words 1..3.
#define FP_HOT_WORDS 3
#define FP_RESIDENT_HDR 4
#define FP_NK_WORDS 11
#define FP_SLOT_MASK 0xFFFFFu
#define FP_RTT_MAX_US (0xFFu << 14)

// 16-byte entries: a 64-bit key FINGERPRINT instead of the 40-byte key.
// The table must be probed once per record at line rate; 48-byte entries
// made every probe a random DRAM access (measured 9.8M rec/s on the pack
// loop). A fingerprint collision (p ~ n^2/2^65 — ~1e-6 at a full 2^18
// table) maps a new flow onto an existing slot: its records fold under
// that slot's key words, a bounded mis-attribution of the same order as a
// Count-Min collision. The sketch plane hashes the (gathered) key words
// themselves, so nothing downstream amplifies it.
struct fp_dict_entry {
    uint64_t fp;       // 0 = empty (fingerprints of 0 are remapped to 1)
    uint32_t slot;
    uint32_t pad_;
};

struct fp_dict {
    struct fp_dict_entry *tab;
    size_t mask;       // hash table size - 1 (power of two)
    uint32_t slot_cap;
    uint32_t next_slot;
};

static inline uint64_t key_fp64(const uint32_t *kw) {
    // 40 key bytes = 5 u64 lanes; murmur-style mix per lane + finalizer
    uint64_t h = 0x9E3779B97F4A7C15ull;
    for (int i = 0; i < 5; i++) {
        uint64_t k;
        std::memcpy(&k, reinterpret_cast<const uint8_t *>(kw) + i * 8, 8);
        k *= 0xC2B2AE3D27D4EB4Full;
        k = (k << 31) | (k >> 33);
        k *= 0x9E3779B185EBCA87ull;
        h ^= k;
        h = ((h << 27) | (h >> 37)) * 5 + 0x52DCE729ull;
    }
    h ^= h >> 33;
    h *= 0xFF51AFD7ED558CCDull;
    h ^= h >> 33;
    h *= 0xC4CEB9FE1A85EC53ull;
    h ^= h >> 33;
    return h ? h : 1;
}

void *fp_dict_new(uint32_t slot_cap) {
    if (slot_cap == 0 || slot_cap > (FP_SLOT_MASK + 1))
        return nullptr;
    size_t cap = 1;
    while (cap < static_cast<size_t>(slot_cap) * 2)
        cap <<= 1;
    fp_dict *d = new fp_dict;
    d->tab = new fp_dict_entry[cap]();
    d->mask = cap - 1;
    d->slot_cap = slot_cap;
    d->next_slot = 0;
    return d;
}

void fp_dict_free(void *h) {
    if (!h) return;
    fp_dict *d = static_cast<fp_dict *>(h);
    delete[] d->tab;
    delete d;
}

void fp_dict_reset(void *h) {
    fp_dict *d = static_cast<fp_dict *>(h);
    std::memset(d->tab, 0, (d->mask + 1) * sizeof(fp_dict_entry));
    d->next_slot = 0;
}

uint32_t fp_dict_count(void *h) {
    return static_cast<fp_dict *>(h)->next_slot;
}

// Find the fingerprint's hash-table index; *found says whether it's there.
static inline size_t dict_probe(const fp_dict *d, uint64_t fp, bool *found) {
    size_t i = fp & d->mask;
    for (;;) {
        const fp_dict_entry *e = &d->tab[i];
        if (!e->fp) {
            *found = false;
            return i;
        }
        if (e->fp == fp) {
            *found = true;
            return i;
        }
        i = (i + 1) & d->mask;
    }
}

static inline void make_kw(const struct no_flow_key *k, uint32_t *kw) {
    std::memcpy(kw, k->src_ip, 16);
    std::memcpy(kw + 4, k->dst_ip, 16);
    kw[8] = (static_cast<uint32_t>(k->src_port) << 16) | k->dst_port;
    kw[9] = (static_cast<uint32_t>(k->proto) << 16) |
            (static_cast<uint32_t>(k->icmp_type) << 8) | k->icmp_code;
}

static inline uint32_t rtt_code11(uint32_t rtt_us) {
    // bits 0..7 mantissa, bits 8..10 exponent; value ~= m << (2*e)
    uint32_t e = 0;
    while ((rtt_us >> (2 * e)) > 0xFFu)
        e++;
    return ((rtt_us >> (2 * e)) & 0xFFu) | (e << 8);
}

static inline uint32_t lat_code16(uint64_t us) {
    // bits 0..11 mantissa, bits 12..15 exponent; value ~= m << e
    uint32_t e = 0;
    while ((us >> e) > 0xFFFu && e < 15)
        e++;
    uint64_t m = us >> e;
    if (m > 0xFFFu) m = 0xFFFu;  // saturate at ~134s
    return static_cast<uint32_t>(m) | (e << 12);
}

int64_t fp_pack_resident(const uint8_t *events, size_t start, size_t n,
                         const uint8_t *extra, const uint8_t *dns,
                         const uint8_t *drops, const uint8_t *xlat,
                         const uint8_t *quic,
                         void *dict_h, uint32_t *out, size_t batch_size,
                         size_t dns_cap, size_t drop_cap, size_t nk_cap,
                         size_t spill_cap) {
    fp_dict *d = static_cast<fp_dict *>(dict_h);
    const struct no_flow_event *ev =
        reinterpret_cast<const struct no_flow_event *>(events);
    const struct no_extra_rec *ex =
        reinterpret_cast<const struct no_extra_rec *>(extra);
    const struct no_dns_rec *dn =
        reinterpret_cast<const struct no_dns_rec *>(dns);
    const struct no_drops_rec *dr =
        reinterpret_cast<const struct no_drops_rec *>(drops);
    const struct no_xlat_rec *xl =
        reinterpret_cast<const struct no_xlat_rec *>(xlat);
    const struct no_quic_rec *qc =
        reinterpret_cast<const struct no_quic_rec *>(quic);
    uint32_t *hot = out + FP_RESIDENT_HDR;
    uint32_t *dnsl = hot + batch_size * FP_HOT_WORDS;
    uint32_t *dropl = dnsl + dns_cap;
    uint32_t *nkl = dropl + drop_cap * 2;
    uint32_t *spill = nkl + nk_cap * FP_NK_WORDS;
    size_t nh = 0, nd = 0, nr = 0, nk = 0, ns = 0;
    uint32_t def_sampling = start < n ? ev[start].stats.sampling : 0;

    // fingerprint lookahead pipeline: compute row i+PF's fingerprint and
    // prefetch its table line while processing row i — the probe is a
    // random access into a multi-MB table, and exposed DRAM latency was
    // the pack loop's measured bottleneck
    enum { PF = 16 };
    uint64_t fpbuf[PF];
    for (size_t j = start; j < n && j < start + PF; j++) {
        uint32_t kwp[10];
        make_kw(&ev[j].key, kwp);
        fpbuf[j % PF] = key_fp64(kwp);
        __builtin_prefetch(&d->tab[fpbuf[j % PF] & d->mask]);
    }
    size_t i = start;
    for (; i < n && nh < batch_size; i++) {
        const struct no_flow_key *k = &ev[i].key;
        const struct no_flow_stats *s = &ev[i].stats;
        // row i's fingerprint FIRST: the ring slot is about to be reused
        // for row i+PF
        uint64_t fp = fpbuf[i % PF];
        if (i + PF < n) {
            uint32_t kwp[10];
            make_kw(&ev[i + PF].key, kwp);
            fpbuf[(i + PF) % PF] = key_fp64(kwp);
            __builtin_prefetch(&d->tab[fpbuf[(i + PF) % PF] & d->mask]);
        }
        uint32_t kw[10];
        make_kw(k, kw);
        // ensure the key has a slot (insert through the new-key lane);
        // nk-lane or dictionary exhaustion just routes the row to spill —
        // the key is learned by a later chunk
        bool found;
        size_t hi = dict_probe(d, fp, &found);
        bool have_slot = found;
        uint32_t slot = found ? d->tab[hi].slot : 0;
        if (!found && nk < nk_cap && d->next_slot < d->slot_cap) {
            slot = d->next_slot++;
            d->tab[hi].fp = fp;
            d->tab[hi].slot = slot;
            uint32_t *row = nkl + nk * FP_NK_WORDS;
            row[0] = 0x80000000u | slot;
            std::memcpy(row + 1, kw, 40);
            nk++;
            have_slot = true;
        }
        uint32_t rtt = ex ? static_cast<uint32_t>(ex[i].rtt_ns / 1000) : 0;
        uint64_t dlat = dn ? dn[i].latency_ns / 1000 : 0;
        bool has_drops = dr && (dr[i].bytes || dr[i].packets);
        bool hot_ok = have_slot && s->packets < 0x800 &&
                      s->tcp_flags < 0x800 && s->dscp < 0x40 &&
                      s->sampling == def_sampling && rtt <= FP_RTT_MAX_US &&
                      (!dlat || nd < dns_cap) &&
                      (!has_drops || nr < drop_cap);
        if (hot_ok) {
            uint32_t *row = hot + nh * FP_HOT_WORDS;
            row[0] = 0x80000000u | (rtt_code11(rtt) << 20) | slot;
            float b = static_cast<float>(s->bytes);
            std::memcpy(&row[1], &b, 4);
            row[2] = (s->packets & 0x7FFu) |
                     (static_cast<uint32_t>(s->tcp_flags & 0x7FFu) << 11) |
                     (static_cast<uint32_t>(s->dscp & 0x3Fu) << 22) |
                     (static_cast<uint32_t>(feature_markers(ex, xl, qc, i))
                      << 28);
            if (dlat) {
                dnsl[nd++] = (static_cast<uint32_t>(nh) << 16) |
                             lat_code16(dlat);
            }
            if (has_drops) {
                uint32_t cause = dr[i].latest_cause;
                if (cause > 0xFFFFu) cause = 0xFFFFu;
                uint32_t *de = dropl + nr * 2;
                de[0] = (static_cast<uint32_t>(nh) << 16) | cause;
                de[1] = (static_cast<uint32_t>(dr[i].packets) << 16) |
                        dr[i].bytes;
                nr++;
            }
            nh++;
        } else {
            if (ns >= spill_cap)
                break;  // chunk full: caller continues from row i
            uint32_t *row = spill + ns * FP_DENSE_WORDS;
            std::memcpy(row, kw, 40);
            float b = static_cast<float>(s->bytes);
            std::memcpy(&row[10], &b, 4);
            row[11] = s->packets;
            row[12] = rtt;
            row[13] = static_cast<uint32_t>(dlat);
            row[14] = 1;
            row[15] = s->sampling;
            fill_feature_words(s, ex, xl, qc, dr, i, row + 16);
            ns++;
        }
    }
    out[0] = def_sampling;
    out[1] = static_cast<uint32_t>(nk);
    out[2] = static_cast<uint32_t>(ns);
    out[3] = static_cast<uint32_t>(nd) | (static_cast<uint32_t>(nr) << 16);
    if (nh < batch_size)
        std::memset(hot + nh * FP_HOT_WORDS, 0,
                    (batch_size - nh) * FP_HOT_WORDS * sizeof(uint32_t));
    if (nd < dns_cap)
        std::memset(dnsl + nd, 0, (dns_cap - nd) * sizeof(uint32_t));
    if (nr < drop_cap)
        std::memset(dropl + nr * 2, 0, (drop_cap - nr) * 2 * sizeof(uint32_t));
    if (nk < nk_cap)
        std::memset(nkl + nk * FP_NK_WORDS, 0,
                    (nk_cap - nk) * FP_NK_WORDS * sizeof(uint32_t));
    if (ns < spill_cap)
        std::memset(spill + ns * FP_DENSE_WORDS, 0,
                    (spill_cap - ns) * FP_DENSE_WORDS * sizeof(uint32_t));
    return static_cast<int64_t>(i - start);
}

static inline void merge_times(uint64_t *dfirst, uint64_t *dlast,
                               uint64_t sfirst, uint64_t slast) {
    if (*dfirst == 0 || (sfirst != 0 && sfirst < *dfirst))
        *dfirst = sfirst;
    if (slast > *dlast)
        *dlast = slast;
}

static inline uint16_t sat_add16(uint16_t a, uint16_t b) {
    uint32_t s = static_cast<uint32_t>(a) + b;
    return s > 0xFFFF ? 0xFFFF : static_cast<uint16_t>(s);
}

// Merge per-CPU partials of the base stats struct.
// values: n_cpu consecutive no_flow_stats images for ONE map entry.
// out: one no_flow_stats. Mirrors model/accumulate.py accumulate_base.
void fp_merge_stats(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_flow_stats out;
    std::memcpy(&out, values, sizeof(out));
    // the datapath's lock-free slot reservation can leave the counter
    // TRANSIENTLY above capacity (saturation undo in flight) — clamp before
    // any indexing
    if (out.n_observed_intf > NO_MAX_OBSERVED_INTERFACES)
        out.n_observed_intf = NO_MAX_OBSERVED_INTERFACES;
    const struct no_flow_stats *v =
        reinterpret_cast<const struct no_flow_stats *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_flow_stats *s = &v[c];
        bool dst_empty = out.first_seen_ns == 0 && out.packets == 0;
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        uint64_t nb = out.bytes + s->bytes;
        out.bytes = nb < out.bytes ? UINT64_MAX : nb;  // saturate on wrap
        uint64_t np = static_cast<uint64_t>(out.packets) + s->packets;
        out.packets = np > UINT32_MAX ? UINT32_MAX
                                      : static_cast<uint32_t>(np);
        out.tcp_flags |= s->tcp_flags;
        if (s->eth_protocol) out.eth_protocol = s->eth_protocol;
        if (s->dscp) out.dscp = s->dscp;
        if (s->sampling) out.sampling = s->sampling;
        if (s->errno_fallback) out.errno_fallback = s->errno_fallback;
        bool src_mac_zero = true, dst_mac_zero = true;
        for (int i = 0; i < NO_ETH_ALEN; i++) {
            if (out.src_mac[i]) src_mac_zero = false;
            if (out.dst_mac[i]) dst_mac_zero = false;
        }
        if (src_mac_zero) std::memcpy(out.src_mac, s->src_mac, NO_ETH_ALEN);
        if (dst_mac_zero) std::memcpy(out.dst_mac, s->dst_mac, NO_ETH_ALEN);
        if (dst_empty) {
            out.if_index_first = s->if_index_first;
            out.direction_first = s->direction_first;
        }
        // ssl_version: first non-zero wins; a conflicting later version sets
        // the mismatch flag (mirrors accumulate_base / kernel entry rule)
        if (s->ssl_version) {
            if (out.ssl_version == 0)
                out.ssl_version = s->ssl_version;
            else if (out.ssl_version != s->ssl_version)
                out.misc_flags |= NO_MISC_SSL_MISMATCH;
        }
        if (s->tls_cipher_suite) out.tls_cipher_suite = s->tls_cipher_suite;
        if (s->tls_key_share) out.tls_key_share = s->tls_key_share;
        out.tls_types |= s->tls_types;
        out.misc_flags |= s->misc_flags;
        int ns_obs = s->n_observed_intf > NO_MAX_OBSERVED_INTERFACES
                         ? NO_MAX_OBSERVED_INTERFACES
                         : s->n_observed_intf;
        for (int j = 0; j < ns_obs; j++) {
            bool seen = false;
            for (int i = 0; i < out.n_observed_intf; i++) {
                if (out.observed_intf[i] == s->observed_intf[j] &&
                    out.observed_direction[i] == s->observed_direction[j]) {
                    seen = true;
                    break;
                }
            }
            if (!seen && out.n_observed_intf < NO_MAX_OBSERVED_INTERFACES) {
                out.observed_intf[out.n_observed_intf] = s->observed_intf[j];
                out.observed_direction[out.n_observed_intf] =
                    s->observed_direction[j];
                out.n_observed_intf++;
            }
        }
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the extra (rtt/ipsec) record.
void fp_merge_extra(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_extra_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_extra_rec *v =
        reinterpret_cast<const struct no_extra_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_extra_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        if (s->rtt_ns > out.rtt_ns) out.rtt_ns = s->rtt_ns;
        if (out.ipsec_ret < s->ipsec_ret) {
            out.ipsec_ret = s->ipsec_ret;
            out.ipsec_encrypted = s->ipsec_encrypted;
        } else if (out.ipsec_ret == s->ipsec_ret && s->ipsec_encrypted) {
            out.ipsec_encrypted = s->ipsec_encrypted;
        }
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the drops record.
void fp_merge_drops(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_drops_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_drops_rec *v =
        reinterpret_cast<const struct no_drops_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_drops_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        out.bytes = sat_add16(out.bytes, s->bytes);
        out.packets = sat_add16(out.packets, s->packets);
        out.latest_flags |= s->latest_flags;
        if (s->latest_cause) out.latest_cause = s->latest_cause;
        if (s->latest_state) out.latest_state = s->latest_state;
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the DNS record (max latency wins).
void fp_merge_dns(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_dns_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_dns_rec *v =
        reinterpret_cast<const struct no_dns_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_dns_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        out.dns_flags |= s->dns_flags;
        if (s->dns_id) out.dns_id = s->dns_id;
        if (out.errno_code != s->errno_code) out.errno_code = s->errno_code;
        if (s->latency_ns > out.latency_ns) out.latency_ns = s->latency_ns;
        if (s->name[0]) std::memcpy(out.name, s->name, NO_DNS_NAME_MAX_LEN);
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the network-events record: dedup-append into a
// wrapping ring of NO_MAX_NETWORK_EVENTS slots (n_events is the ring CURSOR,
// not a count — renderers scan slots keyed on packets[i] != 0). Mirrors
// model/accumulate.py accumulate_network_events.
void fp_merge_nevents(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_nevents_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_nevents_rec *v =
        reinterpret_cast<const struct no_nevents_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_nevents_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        uint8_t idx = out.n_events % NO_MAX_NETWORK_EVENTS;
        for (int j = 0; j < NO_MAX_NETWORK_EVENTS; j++) {
            if (s->packets[j] == 0)
                continue;
            bool dup = false;
            for (int i = 0; i < NO_MAX_NETWORK_EVENTS; i++) {
                if (std::memcmp(out.events[i], s->events[j],
                                NO_MAX_EVENT_MD) == 0) {
                    dup = true;
                    break;
                }
            }
            if (!dup) {
                std::memcpy(out.events[idx], s->events[j], NO_MAX_EVENT_MD);
                out.bytes[idx] = sat_add16(out.bytes[idx], s->bytes[j]);
                out.packets[idx] = sat_add16(out.packets[idx], s->packets[j]);
                idx = (idx + 1) % NO_MAX_NETWORK_EVENTS;
            }
        }
        out.n_events = idx;
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the NAT-translation record: a complete
// (both-endpoints) observation replaces. Mirrors accumulate_xlat.
void fp_merge_xlat(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_xlat_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_xlat_rec *v =
        reinterpret_cast<const struct no_xlat_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_xlat_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        bool src_set = false, dst_set = false;
        for (int i = 0; i < NO_IP_LEN; i++) {
            if (s->src_ip[i]) src_set = true;
            if (s->dst_ip[i]) dst_set = true;
        }
        if (src_set && dst_set) {
            std::memcpy(out.src_ip, s->src_ip, NO_IP_LEN);
            std::memcpy(out.dst_ip, s->dst_ip, NO_IP_LEN);
            out.src_port = s->src_port;
            out.dst_port = s->dst_port;
            out.zone_id = s->zone_id;
        }
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// Merge per-CPU partials of the QUIC record: max version wins, header-seen
// flags accumulate. Mirrors accumulate_quic.
void fp_merge_quic(const uint8_t *values, size_t n_cpu, uint8_t *out_buf) {
    struct no_quic_rec out;
    std::memcpy(&out, values, sizeof(out));
    const struct no_quic_rec *v =
        reinterpret_cast<const struct no_quic_rec *>(values);
    for (size_t c = 1; c < n_cpu; c++) {
        const struct no_quic_rec *s = &v[c];
        merge_times(&out.first_seen_ns, &out.last_seen_ns,
                    s->first_seen_ns, s->last_seen_ns);
        if (s->version > out.version) out.version = s->version;
        if (s->seen_long_hdr > out.seen_long_hdr)
            out.seen_long_hdr = s->seen_long_hdr;
        if (s->seen_short_hdr > out.seen_short_hdr)
            out.seen_short_hdr = s->seen_short_hdr;
    }
    std::memcpy(out_buf, &out, sizeof(out));
}

// ---------------------------------------------------------------------------
// Batched per-CPU merges: one call for ALL keys of a drained feature map.
// values: n_keys * n_cpu consecutive record images (the kernel's
// LOOKUP_AND_DELETE_BATCH value buffer, padding already stripped/absent —
// every record struct here is 8-byte-aligned so the per-CPU stride equals
// sizeof); out: n_keys records. One ctypes round trip replaces n_keys of
// them — the eviction plane's native fast path (columnar python twin:
// model/accumulate.py COLUMNAR_MERGES; equivalence pinned in
// tests/test_evict_columnar.py).
// ---------------------------------------------------------------------------
#define FP_MERGE_BATCH(name, type)                                          \
    void name##_batch(const uint8_t *values, size_t n_keys, size_t n_cpu,   \
                      uint8_t *out) {                                       \
        for (size_t k = 0; k < n_keys; k++)                                 \
            name(values + k * n_cpu * sizeof(type), n_cpu,                  \
                 out + k * sizeof(type));                                   \
    }

FP_MERGE_BATCH(fp_merge_stats, struct no_flow_stats)
FP_MERGE_BATCH(fp_merge_extra, struct no_extra_rec)
FP_MERGE_BATCH(fp_merge_drops, struct no_drops_rec)
FP_MERGE_BATCH(fp_merge_dns, struct no_dns_rec)
FP_MERGE_BATCH(fp_merge_nevents, struct no_nevents_rec)
FP_MERGE_BATCH(fp_merge_xlat, struct no_xlat_rec)
FP_MERGE_BATCH(fp_merge_quic, struct no_quic_rec)

// ---------------------------------------------------------------------------
// FLOW_EVENT interleave: compose contiguous no_flow_event rows (key 40B |
// stats 104B) from the two columns a batched map drain yields — the columnar
// eviction plane's single copy boundary done as one native pass instead of
// two strided numpy field assignments (python twin:
// model/binfmt.py events_from_keys_stats; equivalence pinned in
// tests/test_evict_parallel.py). `out` must hold n events; tail rows beyond
// n (the loader's ringbuf-orphan appendix) are the caller's to zero.
// ---------------------------------------------------------------------------
void fp_events_from_keys_stats(const uint8_t *keys, const uint8_t *stats,
                               size_t n, uint8_t *out) {
    for (size_t i = 0; i < n; i++) {
        struct no_flow_event *ev =
            reinterpret_cast<struct no_flow_event *>(
                out + i * sizeof(struct no_flow_event));
        std::memcpy(&ev->key, keys + i * sizeof(struct no_flow_key),
                    sizeof(struct no_flow_key));
        std::memcpy(&ev->stats, stats + i * sizeof(struct no_flow_stats),
                    sizeof(struct no_flow_stats));
    }
}

// crc32c (Castagnoli) — slice-by-8; used by the Kafka record-batch encoder.
static uint32_t crc32c_table[8][256];
static bool crc32c_ready = false;

static void crc32c_init() {
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = i;
        for (int k = 0; k < 8; k++)
            c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
        crc32c_table[0][i] = c;
    }
    for (uint32_t i = 0; i < 256; i++) {
        uint32_t c = crc32c_table[0][i];
        for (int t = 1; t < 8; t++) {
            c = crc32c_table[0][c & 0xFF] ^ (c >> 8);
            crc32c_table[t][i] = c;
        }
    }
    crc32c_ready = true;
}

#if defined(__x86_64__)
// Hardware CRC32C (SSE4.2) — ~10x the sliced table walk; the key
// dictionary hashes 40 bytes per record, so this sits on the pack hot path.
__attribute__((target("sse4.2")))
static uint32_t crc32c_hw(const uint8_t *data, size_t n) {
    uint64_t crc = 0xFFFFFFFFu;
    size_t i = 0;
    for (; n - i >= 8; i += 8) {
        uint64_t v;
        std::memcpy(&v, data + i, 8);
        crc = __builtin_ia32_crc32di(crc, v);
    }
    for (; i < n; i++)
        crc = __builtin_ia32_crc32qi(static_cast<uint32_t>(crc), data[i]);
    return static_cast<uint32_t>(crc) ^ 0xFFFFFFFFu;
}
static int crc32c_have_hw = -1;
#endif

uint32_t fp_crc32c(const uint8_t *data, size_t n) {
#if defined(__x86_64__)
    if (crc32c_have_hw < 0)
        crc32c_have_hw = __builtin_cpu_supports("sse4.2") ? 1 : 0;
    if (crc32c_have_hw)
        return crc32c_hw(data, n);
#endif
    if (!crc32c_ready)
        crc32c_init();
    uint32_t crc = 0xFFFFFFFFu;
    size_t i = 0;
    while (n - i >= 8) {
        uint32_t lo, hi;
        std::memcpy(&lo, data + i, 4);
        std::memcpy(&hi, data + i + 4, 4);
        crc ^= lo;
        crc = crc32c_table[7][crc & 0xFF] ^ crc32c_table[6][(crc >> 8) & 0xFF] ^
              crc32c_table[5][(crc >> 16) & 0xFF] ^
              crc32c_table[4][(crc >> 24) & 0xFF] ^
              crc32c_table[3][hi & 0xFF] ^ crc32c_table[2][(hi >> 8) & 0xFF] ^
              crc32c_table[1][(hi >> 16) & 0xFF] ^
              crc32c_table[0][(hi >> 24) & 0xFF];
        i += 8;
    }
    for (; i < n; i++)
        crc = crc32c_table[0][(crc ^ data[i]) & 0xFF] ^ (crc >> 8);
    return crc ^ 0xFFFFFFFFu;
}

// ===========================================================================
// Eviction alignment: the key join, event compose and feature alignment of
// one drain — the native form of loader._join_keys plus the numpy align of
// loader.decode_eviction, which stay as its fallback and the tests' oracle.
// fp_align_eviction serves the sequential and the lane drains as one
// GIL-releasing ctypes call; fp_drain_to_resident runs the very same
// align_join / align_fill below.
//
// Bit-exact with the twin: rows are numbered [agg | feature map 0 | map 1 |
// ...]; a STABLE sort of (hash, row) — the twin's argsort(kind="stable") —
// orders every key group by row, so a group's agg rows lead it and the LAST
// of them is the match (dict-idiom parity); a group with no agg row appends
// one orphan event, in sorted-group order. Two distinct keys on one 64-bit
// hash anywhere in the drain redo the order lexicographically (the twin's
// lexsort) before anything is written.
//
// Bounded by its input: the sort is an LSD radix sort (ALIGN_RADIX_PASSES
// passes over the rows, a pass skipped where one bucket holds every row),
// the walk visits each sorted row once and compares keys only where two
// neighbours share a hash, and the rare lexicographic fallback is one
// std::sort. The scratch is the caller's (fp_align_scratch_bytes): nothing
// is allocated here.
// ===========================================================================

#define FP_ALIGN_MAX_FEATS 8
#define ALIGN_RADIX_BITS 16
#define ALIGN_RADIX_BUCKETS (1u << ALIGN_RADIX_BITS)
#define ALIGN_RADIX_PASSES ((64 + ALIGN_RADIX_BITS - 1) / ALIGN_RADIX_BITS)

// loader._hash_keys_u64 twin: the join's sort key over the 5 key words.
static inline uint64_t key_hash64(const uint8_t *k) {
    uint64_t w[5];
    std::memcpy(w, k, sizeof(w));
    uint64_t h = w[0];
    for (int i = 1; i < 5; i++) {
        h = (h ^ (w[i] * 0xC2B2AE3D27D4EB4FULL)) * 0x9E3779B97F4A7C15ULL;
        h ^= h >> 29;  // per-round mix, exactly like the numpy twin
    }
    return h;
}

// One feature map of a drain. Every record type leads with its first- and
// last-seen u64s (the orphan events' times are read from there).
struct fp_align_feat {
    const uint8_t *keys;  // [n] no_flow_key
    const uint8_t *recs;  // [n] merged records, value_size bytes each
    uint8_t *out;         // [>= n_events] aligned rows (the fill zeroes them)
    uint64_t n, value_size;
};

struct fp_align_result {
    uint64_t n_events, n_orphans, lex_fallback;
};

struct align_job {
    const uint8_t *agg_keys, *agg_stats;
    uint64_t n_agg, total, n_orphans, lex;
    uint32_t n_feats;
    struct fp_align_feat feats[FP_ALIGN_MAX_FEATS];
    uint64_t feat_base[FP_ALIGN_MAX_FEATS + 1];  // first row of each map
    // scratch views (align_bind_scratch)
    uint64_t *hs, *hs_b, *app_first, *app_last;
    uint32_t *rows, *rows_b, *feat_eidx, *app_row, *hist;
};

static size_t align_scratch_bytes(uint64_t total, uint64_t n_feat_rows) {
    if (n_feat_rows == 0)
        return 0;  // nothing to join: the agg rows compose as they are
    return (2 * total + 2 * n_feat_rows) * (sizeof(uint64_t) + sizeof(uint32_t)) +
           static_cast<size_t>(ALIGN_RADIX_PASSES) * ALIGN_RADIX_BUCKETS *
               sizeof(uint32_t);
}

// Fills `j` from the drain's maps; -2 on arguments the join cannot take.
static int align_setup(struct align_job *j, const uint8_t *agg_keys,
                       const uint8_t *agg_stats, uint64_t n_agg,
                       const struct fp_align_feat *feats, uint32_t n_feats) {
    if (n_feats > FP_ALIGN_MAX_FEATS || (n_agg && (!agg_keys || !agg_stats)))
        return -2;
    std::memset(j, 0, sizeof(*j));
    j->agg_keys = agg_keys;
    j->agg_stats = agg_stats;
    j->n_agg = n_agg;
    j->n_feats = n_feats;
    j->feat_base[0] = n_agg;
    for (uint32_t f = 0; f < n_feats; f++) {
        const struct fp_align_feat *m = &feats[f];
        if (m->n && (!m->keys || !m->recs || m->value_size < 16))
            return -2;
        j->feats[f] = *m;
        j->feat_base[f + 1] = j->feat_base[f] + m->n;
    }
    j->total = j->feat_base[n_feats];
    if (j->total >= UINT32_MAX)
        return -2;  // rows are u32 in the scratch
    return 0;
}

static void align_bind_scratch(struct align_job *j, uint8_t *scratch) {
    const uint64_t t = j->total, nf = t - j->n_agg;
    uint64_t *w = reinterpret_cast<uint64_t *>(scratch);
    j->hs = w;
    j->hs_b = w + t;
    j->app_first = w + 2 * t;
    j->app_last = w + 2 * t + nf;
    uint32_t *h = reinterpret_cast<uint32_t *>(w + 2 * t + 2 * nf);
    j->rows = h;
    j->rows_b = h + t;
    j->feat_eidx = h + 2 * t;
    j->app_row = h + 2 * t + nf;
    j->hist = h + 2 * t + 2 * nf;
}

// `wa`: a key's five words, loaded once for the run it starts
static inline bool key_eq(const uint64_t *wa, const uint8_t *b) {
    uint64_t wb[5];
    std::memcpy(wb, b, sizeof(wb));
    return !((wa[0] ^ wb[0]) | (wa[1] ^ wb[1]) | (wa[2] ^ wb[2]) |
             (wa[3] ^ wb[3]) | (wa[4] ^ wb[4]));
}

static inline const uint8_t *align_key(const struct align_job *j,
                                       uint32_t row) {
    if (row < j->n_agg)
        return j->agg_keys + static_cast<size_t>(row) * sizeof(struct no_flow_key);
    uint32_t f = 0;
    while (row >= j->feat_base[f + 1])  // ends at the row's map (row < total)
        f++;
    return j->feats[f].keys +
           static_cast<size_t>(row - j->feat_base[f]) * sizeof(struct no_flow_key);
}

// One pass over the sorted rows: group them by key, give every feature row
// its event row (feat_eidx) and every agg-less group an orphan. With `hs`
// (hash order) a group is a run of one hash, and two different keys in one
// run are a collision: false, nothing kept. Without it (lexicographic order)
// a group is a run of one key.
static bool align_walk(struct align_job *j, const uint64_t *hs,
                       const uint32_t *ord) {
    const uint64_t t = j->total, n_agg = j->n_agg;
    uint64_t n_app = 0, a = 0;
    while (a < t) {
        uint64_t b = a + 1;
        if (!hs || (b < t && hs[b] == hs[a])) {
            uint64_t wa[5];
            std::memcpy(wa, align_key(j, ord[a]), sizeof(wa));
            if (hs) {
                for (; b < t && hs[b] == hs[a]; b++)
                    if (!key_eq(wa, align_key(j, ord[b])))
                        return false;
            } else {
                while (b < t && key_eq(wa, align_key(j, ord[b])))
                    b++;
            }
        }
        uint64_t k = a;
        while (k < b && ord[k] < n_agg)  // a group's agg rows lead it
            k++;
        if (k < b) {
            uint32_t eidx;
            if (k > a) {
                eidx = ord[k - 1];  // the last agg row wins
            } else {
                j->app_row[n_app] = ord[a];
                j->app_first[n_app] = UINT64_MAX;
                j->app_last[n_app] = 0;
                eidx = static_cast<uint32_t>(n_agg + n_app++);
            }
            for (; k < b; k++)
                j->feat_eidx[ord[k] - n_agg] = eidx;
        }
        a = b;
    }
    j->n_orphans = n_app;
    return true;
}

// The join: hash, stable radix sort, walk (lexicographic on a collision).
static void align_join(struct align_job *j) {
    const uint64_t t = j->total, n_agg = j->n_agg;
    j->n_orphans = 0;
    j->lex = 0;
    if (t == n_agg)
        return;  // no feature rows (the twin skips the join too)
    const size_t ks = sizeof(struct no_flow_key);
    uint64_t *hs = j->hs;
    uint32_t *rows = j->rows;
    for (uint64_t r = 0; r < n_agg; r++) {
        hs[r] = key_hash64(j->agg_keys + r * ks);
        rows[r] = static_cast<uint32_t>(r);
    }
    for (uint32_t f = 0; f < j->n_feats; f++) {
        const uint64_t base = j->feat_base[f];
        for (uint64_t r = 0; r < j->feats[f].n; r++) {
            hs[base + r] = key_hash64(j->feats[f].keys + r * ks);
            rows[base + r] = static_cast<uint32_t>(base + r);
        }
    }
    // LSD radix sort of (hash, row): every pass's histogram in one read
    const uint32_t mask = ALIGN_RADIX_BUCKETS - 1;
    uint32_t *hist = j->hist;
    std::memset(hist, 0, sizeof(uint32_t) * ALIGN_RADIX_PASSES * ALIGN_RADIX_BUCKETS);
    for (uint64_t i = 0; i < t; i++) {
        const uint64_t h = hs[i];
        for (uint32_t p = 0; p < ALIGN_RADIX_PASSES; p++)
            hist[p * ALIGN_RADIX_BUCKETS + ((h >> (p * ALIGN_RADIX_BITS)) & mask)]++;
    }
    uint64_t *sh = hs, *dh = j->hs_b;
    uint32_t *sr = rows, *dr = j->rows_b;
    for (uint32_t p = 0; p < ALIGN_RADIX_PASSES; p++) {
        const uint32_t shift = p * ALIGN_RADIX_BITS;
        uint32_t *c = hist + p * ALIGN_RADIX_BUCKETS;
        if (c[(sh[0] >> shift) & mask] == t)
            continue;  // one bucket holds every row: the pass moves nothing
        uint32_t sum = 0;
        for (uint32_t b = 0; b < ALIGN_RADIX_BUCKETS; b++) {
            const uint32_t n = c[b];
            c[b] = sum;
            sum += n;
        }
        for (uint64_t i = 0; i < t; i++) {
            const uint32_t pos = c[(sh[i] >> shift) & mask]++;
            dh[pos] = sh[i];
            dr[pos] = sr[i];
        }
        std::swap(sh, dh);
        std::swap(sr, dr);
    }
    if (align_walk(j, sh, sr))
        return;
    // distinct keys on one 64-bit hash (p ~ rows^2 / 2^65): the exact
    // lexicographic order of the five key words, ties by row (lexsort twin)
    j->lex = 1;
    for (uint64_t r = 0; r < t; r++)
        rows[r] = static_cast<uint32_t>(r);
    std::sort(rows, rows + t, [j](uint32_t a, uint32_t b) {
        uint64_t wa[5], wb[5];
        std::memcpy(wa, align_key(j, a), sizeof(wa));
        std::memcpy(wb, align_key(j, b), sizeof(wb));
        for (int i = 0; i < 5; i++)
            if (wa[i] != wb[i])
                return wa[i] < wb[i];
        return a < b;
    });
    align_walk(j, NULL, rows);
}

// The outputs: events (n_agg + n_orphans rows, every byte written) and each
// feature map's aligned rows (`out`, zeroed, then its records scattered in
// row order: duplicate keys across drain chunks, the last record wins).
static void align_fill(const struct align_job *j, uint8_t *events) {
    const uint64_t n_agg = j->n_agg, n_app = j->n_orphans;
    const uint64_t n_events = n_agg + n_app;
    if (n_agg)
        fp_events_from_keys_stats(j->agg_keys, j->agg_stats, n_agg, events);
    struct no_flow_event *ev = reinterpret_cast<struct no_flow_event *>(events);
    for (uint64_t a = 0; a < n_app; a++) {
        std::memcpy(&ev[n_agg + a].key, align_key(j, j->app_row[a]),
                    sizeof(struct no_flow_key));
        std::memset(&ev[n_agg + a].stats, 0, sizeof(struct no_flow_stats));
    }
    for (uint32_t f = 0; f < j->n_feats; f++) {
        const struct fp_align_feat *m = &j->feats[f];
        if (m->n == 0 || !m->out)
            continue;
        const size_t vs = m->value_size;
        const uint32_t *eidx = j->feat_eidx + (j->feat_base[f] - n_agg);
        std::memset(m->out, 0, n_events * vs);
        for (uint64_t r = 0; r < m->n; r++) {
            const uint8_t *rec = m->recs + r * vs;
            const uint64_t e = eidx[r];
            std::memcpy(m->out + e * vs, rec, vs);
            if (e >= n_agg) {
                uint64_t ft, lt;
                std::memcpy(&ft, rec, 8);
                std::memcpy(&lt, rec + 8, 8);
                if (ft == 0)
                    ft = UINT64_MAX;  // the 0 -> U64_MAX sentinel (loader)
                if (ft < j->app_first[e - n_agg])
                    j->app_first[e - n_agg] = ft;
                if (lt > j->app_last[e - n_agg])
                    j->app_last[e - n_agg] = lt;
            }
        }
    }
    for (uint64_t a = 0; a < n_app; a++) {
        ev[n_agg + a].stats.first_seen_ns =
            j->app_first[a] == UINT64_MAX ? 0 : j->app_first[a];
        ev[n_agg + a].stats.last_seen_ns = j->app_last[a];
    }
}

// Scratch bytes fp_align_eviction needs for a drain of these row counts.
uint64_t fp_align_scratch_bytes(uint64_t n_agg, uint64_t n_feat_rows) {
    return align_scratch_bytes(n_agg + n_feat_rows, n_feat_rows);
}

// The align half of a drain in one call. `events` and every feature map's
// `out` hold `out_rows` rows; the caller sizes them for n_agg + every
// feature row (the most events a drain can give) and keeps the first
// n_events. Returns n_events, or -2 on bad arguments (the caller then runs
// the numpy twin).
int64_t fp_align_eviction(const uint8_t *agg_keys, const uint8_t *agg_stats,
                          uint64_t n_agg, const struct fp_align_feat *feats,
                          uint32_t n_feats, uint8_t *scratch,
                          uint64_t scratch_bytes, uint8_t *events,
                          uint64_t out_rows, struct fp_align_result *res) {
    struct align_job j;
    if (!res || (n_feats && !feats))
        return -2;
    std::memset(res, 0, sizeof(*res));
    if (align_setup(&j, agg_keys, agg_stats, n_agg, feats, n_feats))
        return -2;
    const size_t need = align_scratch_bytes(j.total, j.total - n_agg);
    if (scratch_bytes < need || (need && !scratch) ||
        reinterpret_cast<uintptr_t>(scratch) % sizeof(uint64_t) ||
        out_rows < j.total || (j.total && !events))
        return -2;
    for (uint32_t f = 0; f < n_feats; f++)
        if (j.feats[f].n && !j.feats[f].out)
            return -2;
    if (need)
        align_bind_scratch(&j, scratch);
    align_join(&j);
    align_fill(&j, events);
    res->n_events = n_agg + j.n_orphans;
    res->n_orphans = j.n_orphans;
    res->lex_fallback = j.lex;
    return static_cast<int64_t>(res->n_events);
}

// ===========================================================================
// Fused one-call eviction pipeline (fp_drain_to_resident). ONE native call
// owns the whole host chain of a drain: batched bpf(2) lookup-and-delete
// over every map, per-CPU columnar merge, hash-sort key join (the
// loader._join_keys twin), feature alignment, and — optionally — the direct
// resident-region pack replicating ShardedResidentStagingRing._fold_chunk.
// The call releases the GIL for its whole duration (ctypes), so drain lanes
// scale with cores instead of re-entering the interpreter between islands.
//
// SCHEDULING ONLY: the merge semantics are the very fp_merge_*_batch calls
// above (never a fifth merge form), the pack is the very fp_pack_resident
// above (never a fourth resident layout), and the join, compose and
// alignment are fp_align_eviction's own align_join / align_fill (the
// loader._join_keys twin, bit-exact). tests/test_native_pipeline.py pins the
// fused output against the Python-orchestrated chain.
//
// Buffer ownership: per-map drain scratch, merged/aligned arrays, the event
// compose buffer and the chunk table live in the fp_pipe handle and are
// valid until the next fp_drain_to_resident call (the caller copies at the
// EvictedFlows boundary — the same cached-buffer lifetime rule as
// drain_batched_arrays). The packed arena is malloc'd fresh per call and
// ownership passes to the caller (fp_buf_free) because packed regions may
// outlive the next drain in the overlap handoff.
// ===========================================================================

enum {
    FPK_STATS = 0, FPK_EXTRA = 1, FPK_DNS = 2, FPK_DROPS = 3,
    FPK_NEVENTS = 4, FPK_XLAT = 5, FPK_QUIC = 6,
};

#define FP_PIPE_MAX_MAPS 8
#define FP_PIPE_MAX_LADDER 8
#define FP_BPF_LOOKUP_AND_DELETE_BATCH 25

#if defined(__linux__)
#if defined(SYS_bpf)
#define FP_SYS_BPF SYS_bpf
#elif defined(__x86_64__)
#define FP_SYS_BPF 321
#elif defined(__aarch64__) || defined(__riscv)
#define FP_SYS_BPF 280
#elif defined(__powerpc64__)
#define FP_SYS_BPF 361
#elif defined(__s390x__)
#define FP_SYS_BPF 351
#endif
#endif

struct fp_pipe_map_cfg {
    int32_t fd;            // >= 0: drain via batched bpf(2); < 0: injected
    uint32_t kind;         // FPK_*
    uint32_t value_size;   // sizeof record struct (8-aligned)
    uint32_t n_cpus;       // per-CPU images per entry (1 = plain map)
    uint32_t max_entries;  // drain capacity bound
};

struct fp_pipe_ladder {
    uint32_t k;             // superbatch ladder entry
    uint32_t nr;            // regions per k-chunk (n_shards * k * lanes)
    const uint64_t *dicts;  // [nr] fp_dict handles (ring.kdicts mapping)
};

struct fp_pipe_pack_cfg {
    uint32_t n_ladder, batch_size, batch_per_region, slot_cap;
    uint32_t dns_cap, drop_cap, nk_cap, spill_cap;
    struct fp_pipe_ladder ladder[FP_PIPE_MAX_LADDER];  // ascending k; [0].k==1
};

struct fp_pipe_chunk {
    uint64_t row_start;   // first event row of this chunk
    uint64_t rows;        // rows packed by this chunk
    uint64_t arena_off;   // word offset of the chunk's first segment
    uint32_t k, n_segs, spills, resets;
};

struct fp_pipe_result {
    uint64_t n_events, n_agg, n_orphans, packed_rows;
    uint64_t drain_ns, merge_ns, join_ns, pack_ns;   // drain/merge: summed lane CPU
    uint64_t syscalls, lex_fallback, batch_err_mask, n_chunks;
    uint64_t arena_words, spill_rows, dict_resets, segs;
    const uint8_t *events;               // [n_events] no_flow_event (handle-owned)
    uint32_t *arena;                     // packed regions (caller frees: fp_buf_free)
    const struct fp_pipe_chunk *chunks;  // [n_chunks] (handle-owned)
    const uint8_t *aligned[FP_PIPE_MAX_MAPS];  // per map; NULL when absent/empty
    uint64_t map_rows[FP_PIPE_MAX_MAPS];       // drained rows per map
};

struct fp_pipe_buf {
    uint8_t *p;
    size_t cap;
};

static int pipe_reserve(struct fp_pipe_buf *b, size_t need) {
    if (need == 0 || b->cap >= need)
        return 0;
    size_t cap = b->cap ? b->cap : 4096;
    while (cap < need)
        cap *= 2;
    uint8_t *np = static_cast<uint8_t *>(realloc(b->p, cap));
    if (!np)
        return -1;
    b->p = np;
    b->cap = cap;
    return 0;
}

struct fp_pipe_map_state {
    int32_t fd;
    uint32_t kind, value_size, n_cpus, max_entries;
    struct fp_pipe_buf keys, vals, merged, aligned;
    uint32_t n;       // drained rows this call (injected rows when fd < 0)
    int32_t err;      // last drain/merge errno (0 = ok)
    uint64_t drain_ns, merge_ns, syscalls;
    uint8_t tok_a[64], tok_b[64];  // batch iteration tokens (>= key size)
};

struct fp_pipe {
    uint32_t n_maps, lanes;
    struct fp_pipe_map_state maps[FP_PIPE_MAX_MAPS];
    struct fp_pipe_buf events, join;
    struct fp_pipe_chunk *chunks;
    size_t chunks_cap;
};

static uint64_t pipe_now_ns(void) {
#if defined(__linux__)
    struct timespec ts;
    clock_gettime(CLOCK_MONOTONIC, &ts);
    return static_cast<uint64_t>(ts.tv_sec) * 1000000000ull +
           static_cast<uint64_t>(ts.tv_nsec);
#else
    return 0;
#endif
}

void *fp_pipe_new(const struct fp_pipe_map_cfg *cfgs, uint32_t n_maps,
                  uint32_t lanes) {
    if (!cfgs || n_maps == 0 || n_maps > FP_PIPE_MAX_MAPS)
        return NULL;
    if (cfgs[0].kind != FPK_STATS || cfgs[0].n_cpus != 1)
        return NULL;  // map 0 is the aggregation map, used verbatim
    for (uint32_t i = 0; i < n_maps; i++) {
        if (cfgs[i].kind > FPK_QUIC || cfgs[i].n_cpus == 0 ||
            cfgs[i].max_entries == 0 || cfgs[i].value_size == 0 ||
            cfgs[i].value_size % 8 != 0)  // padded stride == struct size
            return NULL;
    }
    struct fp_pipe *p =
        static_cast<struct fp_pipe *>(calloc(1, sizeof(struct fp_pipe)));
    if (!p)
        return NULL;
    p->n_maps = n_maps;
    p->lanes = lanes ? lanes : 1;
    for (uint32_t i = 0; i < n_maps; i++) {
        p->maps[i].fd = cfgs[i].fd;
        p->maps[i].kind = cfgs[i].kind;
        p->maps[i].value_size = cfgs[i].value_size;
        p->maps[i].n_cpus = cfgs[i].n_cpus;
        p->maps[i].max_entries = cfgs[i].max_entries;
    }
    return p;
}

void fp_pipe_free(void *h) {
    if (!h)
        return;
    struct fp_pipe *p = static_cast<struct fp_pipe *>(h);
    for (uint32_t i = 0; i < p->n_maps; i++) {
        free(p->maps[i].keys.p);
        free(p->maps[i].vals.p);
        free(p->maps[i].merged.p);
        free(p->maps[i].aligned.p);
    }
    free(p->events.p);
    free(p->join.p);
    free(p->chunks);
    free(p);
}

void fp_buf_free(void *ptr) { free(ptr); }

// Test injection for fd < 0 maps: pre-load one drain's (keys, vals)
// as if the batched syscall had produced them. vals layout is the kernel's:
// n rows x n_cpus images x value_size bytes, contiguous.
int fp_pipe_set_drained(void *h, uint32_t idx, const uint8_t *keys,
                        const uint8_t *vals, uint32_t n) {
    struct fp_pipe *p = static_cast<struct fp_pipe *>(h);
    if (!p || idx >= p->n_maps || p->maps[idx].fd >= 0)
        return -1;
    struct fp_pipe_map_state *m = &p->maps[idx];
    size_t ks = sizeof(struct no_flow_key);
    size_t vstride = static_cast<size_t>(m->value_size) * m->n_cpus;
    if (pipe_reserve(&m->keys, n * ks) || pipe_reserve(&m->vals, n * vstride))
        return -1;
    if (n) {
        std::memcpy(m->keys.p, keys, n * ks);
        std::memcpy(m->vals.p, vals, n * vstride);
    }
    m->n = n;
    return 0;
}

// One map's batched lookup-and-delete loop — the drain_batched_arrays twin
// (same attr layout, same token handoff, same partial-round banking). The
// caller pre-probed batch support through the Python chain's first drain,
// so a hard error here is recorded, never retried per-key.
static void pipe_drain_map(struct fp_pipe_map_state *m) {
    m->err = 0;
    m->syscalls = 0;
    if (m->fd < 0)
        return;  // injected rows (fp_pipe_set_drained) stay as-is
    m->n = 0;
#if defined(__linux__) && defined(FP_SYS_BPF)
    const size_t ks = sizeof(struct no_flow_key);
    const size_t vstride = static_cast<size_t>(m->value_size) * m->n_cpus;
    if (pipe_reserve(&m->keys, static_cast<size_t>(m->max_entries) * ks) ||
        pipe_reserve(&m->vals, static_cast<size_t>(m->max_entries) * vstride)) {
        m->err = ENOMEM;
        return;
    }
    struct {
        uint64_t in_batch, out_batch, keys, values;
        uint32_t count, map_fd;
        uint64_t elem_flags, flags;
    } attr;
    bool first = true;
    uint32_t total = 0;
    while (total < m->max_entries) {
        std::memset(&attr, 0, sizeof(attr));
        attr.in_batch =
            first ? 0 : static_cast<uint64_t>(reinterpret_cast<uintptr_t>(m->tok_a));
        attr.out_batch =
            static_cast<uint64_t>(reinterpret_cast<uintptr_t>(m->tok_b));
        attr.keys = static_cast<uint64_t>(
            reinterpret_cast<uintptr_t>(m->keys.p + static_cast<size_t>(total) * ks));
        attr.values = static_cast<uint64_t>(reinterpret_cast<uintptr_t>(
            m->vals.p + static_cast<size_t>(total) * vstride));
        attr.count = m->max_entries - total;
        attr.map_fd = static_cast<uint32_t>(m->fd);
        long rc = syscall(FP_SYS_BPF, FP_BPF_LOOKUP_AND_DELETE_BATCH, &attr,
                          static_cast<unsigned long>(sizeof(attr)));
        int err = rc < 0 ? errno : 0;
        m->syscalls++;
        if (rc == 0 || err == ENOENT) {
            total += attr.count;  // partial counts on ENOENT are valid
        } else {
            m->err = err;  // keep banked rounds: their entries are deleted
            break;
        }
        if (rc < 0 || attr.count == 0)
            break;  // drained to empty
        std::memcpy(m->tok_a, m->tok_b, sizeof(m->tok_a));
        first = false;
    }
    m->n = total;
#else
    m->err = 38;  // ENOSYS: no bpf(2) on this platform — fd<0 mode only
#endif
}

static void pipe_merge_map(struct fp_pipe_map_state *m) {
    if (m->kind == FPK_STATS || m->n == 0)
        return;  // aggregation rows are used verbatim (no per-CPU images)
    size_t need = static_cast<size_t>(m->n) * m->value_size;
    if (pipe_reserve(&m->merged, need)) {
        m->err = ENOMEM;
        return;
    }
    switch (m->kind) {
    case FPK_EXTRA:
        fp_merge_extra_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    case FPK_DNS:
        fp_merge_dns_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    case FPK_DROPS:
        fp_merge_drops_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    case FPK_NEVENTS:
        fp_merge_nevents_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    case FPK_XLAT:
        fp_merge_xlat_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    case FPK_QUIC:
        fp_merge_quic_batch(m->vals.p, m->n, m->n_cpus, m->merged.p);
        break;
    default:
        break;
    }
}

static void pipe_run_map(struct fp_pipe_map_state *m) {
    uint64_t t0 = pipe_now_ns();
    pipe_drain_map(m);
    uint64_t t1 = pipe_now_ns();
    pipe_merge_map(m);
    m->drain_ns = t1 - t0;
    m->merge_ns = pipe_now_ns() - t1;
}

#if defined(__linux__)
struct fp_pipe_job {
    struct fp_pipe *p;
    uint32_t next;
    pthread_mutex_t mu;
};

static void *pipe_worker(void *arg) {
    struct fp_pipe_job *job = static_cast<struct fp_pipe_job *>(arg);
    for (;;) {
        pthread_mutex_lock(&job->mu);
        uint32_t i = job->next++;
        pthread_mutex_unlock(&job->mu);
        if (i >= job->p->n_maps)
            return NULL;
        pipe_run_map(&job->p->maps[i]);
    }
}
#endif

static int64_t pipe_pack(struct fp_pipe *p, const struct fp_pipe_pack_cfg *pk,
                         struct fp_pipe_result *res) {
    const uint64_t n_events = res->n_events;
    if (pk->n_ladder == 0 || pk->n_ladder > FP_PIPE_MAX_LADDER ||
        pk->ladder[0].k != 1 || pk->batch_per_region == 0 ||
        pk->spill_cap == 0 || pk->nk_cap == 0)
        return -2;
    const size_t region_words =
        FP_RESIDENT_HDR + static_cast<size_t>(pk->batch_per_region) * FP_HOT_WORDS +
        pk->dns_cap + static_cast<size_t>(pk->drop_cap) * 2 +
        static_cast<size_t>(pk->nk_cap) * FP_NK_WORDS +
        static_cast<size_t>(pk->spill_cap) * FP_DENSE_WORDS;
    // per-kind aligned feature bases the resident pack consumes (nevents
    // rides EvictedFlows only — the fold lanes never carry it)
    const uint8_t *ali[FPK_QUIC + 1] = {NULL, NULL, NULL, NULL, NULL, NULL, NULL};
    for (uint32_t i = 1; i < p->n_maps; i++)
        if (p->maps[i].n)
            ali[p->maps[i].kind] = p->maps[i].aligned.p;
    uint32_t *arena = NULL;
    size_t arena_cap_words = 0, arena_words = 0;
    uint64_t row = 0, starts[1u << 10];
    while (row < n_events) {
        const uint64_t remaining = n_events - row;
        // the ring's ladder rule: largest available k whose k*batch fits
        uint32_t sel = 0;
        for (uint32_t L = 0; L < pk->n_ladder; L++)
            if (static_cast<uint64_t>(pk->ladder[L].k) * pk->batch_size <=
                remaining)
                sel = L;
        const struct fp_pipe_ladder *lad = &pk->ladder[sel];
        const uint32_t nr = lad->nr;
        if (nr == 0 || nr > (1u << 10)) {
            free(arena);
            return -2;
        }
        const uint64_t take =
            remaining < static_cast<uint64_t>(lad->k) * pk->batch_size
                ? remaining
                : static_cast<uint64_t>(lad->k) * pk->batch_size;
        // chunk bookkeeping
        if (res->n_chunks >= p->chunks_cap) {
            size_t cap = p->chunks_cap ? p->chunks_cap * 2 : 16;
            struct fp_pipe_chunk *nc = static_cast<struct fp_pipe_chunk *>(
                realloc(p->chunks, cap * sizeof(*nc)));
            if (!nc) {
                free(arena);
                return -1;
            }
            p->chunks = nc;
            p->chunks_cap = cap;
        }
        struct fp_pipe_chunk *ch = &p->chunks[res->n_chunks];
        std::memset(ch, 0, sizeof(*ch));
        ch->row_start = row;
        ch->rows = take;
        ch->k = lad->k;
        ch->arena_off = arena_words;
        for (uint32_t i = 0; i < nr; i++)
            starts[i] = 0;
        bool done = false;
        while (!done) {
            // one segment = one shipped ring-slot image of nr regions (the
            // continuation loop of _fold_chunk)
            size_t need_words = arena_words + static_cast<size_t>(nr) * region_words;
            if (need_words > arena_cap_words) {
                size_t cap = arena_cap_words ? arena_cap_words : 65536;
                while (cap < need_words)
                    cap *= 2;
                uint32_t *na =
                    static_cast<uint32_t *>(realloc(arena, cap * sizeof(uint32_t)));
                if (!na) {
                    free(arena);
                    return -1;
                }
                arena = na;
                arena_cap_words = cap;
            }
            done = true;
            for (uint32_t i = 0; i < nr; i++) {
                uint32_t *region = arena + arena_words + i * region_words;
                const uint64_t lo = row + take * i / nr;
                const uint64_t hi = row + take * (i + 1) / nr;
                const uint64_t len = hi - lo;
                if (starts[i] >= len) {
                    // exhausted region in a continuation segment: the
                    // zero_resident_region mask, done as a full memset so
                    // the arena is deterministic (the device reads only the
                    // validity words either way)
                    std::memset(region, 0, region_words * sizeof(uint32_t));
                    continue;
                }
                fp_dict *d = reinterpret_cast<fp_dict *>(
                    static_cast<uintptr_t>(lad->dicts[i]));
                if (d->next_slot >= pk->slot_cap) {
                    fp_dict_reset(d);  // per-region epoch roll (_fold_chunk)
                    ch->resets++;
                }
                int64_t consumed = fp_pack_resident(
                    reinterpret_cast<const uint8_t *>(
                        reinterpret_cast<const struct no_flow_event *>(
                            p->events.p) + lo),
                    starts[i], len,
                    ali[FPK_EXTRA] ? ali[FPK_EXTRA] + lo * sizeof(struct no_extra_rec) : NULL,
                    ali[FPK_DNS] ? ali[FPK_DNS] + lo * sizeof(struct no_dns_rec) : NULL,
                    ali[FPK_DROPS] ? ali[FPK_DROPS] + lo * sizeof(struct no_drops_rec) : NULL,
                    ali[FPK_XLAT] ? ali[FPK_XLAT] + lo * sizeof(struct no_xlat_rec) : NULL,
                    ali[FPK_QUIC] ? ali[FPK_QUIC] + lo * sizeof(struct no_quic_rec) : NULL,
                    d, region, pk->batch_per_region, pk->dns_cap, pk->drop_cap,
                    pk->nk_cap, pk->spill_cap);
                if (consumed <= 0) {
                    free(arena);
                    return -3;  // no progress: caps violate the guarantee
                }
                ch->spills += region[2];
                starts[i] += static_cast<uint64_t>(consumed);
                if (starts[i] < len)
                    done = false;
            }
            arena_words += static_cast<size_t>(nr) * region_words;
            ch->n_segs++;
        }
        res->spill_rows += ch->spills;
        res->dict_resets += ch->resets;
        res->segs += ch->n_segs;
        res->n_chunks++;
        row += take;
    }
    res->arena = arena;
    res->arena_words = arena_words;
    res->packed_rows = n_events;
    res->chunks = p->chunks;
    return 0;
}

// The fused drain: every map's batched drain + per-CPU merge (fanned out
// over `lanes` worker threads), the key join + feature alignment, and —
// when `pack` is non-NULL — the resident-region pack. Returns n_events
// (>= 0) or a negative error (-1 alloc, -2 bad args, -3 pack stuck).
int64_t fp_drain_to_resident(void *h, const struct fp_pipe_pack_cfg *pack,
                             struct fp_pipe_result *res) {
    struct fp_pipe *p = static_cast<struct fp_pipe *>(h);
    if (!p || !res)
        return -2;
    std::memset(res, 0, sizeof(*res));
    // ---- drain + merge (per-map, worker fan-out) ----
    uint32_t nw = p->lanes < p->n_maps ? p->lanes : p->n_maps;
#if defined(__linux__)
    if (nw > 1) {
        struct fp_pipe_job job;
        job.p = p;
        job.next = 0;
        pthread_mutex_init(&job.mu, NULL);
        pthread_t tids[FP_PIPE_MAX_MAPS];
        uint32_t started = 0;
        for (uint32_t t = 0; t + 1 < nw; t++)
            if (pthread_create(&tids[started], NULL, pipe_worker, &job) == 0)
                started++;
        pipe_worker(&job);  // the calling thread is a worker too
        for (uint32_t t = 0; t < started; t++)
            pthread_join(tids[t], NULL);
        pthread_mutex_destroy(&job.mu);
    } else
#endif
    {
        for (uint32_t i = 0; i < p->n_maps; i++)
            pipe_run_map(&p->maps[i]);
    }
    for (uint32_t i = 0; i < p->n_maps; i++) {
        struct fp_pipe_map_state *m = &p->maps[i];
        res->drain_ns += m->drain_ns;
        res->merge_ns += m->merge_ns;
        res->syscalls += m->syscalls;
        res->map_rows[i] = m->n;
        if (m->err == ENOMEM)
            return -1;
        if (m->err)
            res->batch_err_mask |= 1ull << i;
    }
    const uint64_t n_agg = p->maps[0].n;
    // ---- join + event compose + alignment (the fp_align_eviction routine) ----
    uint64_t t_join = pipe_now_ns();
    struct fp_align_feat feats[FP_ALIGN_MAX_FEATS];
    for (uint32_t mi = 1; mi < p->n_maps; mi++) {
        struct fp_pipe_map_state *m = &p->maps[mi];
        feats[mi - 1] = {m->keys.p, m->merged.p, NULL, m->n, m->value_size};
    }
    struct align_job j;
    if (align_setup(&j, p->maps[0].keys.p, p->maps[0].vals.p, n_agg, feats,
                    p->n_maps - 1))
        return -2;
    if (j.total > n_agg) {
        if (pipe_reserve(&p->join, align_scratch_bytes(j.total, j.total - n_agg)))
            return -1;
        align_bind_scratch(&j, p->join.p);
    }
    align_join(&j);
    const uint64_t n_events = n_agg + j.n_orphans;
    res->n_events = n_events;
    res->n_agg = n_agg;
    res->n_orphans = j.n_orphans;
    res->lex_fallback = j.lex;
    if (pipe_reserve(&p->events, n_events * sizeof(struct no_flow_event)))
        return -1;
    for (uint32_t mi = 1; mi < p->n_maps; mi++) {
        struct fp_pipe_map_state *m = &p->maps[mi];
        if (m->n == 0 || n_events == 0)
            continue;
        if (pipe_reserve(&m->aligned, n_events * m->value_size))
            return -1;
        j.feats[mi - 1].out = m->aligned.p;
        res->aligned[mi] = m->aligned.p;
    }
    align_fill(&j, p->events.p);
    res->events = p->events.p;
    res->join_ns = pipe_now_ns() - t_join;
    // ---- resident-region pack (_fold_chunk twin) ----
    if (pack && n_events) {
        uint64_t t_pack = pipe_now_ns();
        int64_t rc = pipe_pack(p, pack, res);
        res->pack_ns = pipe_now_ns() - t_pack;
        if (rc < 0)
            return rc;
    }
    return static_cast<int64_t>(n_events);
}

#ifndef FP_ABI_VERSION
#define FP_ABI_VERSION 11
#endif

uint32_t fp_abi_version(void) { return FP_ABI_VERSION; }

}  // extern "C"

"""Traffic, all of it from --seed: the flow universe, the record stream, and
the stream as the kernel maps would hold it.

`Universe` and `synthesize` are `chip_smoke.py`'s `Traffic.__init__` and
`Traffic.window` (PR 21, ran on the chip), split so that a stream is drawn
once in set-up and handed out in slices. A `Stream` keeps the records the way
a drain finds them: the aggregation map's keys and values, and one per-CPU map
per feature holding only the flows that have the feature — so the fetcher's
hand-over runs the product's own decode (per-CPU merge and key join), not a
pre-built `EvictedFlows`.

Every shape comes from the mix file (`cellbench/traffic/<mix>.json`); nothing
here knows a mix by name.
"""

from __future__ import annotations

import numpy as np

from netobserv_tpu.model import binfmt

FEATURES = ("extra", "dns", "drops", "xlat", "quic")
_KEY_BYTES = binfmt.FLOW_KEY_DTYPE.itemsize


class Universe:
    """`universe` distinct 5-tuples in a fixed popularity order: key i is
    drawn with probability ~ (i+1)^-zipf_a, for the whole run."""

    def __init__(self, rng: np.random.Generator, universe: int,
                 zipf_a: float, v6_share: float = 0.03):
        from netobserv_tpu.model.columnar import pack_key_words

        n = self.n = universe
        n_src = max(n // 8, 16)
        n_dst = max(n // 256, 16)
        src_pool = rng.choice(1 << 24, n_src, replace=False).astype(np.uint32)
        dst_pool = rng.choice(1 << 24, n_dst, replace=False).astype(np.uint32)
        self.src_of_key = rng.integers(0, n_src, n)
        keys = np.zeros(n, binfmt.FLOW_KEY_DTYPE)
        v6 = rng.random(n) < v6_share
        for field, pool_idx, pool, net in (
                ("src_ip", self.src_of_key, src_pool, 10),
                ("dst_ip", rng.integers(0, n_dst, n), dst_pool, 172)):
            ip = np.zeros((n, 16), np.uint8)
            addr = pool[pool_idx]
            ip[:, 12] = net
            ip[:, 13] = (addr >> 16) & 0xFF
            ip[:, 14] = (addr >> 8) & 0xFF
            ip[:, 15] = addr & 0xFF
            ip[:, 10:12] = np.where(v6[:, None], 0, 0xFF)
            ip[v6, 0] = 0x20
            ip[v6, 1] = 0x01
            keys[field] = ip
        keys["src_port"] = rng.integers(1024, 65536, n)
        keys["dst_port"] = rng.choice(
            np.array([53, 80, 443, 5432, 6443, 8080, 9092], np.uint16), n)
        keys["proto"] = np.where(rng.random(n) < 0.8, 6, 17)
        words = pack_key_words(keys)
        uniq = np.unique(words.view([("w", "u4", words.shape[1])]))
        if len(uniq) != n:
            raise ValueError(f"universe holds {len(uniq)} distinct keys, "
                             f"wanted {n}: seed collision")
        self.keys = keys
        self.v6 = v6
        p = np.arange(1, n + 1, dtype=np.float64) ** -zipf_a
        self.cdf = np.cumsum(p / p.sum())

    def draw(self, rng: np.random.Generator, n: int) -> np.ndarray:
        return np.minimum(np.searchsorted(self.cdf, rng.random(n)),
                          self.n - 1)


def synthesize(rng: np.random.Generator, uni: Universe, idx: np.ndarray):
    """Records for the key draws `idx`: events plus aligned feature lanes,
    every lane filled as `chip_smoke.py` fills them."""
    n = len(idx)
    ev = np.zeros(n, binfmt.FLOW_EVENT_DTYPE)
    ev["key"] = uni.keys[idx]
    st = ev["stats"]
    st["bytes"] = rng.integers(64, 9001, n)
    # ~0.5% elephants: more packets than a hot row's 11 bits hold, so the
    # full-width spill lane carries rows in steady state too
    st["packets"] = np.where(rng.random(n) < 0.005,
                             rng.integers(2048, 4096, n),
                             rng.integers(1, 12, n))
    tcp = ev["key"]["proto"] == 6
    st["tcp_flags"] = np.where(tcp, rng.integers(0, 1 << 9, n), 0)
    st["dscp"] = rng.integers(0, 64, n)
    st["eth_protocol"] = np.where(uni.v6[idx], 0x86DD, 0x0800)
    st["if_index_first"] = 2
    st["first_seen_ns"] = 1_000_000_000
    st["last_seen_ns"] = 1_000_000_000 + rng.integers(0, 5_000_000_000, n)
    extra = np.zeros(n, binfmt.EXTRA_REC_DTYPE)
    extra["rtt_ns"] = np.where(rng.random(n) < 0.30,
                               rng.integers(1, 5_000, n) * 1000, 0)
    dns = np.zeros(n, binfmt.DNS_REC_DTYPE)
    dns["latency_ns"] = np.where(rng.random(n) < 0.05,
                                 rng.integers(1, 2_000, n) * 1000, 0)
    drops = np.zeros(n, binfmt.DROPS_REC_DTYPE)
    dropped = rng.random(n) < 0.02
    drops["bytes"] = np.where(dropped, rng.integers(1, 1500, n), 0)
    drops["packets"] = np.where(dropped, rng.integers(1, 4, n), 0)
    drops["latest_cause"] = np.where(dropped, rng.integers(2, 80, n), 0)
    xlat = np.zeros(n, binfmt.XLAT_REC_DTYPE)
    nat = rng.random(n) < 0.03
    xlat["src_ip"][nat] = ev["key"]["src_ip"][nat]
    xlat["dst_ip"][nat] = ev["key"]["dst_ip"][nat]
    quic = np.zeros(n, binfmt.QUIC_REC_DTYPE)
    is_quic = (~tcp) & (rng.random(n) < 0.10)
    quic["version"] = np.where(is_quic, 1, 0)
    quic["seen_long_hdr"] = is_quic
    present = dict(extra=extra["rtt_ns"] > 0, dns=dns["latency_ns"] > 0,
                   drops=dropped, xlat=nat, quic=is_quic)
    return ev, dict(extra=extra, dns=dns, drops=drops, xlat=xlat,
                    quic=quic), present


class MapDump:
    """What one drain finds in the kernel maps: `decode_eviction`'s input."""

    __slots__ = ("agg_keys", "agg_vals", "drained", "n")

    def __init__(self, agg_keys, agg_vals, drained):
        self.agg_keys, self.agg_vals, self.drained = agg_keys, agg_vals, drained
        self.n = len(agg_keys)

    def events(self) -> np.ndarray:
        """The records of this dump as flow events (for the oracle)."""
        ev = np.zeros(self.n, binfmt.FLOW_EVENT_DTYPE)
        ev["key"] = self.agg_keys.view(binfmt.FLOW_KEY_DTYPE).reshape(-1)
        ev["stats"] = self.agg_vals[:, 0]
        return ev


class Stream:
    """`records` seeded records in map form, handed out in slices that wrap
    round. With `new_key_share` > 0 that share of the rows is marked, and each
    hand-over stamps a 5-tuple never seen before into the marked rows (fresh
    source address and port from a counter; the destination stays the
    universe's), in one vectorised write on a copy."""

    def __init__(self, rng: np.random.Generator, uni: Universe, records: int,
                 map_cpus: int, new_key_share: float = 0.0):
        self.n = records
        ev, feats, present = synthesize(rng, uni, uni.draw(rng, records))
        self.agg_keys = np.ascontiguousarray(ev["key"]).view(
            np.uint8).reshape(records, _KEY_BYTES)
        self.agg_vals = np.ascontiguousarray(ev["stats"]).reshape(records, 1)
        self.rows: dict[str, np.ndarray] = {}
        self.partials: dict[str, np.ndarray] = {}
        for attr in FEATURES:
            rows = np.nonzero(present[attr])[0]
            part = np.zeros((len(rows), map_cpus), feats[attr].dtype)
            part[np.arange(len(rows)),
                 rng.integers(0, map_cpus, len(rows))] = feats[attr][rows]
            self.rows[attr], self.partials[attr] = rows, part
        self.new_rows = (np.nonzero(rng.random(records) < new_key_share)[0]
                         if new_key_share > 0 else None)
        self._pos = 0
        self._fresh = 0

    def take(self, n: int) -> MapDump:
        """The next `n` records (n <= the stream's length)."""
        lo = self._pos
        if lo + n > self.n:         # wrap: the tail is skipped, never split
            lo = 0
        hi = lo + n
        self._pos = hi % self.n
        keys = self.agg_keys[lo:hi]
        if self.new_rows is not None:
            a, b = np.searchsorted(self.new_rows, (lo, hi))
            rows = self.new_rows[a:b] - lo
            keys = keys.copy()
            fresh = self._fresh + np.arange(len(rows), dtype=np.uint64)
            self._fresh += len(rows)
            # src_ip bytes 4..9 and src_port: 64 bits of counter, and byte 0
            # set to 0xFD (no universe address has it), so no stamped key
            # repeats or meets the universe
            keys[rows, 0] = 0xFD
            for j in range(6):
                keys[rows, 4 + j] = (fresh >> np.uint64(8 * j)) & 0xFF
            keys[rows, 32] = (fresh >> np.uint64(48)) & 0xFF
            keys[rows, 33] = (fresh >> np.uint64(56)) & 0xFF
        drained = {}
        for attr in FEATURES:
            a, b = np.searchsorted(self.rows[attr], (lo, hi))
            drained[attr] = (keys[self.rows[attr][a:b] - lo],
                             self.partials[attr][a:b])
        return MapDump(keys, self.agg_vals[lo:hi], drained)

"""Closed loop: evictions of a fixed size, one always ready, handed whenever
fewer than `max_unacked` are handed and not yet exported. The fetcher asks
`MapTracer.flush()` — the product's own map-full path — for the next drain, so
the CACHE_ACTIVE_TIMEOUT timer never paces the cell; the back-pressure is the
fetcher's, because `MapTracer` drops on a full queue."""


class Generator:
    def __init__(self, params: dict):
        self.eviction = int(params["eviction"])
        self.max_unacked = int(params["max_unacked"])

    def start(self, now: float) -> None:
        pass

    def due(self, now: float, unacked: int) -> int:
        return self.eviction if unacked < self.max_unacked else 0

    def wants_drain(self, unacked: int) -> bool:
        return unacked < self.max_unacked

"""Open loop: records accrue in the maps at a fixed `rate_per_s`, whatever the
agent does, and each drain of the CACHE_ACTIVE_TIMEOUT timer takes what has
accrued. The schedule is the clock's: it never slows when the agent does. One
drain never takes more than `max_eviction` records (a map's capacity); what is
over stays due."""


class Generator:
    def __init__(self, params: dict):
        self.rate = float(params["rate_per_s"])
        self.max_eviction = int(params["max_eviction"])
        self.t0 = None
        self.taken = 0

    def start(self, now: float) -> None:
        self.t0, self.taken = now, 0

    def due(self, now: float, unacked: int) -> int:
        n = min(int((now - self.t0) * self.rate) - self.taken,
                self.max_eviction)
        if n <= 0:
            return 0
        self.taken += n
        return n

    def wants_drain(self, unacked: int) -> bool:
        return False

"""The agent under test, the fetcher that stands for the kernel maps, and the
wrappers the benchmark puts round the product's layer boundaries.

`AgentUnderTest`, `scrape`, `http_json` and `wait_for` are `chip_smoke.py`'s
(PR 21, ran on the chip): the agent is built the way `python -m netobserv_tpu`
builds it from the environment with EXPORT=tpu-sketch, and only the fetcher is
substituted. Everything the benchmark times, it times from here — the program
gets no new option and no new span.
"""

from __future__ import annotations

import contextlib
import json
import os
import threading
import time
import urllib.error
import urllib.request

import numpy as np


class Failed(Exception):
    """The run cannot go on; the message says why."""


def note(msg: str) -> None:
    """An earlier line of stdout: anything but the result."""
    print(f"# {msg}", flush=True)


def process_age_s() -> float:
    """Seconds since this process started, from /proc (so imports count)."""
    with open("/proc/self/stat") as f:
        start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
    with open("/proc/uptime") as f:
        up = float(f.read().split()[0])
    return up - start_ticks / os.sysconf("SC_CLK_TCK")


def thread_cpu_s() -> dict:
    """CPU seconds (user + system) of every thread of this process, by tid,
    with the thread's Python name where it has one."""
    tck = os.sysconf("SC_CLK_TCK")
    named = {t.native_id: t.name for t in threading.enumerate()}
    out = {}
    for tid in os.listdir("/proc/self/task"):
        try:
            with open(f"/proc/self/task/{tid}/stat") as f:
                head, tail = f.read().rsplit(")", 1)
        except OSError:
            continue            # the thread ended between listdir and open
        fields = tail.split()
        out[int(tid)] = (named.get(int(tid)) or head.split("(", 1)[1],
                         (int(fields[11]) + int(fields[12])) / tck)
    return out


def http_json(port: int, path: str):
    try:
        with urllib.request.urlopen(f"http://127.0.0.1:{port}{path}",
                                    timeout=30) as r:
            return r.status, json.loads(r.read())
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read() or b"{}")


def scrape(port: int, prefix: str) -> dict:
    """/metrics as {(name, (label values...)): value}, prefix stripped."""
    from prometheus_client.parser import text_string_to_metric_families

    with urllib.request.urlopen(f"http://127.0.0.1:{port}/metrics",
                                timeout=30) as r:
        text = r.read().decode()
    out = {}
    for fam in text_string_to_metric_families(text):
        for s in fam.samples:
            if s.name.startswith(prefix):
                out[(s.name[len(prefix):],
                     tuple(v for _, v in sorted(s.labels.items())))] = s.value
    return out


def wait_for(pred, timeout_s: float, what: str, poll_s: float = 0.05):
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        got = pred()
        if got:
            return got
        time.sleep(poll_s)
    raise Failed(f"timed out after {timeout_s:.0f}s waiting for {what}")


# --------------------------------------------------------------------------
# spans: the benchmark's own, recorded in memory; in a traced run each also
# opens a jax.profiler.TraceAnnotation, so it lies on the profiler's clock
# --------------------------------------------------------------------------

class Spans:
    """(name, start, end) by name, perf_counter seconds. `annotate` is set
    for the traced run only; un-annotated, a span is two clock reads and a
    list append per call of a layer boundary (per eviction, per pack region,
    per dispatch — never per record)."""

    PREFIX = "cellbench:"

    def __init__(self, annotate: bool):
        self._annotation = None
        if annotate:
            import jax.profiler
            self._annotation = jax.profiler.TraceAnnotation
        self.by_name: dict[str, list] = {}
        self.calls: list[str] = []      # every watched jit call, in order

    @contextlib.contextmanager
    def span(self, name: str):
        ann = None
        if self._annotation is not None:
            ann = self._annotation(self.PREFIX + name)
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann is not None:
                ann.__exit__(None, None, None)
            # list.append is atomic under the GIL; setdefault races only on
            # the first span of a name, which set-up makes on one thread
            self.by_name.setdefault(name, []).append((t0, t1))

    def durations(self, name: str, lo: float, hi: float) -> list:
        """Durations of the spans of `name` that ended inside [lo, hi]."""
        return [b - a for a, b in self.by_name.get(name, ()) if lo <= b <= hi]


# --------------------------------------------------------------------------
# the fetcher: the kernel maps, filled by the mix's generator
# --------------------------------------------------------------------------

class Offer:
    """Hand-over accounting shared by the fetcher (drain thread) and the
    export wrapper (export thread)."""

    def __init__(self):
        self.lock = threading.Lock()
        self.handed_records = 0
        self.handed = 0             # evictions handed to the agent
        self.exported = 0           # evictions whose export_evicted returned
        self.asked_empty = 0        # drains that found nothing while wanted
        self.gen_s_max = 0.0        # longest the generator held a drain up
        self.samples: list = []     # (t_handover, t_export_in, t_export_out, n)

    @property
    def unacked(self) -> int:
        return self.handed - self.exported


def make_fetcher(spans: Spans, offer: Offer):
    from netobserv_tpu.datapath import loader
    from netobserv_tpu.datapath.fetcher import EvictedFlows, FakeFetcher
    from netobserv_tpu.model import binfmt

    class MapFetcher(FakeFetcher):
        """`lookup_and_delete` hands over what the generator says is due, in
        map form, through the product's own `decode_eviction` (per-CPU merge
        and key join) — the drain thread pays what it pays on a kernel."""

        def __init__(self):
            super().__init__()
            self.generator = None       # the mix's loop, while it offers
            self.stream = None
            self.queued: list = []      # MapDumps handed before anything due
            self.flush = lambda: None   # MapTracer.flush, once the agent is up

        def lookup_and_delete(self):
            t_call = time.perf_counter()
            gen = self.generator
            dump = None
            if self.queued:
                dump = self.queued.pop(0)
            elif gen is not None:
                n = gen.due(t_call, offer.unacked)
                if n:
                    dump = self.stream.take(n)
                elif gen.wants_drain(offer.unacked):
                    offer.asked_empty += 1
            if dump is None:
                return EvictedFlows(np.zeros(0, binfmt.FLOW_EVENT_DTYPE))
            t_gen = time.perf_counter()
            with spans.span("decode"):
                evicted = loader.decode_eviction(
                    dump.agg_keys, dump.agg_vals, dump.drained)
            evicted.decode_stats["seconds"] = time.perf_counter() - t_gen
            evicted.cellbench_handover = t_call
            with offer.lock:
                offer.handed += 1
                offer.handed_records += dump.n
                offer.gen_s_max = max(offer.gen_s_max, t_gen - t_call)
            if self.queued or (gen is not None
                               and gen.wants_drain(offer.unacked)):
                self.flush()
            return evicted

    return MapFetcher()


def wrap_product(spans: Spans) -> None:
    """The traced run's extra spans, put round the product's functions from
    here: pack (every `pack_resident` call) and jit call (every watched
    executable, by name, and logged in call order for xtrace.name_modules).
    Call before the exporter exists: its warm thread calls watched jits."""
    from netobserv_tpu.datapath import flowpack
    from netobserv_tpu.utils import retrace

    pack = flowpack.pack_resident

    def pack_resident(*a, **kw):
        with spans.span("pack"):
            return pack(*a, **kw)
    flowpack.pack_resident = pack_resident

    call = retrace.Watched.__call__

    def watched_call(w, *a, **kw):
        spans.calls.append(w.name)
        with spans.span("jit_call:" + w.name):
            return call(w, *a, **kw)
    retrace.Watched.__call__ = watched_call


# --------------------------------------------------------------------------
# the agent under test
# --------------------------------------------------------------------------

class AgentUnderTest:
    """A FlowsAgent built the way `python -m netobserv_tpu` builds it from
    the environment, with the fetcher substituted and the sink observed."""

    def __init__(self, spans: Spans, offer: Offer, wrap_publish: bool = False):
        from netobserv_tpu.agent.agent import FlowsAgent
        from netobserv_tpu.config import load_config
        from netobserv_tpu.exporter.tpu_sketch import TpuSketchExporter
        from netobserv_tpu.metrics.registry import Metrics, MetricsSettings
        from netobserv_tpu.metrics.server import start_metrics_server
        from netobserv_tpu.server import start_debug_server

        self.spans, self.offer = spans, offer
        self.cfg = cfg = load_config()
        cfg.validate()
        self.reports: list = []         # (arrival perf_counter, report)
        self.arrived = threading.Condition()
        self.metrics = Metrics(MetricsSettings(prefix=cfg.metrics_prefix,
                                               level=cfg.metrics_level))
        self.exporter = TpuSketchExporter.from_config(
            cfg, metrics=self.metrics, sink=self._sink)
        self.fetcher = make_fetcher(spans, offer)
        self.agent = FlowsAgent(cfg, self.fetcher, self.exporter,
                                metrics=self.metrics,
                                agent_ip=cfg.agent_ip or "127.0.0.1")
        self.fetcher.flush = self.agent.map_tracer.flush
        self._wrap_export()
        if wrap_publish:
            publish = self.exporter._publish_report

            def publish_report(*a, **kw):
                with spans.span("publish"):
                    return publish(*a, **kw)
            self.exporter._publish_report = publish_report
        self.srv = start_metrics_server(
            self.metrics.registry, "127.0.0.1", 0,
            health_source=self.agent.health_snapshot,
            query_routes=self.agent.query_routes)
        self.port = self.srv.server_address[1]
        self.debug = start_debug_server("127.0.0.1:0")
        self.debug_port = self.debug.server_address[1]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self.agent.run,
                                        args=(self._stop,), daemon=True)
        self._thread.start()

    def _sink(self, report: dict) -> None:
        with self.arrived:
            self.reports.append((time.perf_counter(), report))
            self.arrived.notify_all()

    def _wrap_export(self) -> None:
        """Time every eviction through `export_evicted`: always on, because
        the closed loop's back-pressure and the lag metrics both need it."""
        inner, spans, offer = self.exporter.export_evicted, self.spans, self.offer
        fetcher = self.fetcher

        def export_evicted(evicted):
            t_in = time.perf_counter()
            try:
                with spans.span("export"):
                    inner(evicted)
            finally:
                t_out = time.perf_counter()
                with offer.lock:
                    offer.exported += 1
                    offer.samples.append(
                        (getattr(evicted, "cellbench_handover", t_in),
                         t_in, t_out, len(evicted)))
                gen = fetcher.generator
                if gen is not None and gen.wants_drain(offer.unacked):
                    fetcher.flush()

        self.exporter.export_evicted = export_evicted

    def counters(self) -> dict:
        return scrape(self.port, self.cfg.metrics_prefix)

    def executables(self) -> dict:
        return http_json(self.debug_port, "/debug/executables")[1]

    def query(self, path: str):
        return http_json(self.port, path)

    def n_reports(self) -> int:
        return len(self.reports)

    def wait_report(self, seen: int, timeout_s: float, what: str) -> int:
        """Block until more than `seen` reports have arrived; returns the
        index of the first new one."""
        deadline = time.monotonic() + timeout_s
        with self.arrived:
            while len(self.reports) <= seen:
                left = deadline - time.monotonic()
                if left <= 0:
                    raise Failed(f"timed out after {timeout_s:.0f}s waiting "
                                 f"for {what}")
                self.arrived.wait(left)
        return seen

    def stop(self) -> None:
        self._stop.set()
        self._thread.join(timeout=120)
        self.srv.shutdown()
        self.debug.shutdown()
        if self._thread.is_alive():
            raise Failed("agent did not stop within 120s")


def wait_ladder_warm(aut: AgentUnderTest, timeout_s: float) -> list:
    def warm():
        _, st = aut.query("/query/status")
        sb = st.get("superbatch")
        return sb["ladder"] if sb and sb["warm"] == sb["ladder"] else None
    return wait_for(warm, timeout_s, "every superbatch ladder entry to warm "
                    "(one that fails to compile logs an error by name)", 0.25)

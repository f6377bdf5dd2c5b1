"""Observability battery (DESIGN.md §15): tracer span trees through the
sync and concurrent serve paths, histogram algebra, metrics semantics,
Prometheus exposition, profiler hooks, and the bit-identity guarantee
with tracing on at 100% sampling.

The concurrent stress (8 producers, 50% sampling, requeues in flight)
asserts the span-tree invariants the Chrome-trace validator
(scripts/check_trace.py) enforces on the verify smoke: exactly one root
per completed request, children nested inside their root's interval,
retried batches produce linked retry spans, and sampling drops whole
requests atomically — never orphan children.
"""
import json
import threading
import time
from collections import defaultdict

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.engine import EngineConfig, GeoEngine
from repro.obs import (LatencyHistogram, SpanBuffer, Tracer,
                       profile_range, profiler_available)
from repro.obs.trace import Span
from repro.serving import (AsyncGeoServer, FrontendConfig, GeoServer,
                           ServeConfig)
from repro.serving.metrics import LatencyWindow, ServerMetrics

EXACT_CFG = EngineConfig(backend="ref", cap_state=1.0, cap_county=1.0,
                         cap_block=1.0, cap_boundary=1.0, max_level=8,
                         fused=True)
BUCKETS = (64, 256, 1024)
STREAM = (1, 7, 300, 555, 1024, 113)

# Child nesting tolerance: spans stamp time.perf_counter monotonically
# in program order, so exact containment should hold; allow float slack.
EPS_S = 1e-9


@pytest.fixture(scope="module")
def engine(synth_small):
    return GeoEngine.build(synth_small.census, "fast", EXACT_CFG)


def _mk_span(i, trace_id=1, parent=None, name="s"):
    return Span(trace_id=trace_id, span_id=i, parent_id=parent,
                name=name, t0=float(i), t1=float(i + 1),
                thread="t", attrs={})


def _by_trace(spans):
    groups = defaultdict(list)
    for s in spans:
        groups[s.trace_id].append(s)
    return groups


def _assert_tree_invariants(spans):
    """The span-tree invariants for a set of *completed* traces."""
    for tid, group in _by_trace(spans).items():
        roots = [s for s in group if s.parent_id is None]
        assert len(roots) == 1, \
            f"trace {tid}: {len(roots)} roots (want exactly 1)"
        root = roots[0]
        assert root.name == "request"
        ids = {s.span_id for s in group}
        for s in group:
            if s is root:
                continue
            assert s.parent_id in ids, \
                f"trace {tid}: {s.name} parent {s.parent_id} unresolved"
            assert s.t0 >= root.t0 - EPS_S and s.t1 <= root.t1 + EPS_S, \
                f"trace {tid}: {s.name} outside root interval"
            assert s.t1 >= s.t0 - EPS_S


# -- histogram algebra -------------------------------------------------------

def test_hist_quantile_within_bucket_resolution():
    h = LatencyHistogram()
    rng = np.random.default_rng(0)
    samples = rng.uniform(1e-4, 1e-1, 4096)
    for s in samples:
        h.observe(s)
    # Geometric-midpoint answers are exact within one bucket's half
    # width: a factor of 2**(1/(2*per_octave)) (~9% at 4/octave).
    tol = 2 ** (0.5 / h.per_octave)
    for q in (0.5, 0.9, 0.99):
        exact = np.quantile(samples, q)
        approx = h.quantile(q)
        assert exact / tol <= approx <= exact * tol, (q, exact, approx)


def test_hist_merge_matches_single_feed_and_is_associative():
    rng = np.random.default_rng(1)
    parts = [rng.uniform(1e-5, 1.0, 257) for _ in range(3)]
    hs = []
    for p in parts:
        h = LatencyHistogram()
        for s in p:
            h.observe(s)
        hs.append(h)
    direct = LatencyHistogram()
    for s in np.concatenate(parts):
        direct.observe(s)
    ab_c = hs[0].merge(hs[1]).merge(hs[2])
    a_bc = hs[0].merge(hs[1].merge(hs[2]))
    for m in (ab_c, a_bc):
        np.testing.assert_array_equal(m.counts, direct.counts)
        assert m.count == direct.count
        assert m.max == direct.max
        assert m.sum == pytest.approx(direct.sum)


def test_hist_merge_rejects_layout_mismatch():
    with pytest.raises(ValueError, match="layout"):
        LatencyHistogram().merge(LatencyHistogram(per_octave=8))


def test_hist_overflow_and_empty():
    h = LatencyHistogram()
    assert h.quantile(0.5) == 0.0
    assert h.snapshot_ms()["count"] == 0
    assert h.snapshot_ms()["p99"] is None
    h.observe(1e9)                     # beyond hi -> overflow bucket
    assert h.counts[-1] == 1
    assert h.quantile(0.99) == float(h.uppers[-1])
    assert h.snapshot_ms()["max"] == pytest.approx(1e12)  # ms, exact


def test_hist_cumulative_truncates_after_covering_bucket():
    h = LatencyHistogram()
    h.observe(2e-6)                    # bucket upper exactly 2e-06
    cum = h.cumulative()
    assert cum[-1] == (pytest.approx(2e-6), 1)
    assert all(c == 0 for _, c in cum[:-1])
    assert len(cum) == 4               # 4 buckets/octave, one octave up


# -- metrics semantics (satellites 1 + 2) ------------------------------------

def test_latency_window_reports_both_counts():
    w = LatencyWindow(window=8)
    for i in range(20):
        w.observe(0.001 * (i + 1))
    snap = w.snapshot_ms()
    assert snap["count_total"] == 20
    assert snap["count_window"] == 8   # percentiles cover only these
    assert snap["p50"] == pytest.approx(
        np.percentile(np.arange(13, 21) * 1.0, 50))


def test_observe_cache_gauges_survive_rewind():
    """Cache absolutes are gauges: a cache clear rewinds them without
    corrupting any counter a scraper might diff."""
    m = ServerMetrics()
    m.observe_cache({"hits": 50, "misses": 10, "insertions": 8,
                     "evictions": 1, "entries": 7})
    assert m.gauges["cache_hits"] == 50
    counters_before = dict(m.counters)
    m.observe_cache({"hits": 2, "misses": 1, "insertions": 1,
                     "evictions": 0, "entries": 1})   # post-clear
    assert m.gauges["cache_hits"] == 2                # gauge follows
    assert m.counters == counters_before              # counters untouched
    snap = m.snapshot()
    assert snap["derived"]["cache_hit_rate"] == pytest.approx(2 / 3)


def test_serving_cache_totals_are_monotonic(engine, points_small):
    """The serving-side cache_*_total counters increment at observation
    sites and never rewind, even when the cache itself is cleared."""
    xy, *_ = points_small
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=True))
    server.submit(xy[:500])
    c1 = server.metrics.counters["cache_misses_total"]
    assert c1 > 0
    cache = server.regions[0].cache   # simulate a cache clear/restart
    cache._map.clear()
    cache.hits = cache.misses = 0
    server.submit(xy[:500])
    assert server.metrics.counters["cache_misses_total"] > c1
    # while the gauge absolutes rewound with the clear:
    assert server.snapshot()["gauges"]["cache_misses"] < \
        server.metrics.counters["cache_misses_total"]


def test_expose_text_golden():
    m = ServerMetrics()
    m.inc("requests", 3)
    m.inc("points_in", 42)
    m.set_gauge("queue_depth_points", 0)
    m.observe_stage("merge", 2e-6)     # lands exactly on a bucket upper
    got = m.expose_text()
    assert got == (
        "# TYPE points_in_total counter\n"
        "points_in_total 42\n"
        "# TYPE requests_total counter\n"
        "requests_total 3\n"
        "# TYPE queue_depth_points gauge\n"
        "queue_depth_points 0\n"
        "# TYPE stage_latency_seconds histogram\n"
        'stage_latency_seconds_bucket{stage="merge",le="1.18921e-06"} 0\n'
        'stage_latency_seconds_bucket{stage="merge",le="1.41421e-06"} 0\n'
        'stage_latency_seconds_bucket{stage="merge",le="1.68179e-06"} 0\n'
        'stage_latency_seconds_bucket{stage="merge",le="2e-06"} 1\n'
        'stage_latency_seconds_bucket{stage="merge",le="+Inf"} 1\n'
        'stage_latency_seconds_sum{stage="merge"} 2e-06\n'
        'stage_latency_seconds_count{stage="merge"} 1\n')


def test_expose_text_sanitizes_metric_names():
    m = ServerMetrics()
    m.inc("weird name-1!", 2)
    txt = m.expose_text()
    assert "weird_name_1__total 2" in txt


# -- span plumbing -----------------------------------------------------------

def test_span_buffer_bounded_drop_oldest():
    buf = SpanBuffer(capacity=4)
    for i in range(6):
        buf.append(_mk_span(i))
    assert len(buf) == 4
    assert buf.dropped == 2
    assert [s.span_id for s in buf.snapshot()] == [2, 3, 4, 5]
    buf.clear()
    assert len(buf) == 0 and buf.dropped == 0


def test_tracer_sampling_is_deterministic_and_exact():
    tr = Tracer(sample_rate=0.25)
    kept = [tr.start_trace() is not None for _ in range(100)]
    assert sum(kept) == 25             # exact long-run rate
    # credit accumulator: every 4th request sampled, deterministically
    assert kept == [((i + 1) % 4 == 0) for i in range(100)]
    assert Tracer(sample_rate=0.0).start_trace() is None
    assert Tracer(sample_rate=1.0).start_trace() is not None
    with pytest.raises(ValueError):
        Tracer(sample_rate=1.5)


def test_request_trace_parentage_and_idempotent_end():
    tr = Tracer(sample_rate=1.0)
    t0 = time.perf_counter()
    rt = tr.start_trace(t0)
    host = rt.span("host_prepare", t0 + 0.01, t0 + 0.02)
    rt.span("route", t0 + 0.011, t0 + 0.015, parent=host, region=0)
    rt.end(t0 + 0.05, n_points=3)
    rt.end(t0 + 9.0)                   # second close must be a no-op
    spans = tr.buffer.snapshot()
    assert [s.name for s in spans] == ["host_prepare", "route", "request"]
    _assert_tree_invariants(spans)
    root = spans[-1]
    assert root.t1 == t0 + 0.05 and root.attrs == {"n_points": 3}
    route = spans[1]
    assert route.parent_id == host and route.attrs["region"] == 0
    assert spans[0].parent_id == root.span_id


def test_chrome_export_shape(tmp_path):
    tr = Tracer(sample_rate=1.0)
    rt = tr.start_trace(time.perf_counter())
    rt.span("queue_wait", rt._t0, rt._t0 + 0.001)
    rt.end(rt._t0 + 0.002)
    path = str(tmp_path / "trace.json")
    n = tr.export_chrome(path)
    doc = json.load(open(path))
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    assert len(events) == n
    xs = [e for e in events if e["ph"] == "X"]
    metas = [e for e in events if e["ph"] == "M"]
    assert {e["name"] for e in xs} == {"queue_wait", "request"}
    assert metas and metas[0]["name"] == "thread_name"
    for e in xs:                       # pid = the request's trace id
        assert e["pid"] == rt.trace_id
        assert e["ts"] >= 0 and e["dur"] >= 0
        assert e["args"]["trace_id"] == rt.trace_id


# -- serve-path integration --------------------------------------------------

def test_sync_serving_bit_identical_with_full_tracing(engine,
                                                      points_small):
    """Acceptance: tracing at 100% sampling changes no served bit."""
    xy, *_ = points_small
    tracer = Tracer(sample_rate=1.0)
    traced = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=True),
                       tracer=tracer)
    plain = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=True))
    off = 0
    for size in STREAM:
        req = xy[off:off + size]
        off += size
        rt = traced.submit(req)
        rp = plain.submit(req)
        direct = engine.assign(jnp.asarray(req))
        np.testing.assert_array_equal(rt.block, np.asarray(direct.block))
        np.testing.assert_array_equal(rt.state, np.asarray(direct.state))
        np.testing.assert_array_equal(rt.block, rp.block)
    assert tracer.stats()["sampled"] == len(STREAM)
    spans = tracer.buffer.snapshot()
    _assert_tree_invariants(spans)
    roots = [s for s in spans if s.parent_id is None]
    assert len(roots) == len(STREAM)
    names = {s.name for s in spans}
    assert {"request", "submit", "queue_wait", "host_prepare", "route",
            "cache_lookup", "device_assign", "merge"} <= names


def test_sync_stage_histograms_always_on(engine, points_small):
    """Per-stage histograms record with NO tracer attached."""
    xy, *_ = points_small
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=False))
    server.submit(xy[:200])
    stages = server.snapshot()["stages"]
    for stage in ("queue_wait", "host_prepare", "device_assign", "merge",
                  "request"):
        assert stages[stage]["count"] > 0, stage
        assert stages[stage]["p99"] >= 0


def test_tracer_off_records_nothing(engine, points_small):
    xy, *_ = points_small
    tracer = Tracer(sample_rate=0.0)
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=False),
                       tracer=tracer)
    server.submit(xy[:100])
    assert len(tracer.buffer) == 0
    assert tracer.stats()["started"] == 1
    assert tracer.stats()["sampled"] == 0


def test_metrics_text_endpoint(engine, points_small):
    xy, *_ = points_small
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=True))
    server.submit(xy[:100])
    txt = server.metrics_text()
    assert "requests_total 1" in txt
    assert "cache_misses gauge" in txt
    assert 'stage_latency_seconds_bucket{stage="device_assign"' in txt
    assert txt.count('le="+Inf"') >= 5     # every serve stage renders


@pytest.mark.timeout(60)
def test_async_tracing_stress_span_tree_invariants(engine, points_small):
    """8 producers, 50% sampling: every sampled request yields exactly
    one root, children nest, whole requests drop atomically."""
    xy, *_ = points_small
    tracer = Tracer(sample_rate=0.5, capacity=1 << 15)
    n_producers, per_producer = 8, 12
    sizes = [1, 9, 33, 120, 300]
    with AsyncGeoServer(
            engine, ServeConfig(buckets=BUCKETS, cache=True,
                                max_delay_ms=1.0),
            frontend=FrontendConfig(n_replicas=2, n_submitters=4),
            tracer=tracer) as server:
        results, errors = [], []
        lock = threading.Lock()

        def producer(pid):
            rng = np.random.default_rng(pid)
            try:
                futs = []
                for i in range(per_producer):
                    size = sizes[rng.integers(0, len(sizes))]
                    start = rng.integers(0, len(xy) - size)
                    futs.append((start, size,
                                 server.submit_async(
                                     xy[start:start + size])))
                for start, size, fut in futs:
                    res = fut.result(timeout=30)
                    with lock:
                        results.append((start, size, res))
            except Exception as e:     # pragma: no cover - failure path
                errors.append(e)

        threads = [threading.Thread(target=producer, args=(p,))
                   for p in range(n_producers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(45)
        assert not errors
        server.drain(timeout=30)
    n_requests = n_producers * per_producer
    assert len(results) == n_requests
    # Bit-identity held under concurrency + tracing:
    for start, size, res in results:
        direct = np.asarray(
            engine.assign(jnp.asarray(xy[start:start + size])).block)
        np.testing.assert_array_equal(res.block, direct)
    # Span-tree invariants over everything recorded:
    st = tracer.stats()
    assert st["started"] == n_requests
    assert st["sampled"] == n_requests // 2    # deterministic 50%
    assert st["dropped"] == 0
    spans = tracer.buffer.snapshot()
    _assert_tree_invariants(spans)
    groups = _by_trace(spans)
    assert len(groups) == st["sampled"]        # whole-request sampling
    for group in groups.values():              # every trace completed
        names = {s.name for s in group}
        assert "merge" in names and "queue_wait" in names


class _FlakyAssign:
    """Thread-safe assign_padded wrapper failing the first ``n_fail``
    calls (mirrors test_frontend's helper)."""

    def __init__(self, engine, n_fail):
        self._orig = engine.assign_padded
        self._lock = threading.Lock()
        self.n_fail = n_fail
        self.calls = 0

    def __call__(self, points, n_valid):
        with self._lock:
            self.calls += 1
            fail = self.calls <= self.n_fail
        if fail:
            raise RuntimeError("device lost")
        return self._orig(points, n_valid)


@pytest.mark.timeout(30)
def test_retry_produces_linked_retry_span(engine, points_small,
                                          monkeypatch):
    """A failed-then-recovered batch records an instant retry span in
    the request's trace and later spans carry the attempt number."""
    xy, *_ = points_small
    tracer = Tracer(sample_rate=1.0)
    monkeypatch.setattr(engine, "assign_padded", _FlakyAssign(engine, 1))
    with AsyncGeoServer(engine,
                        ServeConfig(buckets=BUCKETS, cache=False,
                                    max_delay_ms=1.0),
                        tracer=tracer) as srv:
        res = srv.submit_async(xy[:100]).result(timeout=15)
    monkeypatch.undo()
    np.testing.assert_array_equal(
        res.block, np.asarray(engine.assign(jnp.asarray(xy[:100])).block))
    spans = tracer.buffer.snapshot()
    _assert_tree_invariants(spans)
    retries = [s for s in spans if s.name == "retry"]
    assert len(retries) == 1
    assert retries[0].attrs["attempt"] == 1
    assert retries[0].t0 == retries[0].t1      # instant event
    # post-retry serve stages carry the attempt attribute
    attempted = [s for s in spans
                 if s.attrs.get("attempt") == 1 and s.name != "retry"]
    assert {"queue_wait", "host_prepare"} <= {s.name for s in attempted}


@pytest.mark.timeout(30)
def test_shed_request_closes_trace_without_orphans(engine, points_small):
    xy, *_ = points_small
    tracer = Tracer(sample_rate=1.0)
    server = GeoServer(engine,
                       ServeConfig(buckets=BUCKETS, cache=False,
                                   max_queue_points=100, policy="shed"),
                       tracer=tracer)
    server.enqueue(xy[:80])
    from repro.serving import QueueFull
    with pytest.raises(QueueFull):
        server.enqueue(xy[80:200])
    server.flush()
    spans = tracer.buffer.snapshot()
    _assert_tree_invariants(spans)
    sheds = [s for s in spans
             if s.parent_id is None and s.attrs.get("error")]
    assert len(sheds) == 1
    assert sheds[0].attrs["error"] == "QueueFull"


# -- profiler hooks -----------------------------------------------------------

def test_device_annotation_is_exception_safe():
    with profile_range("geo_test/b256", batch=3):
        x = 1 + 1
    assert x == 2
    assert isinstance(profiler_available(), bool)


def test_trace_device_config_serves_identically(engine, points_small):
    xy, *_ = points_small
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=False,
                                           trace_device=True))
    res = server.submit(xy[:128])
    direct = np.asarray(engine.assign(jnp.asarray(xy[:128])).block)
    np.testing.assert_array_equal(res.block, direct)


# -- profiler ranges on the served path and scopes in the core ---------------

class _RangeRecorder:
    """Stands in for ``obs.profile.range_factory``: records each range as
    (name, thread, start, end, kwargs) when it closes."""

    def __init__(self):
        self.ranges = []
        self._lock = threading.Lock()

    def __call__(self, name, **kw):
        rec = self

        class _Range:
            def __enter__(self):
                self.t0 = time.perf_counter()

            def __exit__(self, *exc):
                t1 = time.perf_counter()
                with rec._lock:
                    rec.ranges.append((name, threading.get_ident(),
                                       self.t0, t1, kw))
        return _Range()


@pytest.fixture(scope="module")
def served_ranges(engine, points_small):
    """The same requests through an AsyncGeoServer (cache and analytics
    on) with ``trace_device`` on, then off, under one recorder: the
    ranges each run opened and its answers."""
    from repro.analytics import AnalyticsConfig
    from repro.obs import profile as obs_profile
    xy, *_ = points_small
    out = {}
    with pytest.MonkeyPatch.context() as mp:
        for on in (True, False):
            rec = _RangeRecorder()
            mp.setattr(obs_profile, "range_factory", rec)
            cfg = ServeConfig(buckets=BUCKETS, cache=True, trace_device=on,
                              analytics=AnalyticsConfig(60.0, 10.0))
            with AsyncGeoServer(engine, cfg) as server:
                futs, off = [], 0
                for size in STREAM:
                    futs.append(server.submit_async(xy[off:off + size]))
                    off += size
                answers = [f.result(timeout=30) for f in futs]
                server.drain(timeout=30)
            out[on] = (rec.ranges, answers)
    return out


def _contains(outer, inner):
    return outer[1] == inner[1] and outer[2] <= inner[2] \
        and inner[3] <= outer[3]


@pytest.mark.timeout(60)
def test_profile_ranges_nest_on_each_thread(served_ranges):
    ranges, _ = served_ranges[True]
    assert ranges
    by_thread = defaultdict(list)
    for r in ranges:
        by_thread[r[1]].append(r)
    for rs in by_thread.values():
        stack = []
        for r in sorted(rs, key=lambda r: (r[2], -r[3])):
            while stack and stack[-1][3] <= r[2]:
                stack.pop()
            assert not stack or r[3] <= stack[-1][3], (stack[-1], r)
            stack.append(r)


@pytest.mark.timeout(60)
def test_profile_ranges_of_a_batch_share_its_id_across_threads(
        served_ranges):
    ranges, _ = served_ranges[True]
    host = [r for r in ranges if r[0] == "geo/host_prepare"]
    done = [r for r in ranges if r[0] == "geo/complete_batch"]
    batches = sorted(r[4]["batch"] for r in host)
    assert batches and batches == sorted(r[4]["batch"] for r in done)
    assert len(set(batches)) == len(batches)
    flusher = {r[1] for r in host}
    replica = {r[1] for r in done}
    assert len(flusher) == 1 and len(replica) == 1 and flusher != replica
    for r in ranges:
        if not r[0].startswith("geo/"):
            continue
        assert r[4]["batch"] in batches, r
        if r[0] == "geo/cache_gauges":       # after its batch, on the replica
            assert r[1] in replica
            continue
        outer = host if r[1] in flusher else done
        owner = [o for o in outer if _contains(o, r)]
        assert len(owner) == 1 and owner[0][4]["batch"] == r[4]["batch"], r
    names = {r[0] for r in ranges}
    assert {"geo/route", "geo/cache_lookup", "geo/cache_learn",
            "geo/dispatch", "geo/analytics_observe", "geo/merge",
            "geo/cache_gauges"} <= names


@pytest.mark.timeout(60)
def test_device_stage_range_holds_dispatch_pull_and_stats_fold(
        served_ranges):
    ranges, _ = served_ranges[True]
    stages = [r for r in ranges if r[0] == "geo/device_stage"]
    assert stages
    for st in stages:
        inside = [r for r in ranges if _contains(st, r) and r is not st]
        kids = {r[0] for r in inside if r[4].get("batch") == st[4]["batch"]}
        assert {"geo/dispatch", "geo/pull", "geo/stats_fold"} <= kids
        assert any(r[0] == f"geo_device_assign/b{st[4]['bucket']}"
                   and any(_contains(d, r) for d in inside
                           if d[0] == "geo/dispatch") for r in inside)


@pytest.mark.timeout(60)
def test_no_profile_range_opens_with_trace_device_off(served_ranges):
    ranges, _ = served_ranges[False]
    assert ranges == []


@pytest.mark.timeout(60)
def test_answers_are_identical_with_profile_ranges_on_and_off(
        served_ranges):
    (_, on), (_, off) = served_ranges[True], served_ranges[False]
    assert len(on) == len(off) == len(STREAM)
    for a, b in zip(on, off):
        for f in ("state", "county", "block", "region"):
            np.testing.assert_array_equal(getattr(a, f), getattr(b, f))


SCOPES = ("geo/locate", "geo/compact", "geo/pip_phase1", "geo/phase2_gather",
          "geo/pip_phase2", "geo/parents")


@pytest.fixture(scope="module")
def assign_fast_op_names(engine, points_small):
    """The op_name metadata of the exact fast assign, compiled."""
    import re

    from repro.core import fast
    xy, *_ = points_small
    text = fast.assign_fast.lower(
        engine.indices.fast, jnp.asarray(xy[:256]),
        cfg=engine.cfg.fast_cfg()).compile().as_text()
    return set(re.findall(r'op_name="([^"]*)"', text))


@pytest.mark.parametrize("scope", SCOPES)
def test_assign_fast_names_each_phase_by_its_scope(assign_fast_op_names,
                                                   scope):
    assert any(f"/{scope}/" in f"/{n}/" for n in assign_fast_op_names)


# -- the exported-trace validator itself -------------------------------------

def test_check_trace_validator_on_live_export(engine, points_small,
                                              tmp_path):
    """scripts/check_trace.py accepts a real export and rejects a
    corrupted one."""
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        "check_trace", os.path.join(os.path.dirname(__file__), "..",
                                    "scripts", "check_trace.py"))
    check_trace = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(check_trace)

    xy, *_ = points_small
    tracer = Tracer(sample_rate=1.0)
    server = GeoServer(engine, ServeConfig(buckets=BUCKETS, cache=True),
                       tracer=tracer)
    for size in STREAM:
        server.submit(xy[:size])
    good = str(tmp_path / "good.json")
    tracer.export_chrome(good)
    check_trace.main(good)                     # must not exit

    doc = json.load(open(good))
    doc["traceEvents"] = [e for e in doc["traceEvents"]
                          if e.get("name") != "request"]
    bad = str(tmp_path / "bad.json")
    json.dump(doc, open(bad, "w"))
    with pytest.raises(SystemExit):
        check_trace.main(bad)

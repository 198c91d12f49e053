"""Unified telemetry subsystem (ISSUE 3): registry/histograms, span
tracing + flight recorder, Prometheus/JSONL export, multi-host
aggregation, the stall watchdog — and the overhead + collection guards
that keep instrumentation free when observability is off."""

import json
import math
import os
import subprocess
import sys
import threading
import time
import urllib.request

import numpy as np
import pytest

from accelerate_tpu.telemetry import (
    MetricsRegistry,
    MetricsServer,
    StallWatchdog,
    StreamingHistogram,
    aggregate_flat,
    aggregate_snapshot,
    clear_flight_recorder,
    configure_tracing,
    drain_spans,
    export_chrome_trace,
    flatten_snapshot,
    flight_recorder,
    get_registry,
    ingest_spans,
    record_span,
    render_prometheus,
    resolve_metrics_port,
    span,
    trace_events,
    tracing_enabled,
)
from accelerate_tpu.telemetry.aggregate import merged_registry
from accelerate_tpu.telemetry.trace import DEFAULT_RING_SIZE
from accelerate_tpu.telemetry.watchdog import StallError


@pytest.fixture(autouse=True)
def _tracing_off():
    """Every test starts and ends with tracing disabled and an empty
    flight recorder (module-level state must not leak across tests)."""
    configure_tracing(enabled=False)
    clear_flight_recorder()
    yield
    configure_tracing(enabled=False)
    clear_flight_recorder()


# ---------------------------------------------------------------------------
# streaming histogram (the shared quantile helper)
# ---------------------------------------------------------------------------


class TestStreamingHistogram:
    def test_quantile_parity_with_numpy_percentile(self):
        """Satellite: the shared histogram must agree with numpy.percentile
        on known data within its declared relative accuracy."""
        rng = np.random.default_rng(0)
        for data in (
            rng.lognormal(0.0, 1.5, 20_000),          # latency-shaped
            rng.uniform(0.001, 10.0, 20_000),
            np.arange(1, 5001).astype(float),
        ):
            h = StreamingHistogram(relative_accuracy=0.01)
            for v in data:
                h.record(v)
            for q in (50, 90, 99):
                exact = float(np.percentile(data, q))
                approx = h.quantile(q / 100)
                # nearest-rank + log buckets: 3x the sketch accuracy is a
                # safe deterministic bound
                assert abs(approx - exact) / exact < 0.03, (q, approx, exact)

    def test_exact_count_sum_mean_min_max(self):
        h = StreamingHistogram()
        data = [0.1, 0.2, 0.4, 0.8]
        for v in data:
            h.record(v)
        assert h.count == 4
        assert h.sum == pytest.approx(sum(data))
        assert h.mean == pytest.approx(sum(data) / 4)
        assert h.min == pytest.approx(0.1)
        assert h.max == pytest.approx(0.8)

    def test_empty_and_zero_values(self):
        h = StreamingHistogram()
        assert math.isnan(h.quantile(0.5)) and math.isnan(h.mean)
        h.record(0.0)
        h.record(0.0)
        h.record(1.0)
        assert h.quantile(0.5) == 0.0
        assert h.quantile(1.0) == pytest.approx(1.0, rel=0.02)

    def test_bounded_memory_collapses_low_buckets(self):
        h = StreamingHistogram(relative_accuracy=0.01, max_buckets=512)
        rng = np.random.default_rng(1)
        data = rng.lognormal(0.0, 1.5, 50_000)
        for v in data:
            h.record(v)
        assert len(h._buckets) <= 512
        # collapsing the LOWEST buckets keeps tail accuracy: p50/p99 sit
        # far above the collapsed bottom of the range
        for q in (50, 99):
            exact = float(np.percentile(data, q))
            assert abs(h.quantile(q / 100) - exact) / exact < 0.05

    def test_merge_equals_combined_stream(self):
        rng = np.random.default_rng(2)
        a_data, b_data = rng.lognormal(0, 1, 5000), rng.lognormal(1, 1, 5000)
        a, b, both = (StreamingHistogram() for _ in range(3))
        for v in a_data:
            a.record(v)
            both.record(v)
        for v in b_data:
            b.record(v)
            both.record(v)
        a.merge(b)
        assert a.count == both.count
        assert a.sum == pytest.approx(both.sum)
        for q in (0.5, 0.99):
            assert a.quantile(q) == pytest.approx(both.quantile(q), rel=0.03)

    def test_roundtrip_through_dict(self):
        h = StreamingHistogram()
        for v in (0.5, 1.5, 2.5):
            h.record(v)
        h2 = StreamingHistogram.from_dict(
            json.loads(json.dumps(h.to_dict())))
        assert h2.count == 3 and h2.sum == pytest.approx(4.5)
        assert h2.quantile(0.5) == pytest.approx(h.quantile(0.5))


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------


class TestRegistry:
    def test_get_or_create_and_labels(self):
        r = MetricsRegistry()
        assert r.counter("req_total", host="0") is r.counter("req_total", host="0")
        assert r.counter("req_total", host="0") is not r.counter("req_total", host="1")
        r.counter("req_total", host="0").inc(3)
        r.gauge("depth").set(7)
        r.histogram("lat_s").record(0.25)
        snap = r.snapshot()
        assert snap["counters"]['req_total{host="0"}'] == 3.0
        assert snap["gauges"]["depth"] == 7.0
        assert snap["histograms"]["lat_s"]["count"] == 1.0

    def test_counter_rejects_negative(self):
        with pytest.raises(ValueError):
            MetricsRegistry().counter("c").inc(-1)

    def test_gauge_set_max_is_high_water(self):
        g = MetricsRegistry().gauge("hbm")
        g.set_max(10)
        g.set_max(5)
        assert g.value == 10

    def test_reset_zeroes_in_place(self):
        r = MetricsRegistry()
        c, h = r.counter("c"), r.histogram("h")
        c.inc(5)
        h.record(1.0)
        r.reset()
        # same objects (cached handles + exporter stay live), zeroed
        assert r.counter("c") is c and c.value == 0
        assert r.histogram("h") is h and h.count == 0

    def test_flatten_snapshot(self):
        r = MetricsRegistry()
        r.counter("tok").inc(2)
        r.histogram("lat").record(0.5)
        flat = flatten_snapshot(r.snapshot(), prefix="t/")
        assert flat["t/tok"] == 2.0
        assert flat["t/lat_count"] == 1.0 and "t/lat_p99" in flat

    def test_concurrent_increments_are_exact(self):
        r = MetricsRegistry()
        c = r.counter("n")

        def work():
            for _ in range(10_000):
                c.inc()

        threads = [threading.Thread(target=work) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert c.value == 40_000


# ---------------------------------------------------------------------------
# span tracing + flight recorder + chrome export
# ---------------------------------------------------------------------------


class TestTracing:
    def test_disabled_span_is_shared_noop(self):
        assert not tracing_enabled()
        s1, s2 = span("a"), span("b", big="attr")
        assert s1 is s2  # the shared null span: no allocation per call
        with s1:
            pass
        assert flight_recorder() == []

    def test_nested_spans_record_ids_and_attrs(self):
        configure_tracing(enabled=True, annotate=False)
        with span("outer", phase="train"):
            with span("inner"):
                time.sleep(0.001)
        events = flight_recorder()
        assert [e["name"] for e in events] == ["inner", "outer"]
        inner, outer = events
        assert inner.get("parent_id") == outer["span_id"]
        assert inner["trace_id"] == outer["trace_id"]
        assert outer["attrs"] == {"phase": "train"}
        assert inner["dur_ns"] >= 1_000_000  # the sleep is inside it
        assert outer["dur_ns"] >= inner["dur_ns"]

    def test_sibling_roots_get_distinct_traces(self):
        configure_tracing(enabled=True, annotate=False)
        with span("a"):
            pass
        with span("b"):
            pass
        a, b = flight_recorder()
        assert a["trace_id"] != b["trace_id"]
        assert a["parent_id"] == 0 and b["parent_id"] == 0

    def test_ring_buffer_is_bounded(self):
        configure_tracing(enabled=True, ring_size=8, annotate=False)
        for i in range(50):
            with span(f"s{i}"):
                pass
        events = flight_recorder()
        assert len(events) == 8
        assert events[-1]["name"] == "s49"
        configure_tracing(enabled=False, ring_size=DEFAULT_RING_SIZE)

    def test_span_records_on_exception(self):
        configure_tracing(enabled=True, annotate=False)
        with pytest.raises(RuntimeError):
            with span("boom"):
                raise RuntimeError("x")
        (e,) = flight_recorder()
        assert e["error"] == "RuntimeError"

    def test_chrome_trace_export(self, tmp_path):
        configure_tracing(enabled=True, annotate=False)
        with span("region", k="v"):
            pass
        path = str(tmp_path / "trace.json")
        doc = export_chrome_trace(path)
        with open(path) as f:
            loaded = json.load(f)
        assert loaded == doc
        (ev,) = loaded["traceEvents"]
        assert ev["ph"] == "X" and ev["name"] == "region"
        assert ev["dur"] >= 0 and ev["args"]["k"] == "v"

    def test_annotation_forwarding_matches_jax_profiler(self):
        """Enabled spans enter jax.profiler.TraceAnnotation so host spans
        line up with XLA device traces (smoke: no device capture here)."""
        configure_tracing(enabled=True, annotate=True)
        with span("annotated-region"):
            pass
        (e,) = flight_recorder()
        assert e["name"] == "annotated-region"


# ---------------------------------------------------------------------------
# exporters
# ---------------------------------------------------------------------------

_PROM_LINE = None  # compiled lazily


def _parse_exposition(body: str) -> dict[str, float]:
    """Minimal exposition parser: every non-comment line must be
    `name[{labels}] value`."""
    import re

    pat = re.compile(
        r'^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^}]*\})?\s+(-?[0-9.eE+naNif]+)$')
    out = {}
    for line in body.strip().splitlines():
        if line.startswith("#"):
            continue
        m = pat.match(line)
        assert m, f"unparseable exposition line: {line!r}"
        out[m.group(1) + (m.group(2) or "")] = m.group(3)
    return out


class TestPrometheusExport:
    def test_render_types_and_values(self):
        r = MetricsRegistry()
        r.counter("tokens_total").inc(12)
        r.gauge("queue_depth").set(3)
        h = r.histogram("ttft_seconds")
        for v in (0.1, 0.2, 0.3):
            h.record(v)
        body = render_prometheus(r)
        assert "# TYPE tokens_total counter" in body
        assert "# TYPE queue_depth gauge" in body
        assert "# TYPE ttft_seconds summary" in body
        series = _parse_exposition(body)
        assert float(series["tokens_total"]) == 12.0
        assert float(series["ttft_seconds_count"]) == 3.0
        assert float(series['ttft_seconds{quantile="0.99"}']) > 0

    def test_label_escaping_and_name_sanitizing(self):
        r = MetricsRegistry()
        r.counter("weird-name.total", path='a"b\\c').inc()
        body = render_prometheus(r)
        assert "weird_name_total" in body
        assert '\\"b' in body

    def test_http_endpoint_serves_parseable_exposition(self):
        """Satellite: bind port 0 (no fixed ports), GET /metrics, parse."""
        r = MetricsRegistry()
        r.counter("up_total").inc()
        r.histogram("lat_seconds").record(0.05)
        server = MetricsServer(registry=r, port=0, host="127.0.0.1").start()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            resp = urllib.request.urlopen(url, timeout=5)
            assert resp.status == 200
            assert "text/plain" in resp.headers["Content-Type"]
            series = _parse_exposition(resp.read().decode())
            assert float(series["up_total"]) == 1.0
            assert float(series["lat_seconds_count"]) == 1.0
            with pytest.raises(urllib.error.HTTPError):
                urllib.request.urlopen(
                    f"http://127.0.0.1:{server.port}/nope", timeout=5)
        finally:
            server.stop()

    def test_resolve_metrics_port(self, monkeypatch):
        monkeypatch.delenv("ACCELERATE_TPU_METRICS_PORT", raising=False)
        assert resolve_metrics_port(None) is None
        assert resolve_metrics_port(9100) == 9100
        monkeypatch.setenv("ACCELERATE_TPU_METRICS_PORT", "0")
        assert resolve_metrics_port(None) == 0
        assert resolve_metrics_port(9100) == 9100  # explicit wins

    def test_server_binds_loopback_by_default(self):
        server = MetricsServer(registry=MetricsRegistry(), port=0)
        try:
            assert server._httpd.server_address[0] == "127.0.0.1"
        finally:
            server.stop()

    def test_env_port_conflict_degrades_instead_of_crashing(self, monkeypatch):
        """Second binder of the env-configured port (e.g. an Engine next
        to an Accelerator) must warn and run without an endpoint, not
        abort construction; an explicit flag still raises."""
        from accelerate_tpu.telemetry import start_metrics_server

        first = start_metrics_server(0, registry=MetricsRegistry())
        try:
            monkeypatch.setenv("ACCELERATE_TPU_METRICS_PORT",
                               str(first.port))
            second = start_metrics_server(None, registry=MetricsRegistry())
            assert second is None
            with pytest.raises(OSError):
                start_metrics_server(first.port, registry=MetricsRegistry())
        finally:
            first.stop()

    def test_jsonl_snapshot_writer(self, tmp_path):
        from accelerate_tpu.telemetry import write_snapshot

        r = MetricsRegistry()
        r.counter("n").inc(4)
        path = str(tmp_path / "telemetry.jsonl")
        write_snapshot(path, r)
        r.counter("n").inc(1)
        write_snapshot(path, r)
        lines = [json.loads(ln) for ln in open(path)]
        assert [ln["n"] for ln in lines] == [4.0, 5.0]
        assert all("ts" in ln for ln in lines)


# ---------------------------------------------------------------------------
# multi-host aggregation
# ---------------------------------------------------------------------------


def _host_snapshot(step_times: list[float], tokens: float, hbm: float) -> dict:
    r = MetricsRegistry()
    r.counter("tokens_total").inc(tokens)
    r.gauge("hbm_peak").set(hbm)
    h = r.histogram("step_time_s")
    for v in step_times:
        h.record(v)
    return r.snapshot(include_sketch=True)


class TestAggregation:
    def test_counters_sum_gauges_reduce_hists_merge(self):
        fast = _host_snapshot([0.10] * 100, tokens=1000, hbm=5.0)
        slow = _host_snapshot([0.30] * 100, tokens=1000, hbm=9.0)
        agg = aggregate_snapshot(snapshots=[fast, slow])
        assert agg["num_hosts"] == 2
        assert agg["counters"]["tokens_total"]["sum"] == 2000.0
        g = agg["gauges"]["hbm_peak"]
        assert (g["min"], g["max"]) == (5.0, 9.0)
        assert g["mean"] == pytest.approx(7.0)
        h = agg["histograms"]["step_time_s"]
        assert h["count"] == 200.0
        # the straggler view: the merged distribution spans both hosts,
        # and slowest_host_mean pins the worst host
        assert h["slowest_host_mean"] == pytest.approx(0.30, rel=0.02)
        assert h["p99"] == pytest.approx(0.30, rel=0.03)
        assert h["mean"] == pytest.approx(0.20, rel=0.02)

    def test_single_host_passthrough_uses_gather(self):
        r = MetricsRegistry()
        r.counter("c").inc(2)
        agg = aggregate_snapshot(registry=r)  # single process: gathers [self]
        assert agg["num_hosts"] == 1
        assert agg["counters"]["c"]["sum"] == 2.0

    def test_aggregate_flat_shape(self):
        snaps = [_host_snapshot([0.1], 10, 1.0),
                 _host_snapshot([0.2], 20, 2.0)]
        flat = aggregate_flat(snapshots=snaps, prefix="t/")
        assert flat["t/num_hosts"] == 2.0
        assert flat["t/tokens_total"] == 30.0
        assert flat["t/hbm_peak__max"] == 2.0
        assert flat["t/step_time_s__slowest_host_mean"] == pytest.approx(0.2, rel=0.02)
        assert all(isinstance(v, float) for v in flat.values())


# ---------------------------------------------------------------------------
# transport-backed merge: pod heartbeat snapshots -> one registry (ISSUE 18)
# ---------------------------------------------------------------------------


class TestMergedRegistry:
    def test_same_series_two_workers_sum_under_one_origin(self):
        """Two workers exposing the same series name must SUM into a
        single labeled series, not collide or shadow each other."""
        a = MetricsRegistry()
        a.counter("pod_tokens_total", role="decode").inc(3)
        b = MetricsRegistry()
        b.counter("pod_tokens_total", role="decode").inc(4)
        b.gauge("pod_active_pages").set(9)
        reg = merged_registry([a.snapshot(include_sketch=True),
                               b.snapshot(include_sketch=True)],
                              origin="workers")
        snap = reg.snapshot()
        (key,) = snap["counters"]
        assert 'origin="workers"' in key and 'role="decode"' in key
        assert snap["counters"][key] == 7.0
        # gauges expand to the min/mean/max family, still origin-tagged
        assert any(k.startswith("pod_active_pages__max{")
                   and 'origin="workers"' in k for k in snap["gauges"])

    def test_exemplar_histograms_merge_across_origins(self):
        """Exemplar-carrying histograms from different origins merge as
        distinct series (no cross-origin collision) and still render."""
        a = MetricsRegistry()
        ha = a.histogram("pod_latency_s")
        ha.record(0.1, exemplar="trace-a")
        ha.record(0.2, exemplar="trace-a2")
        b = MetricsRegistry()
        hb = b.histogram("pod_latency_s")
        hb.record(0.4, exemplar="trace-b")
        reg = MetricsRegistry()
        merged_registry([a.snapshot(include_sketch=True)],
                        registry=reg, origin="workers")
        merged_registry([b.snapshot(include_sketch=True)],
                        registry=reg, origin="workers", stale="true")
        snap = reg.snapshot()
        hists = snap["histograms"]
        assert len(hists) == 2          # one series per label set
        assert sorted(e["count"] for e in hists.values()) == [1.0, 2.0]
        stale_key = next(k for k in hists if 'stale="true"' in k)
        assert hists[stale_key]["sum"] == pytest.approx(0.4)
        # merged output renders cleanly for the scrape endpoint
        assert "pod_latency_s" in render_prometheus(reg)

    def test_newer_schema_unknown_keys_are_ignored_not_fatal(self):
        """A snapshot from a NEWER worker build (extra sections, extra
        histogram keys, exotic sketch encoding) merges best-effort: the
        series we understand survive, the rest are skipped."""
        newer = {
            "counters": {"tokens_total": 5.0},
            "gauges": {"hbm_peak": 2.0},
            "histograms": {
                "step_time_s": {"count": 2.0, "sum": 0.4,
                                "future_stat": "x",
                                "sketch": {"v2_encoding": True}},
            },
            "spans_v2": [{"opaque": 1}],      # unknown section
        }
        older = _host_snapshot([0.1, 0.3], tokens=7, hbm=1.0)
        reg = merged_registry([newer, older], origin="workers")
        snap = reg.snapshot()
        (ckey,) = snap["counters"]
        assert snap["counters"][ckey] == 12.0
        # the foreign sketch is dropped but the host's scalar stats and
        # the older host's real sketch still produce a distribution
        (hkey,) = snap["histograms"]
        assert snap["histograms"][hkey]["count"] == 2.0

    def test_older_schema_and_garbage_sections_tolerated(self):
        """Missing sections, non-dict sections, non-numeric values, and
        histogram entries that aren't dicts must not crash the merge."""
        garbage = [
            {},                                     # empty snapshot
            {"counters": "not-a-dict"},             # wrong section type
            {"counters": {"tokens_total": "NaNish"},
             "gauges": {"hbm_peak": None},
             "histograms": {"step_time_s": 3.14}},  # entry not a dict
            {"counters": {"tokens_total": 2.0}},    # old build: no hists
        ]
        reg = merged_registry(garbage, origin="workers")
        snap = reg.snapshot()
        (ckey,) = snap["counters"]
        assert snap["counters"][ckey] == 2.0
        agg = aggregate_snapshot(snapshots=garbage)
        assert agg["num_hosts"] == 4
        assert agg["counters"][next(iter(agg["counters"]))]["sum"] == 2.0


# ---------------------------------------------------------------------------
# cross-process span export: drain -> wire -> ingest (ISSUE 18)
# ---------------------------------------------------------------------------


class TestSpanExport:
    def test_drain_cursor_monotone_newest_first_and_filtered(self):
        configure_tracing(enabled=True, annotate=False)
        record_span("local-chatter", 0.0, 0.1, trace=12345)   # int id: home
        record_span("req-a", 0.0, 0.2, trace="req-a")
        record_span("req-b", 0.3, 0.4, trace="req-b")
        events, cur = drain_spans(0)
        assert [e["name"] for e in events] == ["req-b", "req-a"]  # newest 1st
        # nothing new: cursor is stable and returns empty
        again, cur2 = drain_spans(cur)
        assert again == [] and cur2 == cur
        record_span("req-c", 0.5, 0.6, trace="req-c")
        events, cur3 = drain_spans(cur)
        assert [e["name"] for e in events] == ["req-c"] and cur3 > cur
        # the cursor space survives a ring clear: it never moves back
        clear_flight_recorder()
        empty, cur4 = drain_spans(cur3)
        assert empty == [] and cur4 == cur3

    def test_drain_keeps_link_carrying_int_trace_events(self):
        configure_tracing(enabled=True, annotate=False)
        record_span("shared-step", 0.0, 0.1, trace=99, links=[7, 8])
        events, _ = drain_spans(0)
        assert [e["name"] for e in events] == ["shared-step"]

    def test_ingest_rebases_namespaces_and_skips_malformed(self):
        configure_tracing(enabled=True, annotate=False)
        events = [
            {"name": "w-span", "trace_id": 7, "span_id": 3, "parent_id": 0,
             "start_ns": 1_000, "dur_ns": 10},
            "garbage",                       # not a dict: skipped
            {"name": "half"},                # missing start_ns: skipped
        ]
        n = ingest_spans(events, offset_s=5.0, pid=4242, worker=2)
        assert n == 1
        (ev,) = trace_events("w2:7")         # int id namespaced per worker
        assert ev["start_ns"] == 1_000 + int(5.0 * 1e9)   # rebased
        assert ev["attrs"]["worker"] == 2 and ev["pid"] == 4242
        # string (request-scoped) trace ids merge verbatim with ours
        record_span("router-side", 10.0, 10.1, trace="req-x")
        ingest_spans([{"name": "worker-side", "trace_id": "req-x",
                       "start_ns": int(9.9e9), "dur_ns": 50}],
                     offset_s=0.25, worker=1)
        names = {e["name"] for e in trace_events("req-x")}
        assert names == {"router-side", "worker-side"}

    def test_ingest_is_a_noop_when_tracing_disabled(self):
        assert ingest_spans([{"name": "x", "trace_id": "t",
                              "start_ns": 0}], offset_s=0.0) == 0


# ---------------------------------------------------------------------------
# stall watchdog
# ---------------------------------------------------------------------------


class TestStallWatchdog:
    def test_missed_heartbeat_fires_exactly_once_with_payload(self):
        """Satellite: fake clock — a missed heartbeat fires once with the
        stack/HBM/flight-recorder payload; ticking keeps it silent."""
        configure_tracing(enabled=True, annotate=False)
        with span("last-thing-before-hang"):
            pass
        now = [0.0]
        reports = []
        wd = StallWatchdog(10.0, clock=lambda: now[0],
                           on_stall=reports.append)
        wd.tick()
        now[0] = 9.0
        assert wd.check() is None          # within budget: silent
        now[0] = 11.0
        report = wd.check()                # fired
        assert report is not None and len(reports) == 1
        assert wd.check() is None          # exactly once per stall
        now[0] = 500.0
        assert wd.check() is None          # still the same stall
        # payload: all-thread stacks, device memory stats, recorder tail
        assert any("test_telemetry" in "".join(stack)
                   for stack in report["stacks"].values())
        assert isinstance(report["device_memory_stats"], dict)
        assert [e["name"] for e in report["flight_recorder"]] == [
            "last-thing-before-hang"]
        assert report["silence_s"] == pytest.approx(11.0)

    def test_tick_rearms_for_the_next_stall(self):
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0], logger=_SilentLogger())
        now[0] = 6.0
        assert wd.check() is not None
        wd.tick()                          # progress: re-armed
        now[0] = 8.0
        assert wd.check() is None
        now[0] = 12.0
        assert wd.check() is not None      # second stall fires again
        assert wd.stall_count == 2

    def test_raise_on_stall(self):
        now = [0.0]
        wd = StallWatchdog(1.0, clock=lambda: now[0], raise_on_stall=True,
                           logger=_SilentLogger())
        now[0] = 2.0
        with pytest.raises(StallError):
            wd.check()

    def test_background_thread_fires_and_stays_silent_when_ticked(self):
        fired = threading.Event()
        wd = StallWatchdog(0.1, poll_interval_s=0.02,
                           on_stall=lambda r: fired.set(),
                           logger=_SilentLogger())
        with wd:
            for _ in range(5):
                wd.tick()
                time.sleep(0.02)
            assert not fired.is_set()      # heartbeats kept it silent
            assert fired.wait(timeout=5.0)  # then silence fires it

    def test_rejects_nonpositive_timeout(self):
        with pytest.raises(ValueError):
            StallWatchdog(0.0)


class _SilentLogger:
    def error(self, *a, **k):
        pass


# ---------------------------------------------------------------------------
# request-scoped trace context (ISSUE 8)
# ---------------------------------------------------------------------------


class TestTraceContext:
    def test_parse_traceparent_valid(self):
        from accelerate_tpu.telemetry import parse_traceparent

        tid, pid = "ab" * 16, "cd" * 8
        assert parse_traceparent(f"00-{tid}-{pid}-01") == (tid, pid)
        # case-insensitive per spec, normalized to lowercase
        assert parse_traceparent(f"00-{tid.upper()}-{pid}-01") == (tid, pid)

    @pytest.mark.parametrize("bad", [
        None, "", "garbage", "00-short-0011223344556677-01",
        "00-" + "0" * 32 + "-" + "cd" * 8 + "-01",   # all-zero trace id
        "00-" + "ab" * 16 + "-" + "0" * 16 + "-01",  # all-zero parent
        "ff-" + "ab" * 16 + "-" + "cd" * 8 + "-01",  # reserved version
        "00-" + "zz" * 16 + "-" + "cd" * 8 + "-01",  # non-hex
        "00-" + "ab" * 16 + "-" + "cd" * 8,          # missing flags
    ])
    def test_parse_traceparent_malformed_is_none(self, bad):
        """Satellite contract: anything malformed -> None, so the caller
        mints a fresh id instead of erroring or propagating garbage."""
        from accelerate_tpu.telemetry import parse_traceparent

        assert parse_traceparent(bad) is None

    def test_new_trace_id_shape(self):
        from accelerate_tpu.telemetry import new_trace_id

        ids = {new_trace_id() for _ in range(32)}
        assert len(ids) == 32
        assert all(len(t) == 32 and int(t, 16) >= 0 for t in ids)

    def test_explicit_context_and_record_span_share_a_trace(self):
        """The request-tracing shape: a pre-allocated root id, live child
        spans joined via trace=/parent=, retrospective spans via
        record_span — all indexed under one trace id."""
        from accelerate_tpu.telemetry import (
            new_trace_id,
            record_span,
            trace_events,
        )
        from accelerate_tpu.telemetry.trace import next_span_id

        configure_tracing(enabled=True, annotate=False)
        tid = new_trace_id()
        root = next_span_id()
        with span("admit", trace=tid, parent=root, slot=1):
            pass
        record_span("queue_wait", 1.0, 2.0, trace=tid, parent=root)
        record_span("request", 0.5, 4.0, trace=tid, span_id=root,
                    status="finished")
        events = trace_events(tid)
        assert [e["name"] for e in events] == ["request", "queue_wait",
                                               "admit"]  # by start time
        assert all(e["trace_id"] == tid for e in events)
        children = [e for e in events if e["name"] != "request"]
        assert all(e["parent_id"] == root for e in children)
        root_ev = next(e for e in events if e["name"] == "request")
        assert root_ev["span_id"] == root
        assert root_ev["attrs"]["status"] == "finished"
        # the filtered chrome export carries exactly this trace
        doc = export_chrome_trace(trace_id=tid)
        assert len(doc["traceEvents"]) == 3

    def test_record_span_and_span_share_a_timebase(self):
        """The ring is one timeline: a retrospective span given in
        `time.monotonic` (or `perf_counter`) seconds and a live span, which
        reads `perf_counter_ns`, nest as the calls did. On this platform
        the two clocks are one (CLOCK_MONOTONIC); were they not,
        `record_span` would have to convert."""
        configure_tracing(enabled=True, annotate=False)
        assert abs(time.monotonic_ns() - time.perf_counter_ns()) < 1_000_000
        for clock in (time.monotonic, time.perf_counter):
            clear_flight_recorder()
            t0 = clock()
            with span("live"):
                time.sleep(0.002)
            t1 = clock()
            record_span("retro", t0, t1)
            live, retro = flight_recorder()
            assert retro["start_ns"] <= live["start_ns"]
            assert (live["start_ns"] + live["dur_ns"]
                    <= retro["start_ns"] + retro["dur_ns"] + 1000)
            assert retro["dur_ns"] < live["dur_ns"] + 50_000_000

    def test_span_links(self):
        """A span serving many requests at once (one batched decode step)
        links their traces without belonging to any one of them."""
        configure_tracing(enabled=True, annotate=False)
        with span("decode_step", links=["t-a", "t-b"]):
            pass
        ev = flight_recorder()[-1]
        assert ev["links"] == ["t-a", "t-b"]
        doc = export_chrome_trace()
        assert doc["traceEvents"][-1]["args"]["links"] == ["t-a", "t-b"]

    def test_ring_eviction_prunes_trace_index(self):
        from accelerate_tpu.telemetry import record_span, trace_events

        configure_tracing(enabled=True, annotate=False, ring_size=4)
        try:
            for i in range(10):
                record_span("x", 0.0, 1.0, trace=f"t{i}")
            assert len(flight_recorder()) == 4
            assert trace_events("t0") == []          # evicted AND pruned
            assert len(trace_events("t9")) == 1
        finally:
            configure_tracing(enabled=False, ring_size=DEFAULT_RING_SIZE)

    def test_record_span_disabled_is_free(self):
        from accelerate_tpu.telemetry import record_span, trace_events

        assert record_span("x", 0.0, 1.0, trace="t") == 0
        assert flight_recorder() == [] and trace_events("t") == []

    def test_head_sampling_rates(self):
        from accelerate_tpu.telemetry import head_sample

        # disabled tracing: never sampled, whatever the rates say
        configure_tracing(enabled=False, sample_rates={"gold": 1.0})
        assert head_sample("gold") is False
        configure_tracing(enabled=True,
                          sample_rates={"gold": 1.0, "bronze": 0.0},
                          default_sample_rate=1.0)
        try:
            assert all(head_sample("gold") for _ in range(50))
            assert not any(head_sample("bronze") for _ in range(50))
            assert all(head_sample("unlisted") for _ in range(50))
            configure_tracing(default_sample_rate=0.0)
            assert not any(head_sample("unlisted") for _ in range(50))
        finally:
            configure_tracing(enabled=False, sample_rates={},
                              default_sample_rate=1.0)


# ---------------------------------------------------------------------------
# exporter: content negotiation, HEAD, exemplars (ISSUE 8 satellites)
# ---------------------------------------------------------------------------


class TestExportNegotiation:
    def _server(self):
        r = MetricsRegistry()
        r.counter("up_total").inc()
        h = r.histogram("serving_ttft_seconds")
        h.record(0.05, exemplar="ee" * 16)
        return MetricsServer(registry=r, port=0, host="127.0.0.1").start(), r

    def test_content_type_and_head_support(self):
        """Satellite: proper `text/plain; version=0.0.4` Content-Type and
        HEAD answered with headers only."""
        server, _ = self._server()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            resp = urllib.request.urlopen(url, timeout=5)
            assert resp.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            body = resp.read()
            assert b"up_total" in body
            head_req = urllib.request.Request(url, method="HEAD")
            head = urllib.request.urlopen(head_req, timeout=5)
            assert head.status == 200
            assert head.headers["Content-Type"].startswith(
                "text/plain; version=0.0.4")
            assert int(head.headers["Content-Length"]) == len(body)
            assert head.read() == b""
        finally:
            server.stop()

    def test_two_concurrent_scrapes(self):
        """Satellite regression: two scrapers hitting the ThreadingHTTP
        endpoint at once both get complete, parseable expositions."""
        server, _ = self._server()
        results: list[bytes] = []
        errors: list[Exception] = []

        def scrape():
            try:
                url = f"http://127.0.0.1:{server.port}/metrics"
                results.append(urllib.request.urlopen(url, timeout=10).read())
            except Exception as e:  # pragma: no cover - failure detail
                errors.append(e)

        try:
            threads = [threading.Thread(target=scrape) for _ in range(2)]
            for t in threads:
                t.start()
            for t in threads:
                t.join(timeout=10)
            assert not errors
            assert len(results) == 2
            for body in results:
                series = _parse_exposition(body.decode())
                assert float(series["up_total"]) == 1.0
        finally:
            server.stop()

    def test_openmetrics_negotiation_carries_exemplars(self):
        """An OpenMetrics Accept switches the exemplar-carrying series to
        bucket histograms with `# {trace_id=...}` exemplars and an EOF
        terminator; the default scrape is unchanged 0.0.4."""
        server, _ = self._server()
        try:
            url = f"http://127.0.0.1:{server.port}/metrics"
            req = urllib.request.Request(
                url, headers={"Accept": "application/openmetrics-text"})
            resp = urllib.request.urlopen(req, timeout=5)
            assert resp.headers["Content-Type"].startswith(
                "application/openmetrics-text")
            body = resp.read().decode()
            assert "# TYPE serving_ttft_seconds histogram" in body
            assert 'serving_ttft_seconds_bucket{le="+Inf"} 1' in body
            assert f'trace_id="{"ee" * 16}"' in body
            assert body.rstrip().endswith("# EOF")
            # OpenMetrics 1.0: counter FAMILY without _total, sample
            # with it — a strict OM parser rejects the scrape otherwise
            assert "# TYPE up counter" in body
            assert "# TYPE up_total counter" not in body
            assert "up_total 1.0" in body
            plain = urllib.request.urlopen(url, timeout=5).read().decode()
            assert "trace_id" not in plain and "# EOF" not in plain
            assert "# TYPE serving_ttft_seconds summary" in plain
            assert "# TYPE up_total counter" in plain  # 0.0.4 unchanged
        finally:
            server.stop()

    def test_exemplar_bounded_and_reset(self):
        h = StreamingHistogram()
        for i in range(1, 200):
            h.record(float(i), exemplar=f"t{i}")
        assert len(h.exemplars()) <= h._MAX_EXEMPLARS
        # the tail is kept: the largest value's bucket still has one
        assert any(v[1] == "t199" for v in h.exemplars().values())
        h.reset()
        assert h.exemplars() == {} and h.count == 0


# ---------------------------------------------------------------------------
# incident bundles (ISSUE 8 tentpole c)
# ---------------------------------------------------------------------------


class TestIncidentBundles:
    def _fire(self, tmp_path, dumps=None, registry=None):
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0], logger=_SilentLogger(),
                           incident_dir=str(tmp_path), registry=registry,
                           dumps=dumps, name="unit")
        now[0] = 6.0
        return wd.check()

    def test_stall_writes_complete_bundle(self, tmp_path):
        configure_tracing(enabled=True, annotate=False)
        with span("last-act"):
            pass
        r = MetricsRegistry()
        r.counter("serving_tokens_out_total").inc(7)
        report = self._fire(tmp_path, registry=r,
                            dumps=lambda: {"scheduler": {"queue_depth": 2}})
        assert "bundle_path" in report
        path = report["bundle_path"]
        files = sorted(os.listdir(path))
        for fname in ("manifest.json", "report.json", "stacks.txt",
                      "trace.json", "metrics.json", "metrics.prom",
                      "scheduler.json"):
            assert fname in files, files
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["version"] >= 1
        assert manifest["silence_s"] == pytest.approx(6.0)
        assert set(manifest["files"]) == set(files) - {"manifest.json"}
        trace_doc = json.load(open(os.path.join(path, "trace.json")))
        assert any(e["name"] == "last-act" for e in trace_doc["traceEvents"])
        sched = json.load(open(os.path.join(path, "scheduler.json")))
        assert sched == {"queue_depth": 2}
        prom = open(os.path.join(path, "metrics.prom")).read()
        assert "serving_tokens_out_total 7.0" in prom
        assert "incident" in os.path.basename(path)

    def test_no_incident_dir_means_no_bundle(self, tmp_path, monkeypatch):
        monkeypatch.delenv("ACCELERATE_TPU_INCIDENT_DIR", raising=False)
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0], logger=_SilentLogger())
        now[0] = 6.0
        report = wd.check()
        assert "bundle_path" not in report
        assert os.listdir(tmp_path) == []

    def test_env_var_arms_bundles(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ACCELERATE_TPU_INCIDENT_DIR", str(tmp_path))
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0], logger=_SilentLogger())
        assert wd.incident_dir == str(tmp_path)
        now[0] = 6.0
        report = wd.check()
        assert report["bundle_path"].startswith(str(tmp_path))

    def test_dumps_failure_costs_only_the_dump_files(self, tmp_path):
        """Review regression: dumps() walks live engine state and may
        throw mid-stall — the bundle (stacks/trace/metrics) must still
        land, with the failure recorded in a dumps_error file."""
        r = MetricsRegistry()
        r.counter("alive_total").inc()

        def exploding_dumps():
            raise RuntimeError("deque mutated during iteration")

        report = self._fire(tmp_path, registry=r, dumps=exploding_dumps)
        assert "bundle_path" in report, report.get("bundle_error")
        files = set(os.listdir(report["bundle_path"]))
        assert {"manifest.json", "report.json", "stacks.txt",
                "trace.json", "metrics.json", "dumps_error.json"} <= files
        err = json.load(open(os.path.join(report["bundle_path"],
                                          "dumps_error.json")))
        assert "deque mutated" in err["error"]

    def test_bundle_failure_does_not_mask_the_report(self, tmp_path):
        """Forensics must never break the stall report: an unwritable
        bundle dir degrades to bundle_error, the report still lands."""
        bad = tmp_path / "file-not-dir"
        bad.write_text("x")
        now = [0.0]
        wd = StallWatchdog(5.0, clock=lambda: now[0], logger=_SilentLogger(),
                           incident_dir=str(bad))
        now[0] = 6.0
        report = wd.check()
        assert report is not None and "bundle_error" in report

    def test_exception_report_shape(self, tmp_path):
        from accelerate_tpu.telemetry import (
            build_exception_report,
            write_incident_bundle,
        )

        try:
            raise RuntimeError("drive loop died")
        except RuntimeError as e:
            report = build_exception_report(e, name="drive-loop")
        assert "drive loop died" in report["error"]
        assert any("drive loop died" in ln for ln in report["traceback"])
        assert report["stacks"]
        path = write_incident_bundle(str(tmp_path), report,
                                     name="drive-loop")
        manifest = json.load(open(os.path.join(path, "manifest.json")))
        assert manifest["kind"] == "drive-loop"
        assert "drive loop died" in manifest["error"]

    def test_same_second_bundles_get_distinct_dirs(self, tmp_path):
        from accelerate_tpu.telemetry import write_incident_bundle

        p1 = write_incident_bundle(str(tmp_path), {"stacks": {}}, name="x")
        p2 = write_incident_bundle(str(tmp_path), {"stacks": {}}, name="x")
        assert p1 != p2 and os.path.isdir(p1) and os.path.isdir(p2)

    def test_incident_cli_list_and_show(self, tmp_path, capsys):
        """`accelerate-tpu incident` renders bundles: list newest-first
        with indices, show by index/name/path, sane exit codes."""
        from accelerate_tpu.commands.accelerate_cli import main
        from accelerate_tpu.telemetry import write_incident_bundle

        assert main(["incident", "list", "--dir", str(tmp_path)]) == 1
        capsys.readouterr()
        report = {"silence_s": 7.5, "stacks": {"MainThread-1": ["  fake\n"]},
                  "flight_recorder": [
                      {"name": "serving.decode", "dur_ns": 1000,
                       "trace_id": "ab" * 16, "span_id": 1, "parent_id": 0}]}
        path = write_incident_bundle(str(tmp_path), report, name="stall")
        assert main(["incident", "list", "--dir", str(tmp_path)]) == 0
        out = capsys.readouterr().out
        assert "[0]" in out and "stall" in out and "7.5s" in out
        for ref in ("0", os.path.basename(path), path):
            assert main(["incident", "show", ref,
                         "--dir", str(tmp_path)]) == 0
            out = capsys.readouterr().out
            assert "silence  7.5s" in out
            assert "serving.decode" in out
        assert main(["incident", "show", "nope",
                     "--dir", str(tmp_path)]) == 2
        rc = main(["incident", "list", "--dir", str(tmp_path),
                   "--format", "json"])
        assert rc == 0
        listed = json.loads(capsys.readouterr().out)
        assert listed[0]["path"] == path

    def test_incident_cli_requires_a_dir(self, monkeypatch, capsys):
        from accelerate_tpu.commands.accelerate_cli import main

        monkeypatch.delenv("ACCELERATE_TPU_INCIDENT_DIR", raising=False)
        assert main(["incident", "list"]) == 2


# ---------------------------------------------------------------------------
# overhead guards (CI satellite): observability off must stay ~free
# ---------------------------------------------------------------------------


class TestOverheadGuards:
    N = 20_000

    def _time(self, fn) -> float:
        t0 = time.perf_counter()
        fn()
        return time.perf_counter() - t0

    @pytest.mark.parametrize("site", ["bare", "attrs_and_set"])
    def test_disabled_span_cost_bounded(self, site):
        """Disabled spans sit on dispatch-path code permanently; their cost
        must stay within a generous multiple of a plain function call (and
        an absolute per-iteration ceiling, so tier-1 stays deterministic
        on slow shared runners). `attrs_and_set` is the shape of the
        serving engine's phase sites: scalars at hand going in, counts
        attached at the end."""
        assert not tracing_enabled()

        def noop():
            pass

        def baseline():
            for _ in range(self.N):
                noop()

        def bare():
            for _ in range(self.N):
                with span("x"):
                    pass

        def attrs_and_set():
            for i in range(self.N):
                with span("x", trace=0, queue_depth=i) as sp:
                    sp.set(admitted=i, shed=0)

        with_span = {"bare": bare, "attrs_and_set": attrs_and_set}[site]

        baseline()  # warm both paths
        with_span()
        base = min(self._time(baseline) for _ in range(3))
        spanned = min(self._time(with_span) for _ in range(3))
        per_iter_us = spanned / self.N * 1e6
        assert per_iter_us < 50.0, f"disabled span {per_iter_us:.2f}us/iter"
        assert spanned < max(base, 1e-9) * 100, (spanned, base)

    @pytest.mark.parametrize("what", ["no_span_object", "no_links_list"])
    def test_disabled_engine_step_builds_nothing_for_tracing(
            self, what, monkeypatch):
        """With tracing off a serving step constructs no `_Span` (every
        phase site gets the shared null span) and builds no `links` list
        for its decode dispatch."""
        import jax
        import jax.numpy as jnp

        from accelerate_tpu.models import gpt2
        from accelerate_tpu.serving import Engine, EngineConfig
        from accelerate_tpu.telemetry import trace as trace_mod

        assert not tracing_enabled()
        cfg = gpt2.GPT2Config.tiny()
        eng = Engine(gpt2, cfg, gpt2.init_params(cfg, jax.random.key(0)),
                     EngineConfig(num_slots=2, max_len=64, prefill_chunk=8,
                                  cache_dtype=jnp.float32))
        built = []
        if what == "no_span_object":
            init = trace_mod._Span.__init__

            def counting_init(self, *args, **kwargs):
                built.append(args[0])
                init(self, *args, **kwargs)

            monkeypatch.setattr(trace_mod._Span, "__init__", counting_init)
        else:
            links = Engine._step_links

            def recording_links(slots):
                built.append(links(slots))
                return built[-1]

            monkeypatch.setattr(Engine, "_step_links",
                                staticmethod(recording_links))
        r = eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=4)
        assert len(list(eng.stream(r))) == 4
        if what == "no_span_object":
            assert built == []
        else:
            assert built == [None] * eng.metrics.decode_steps and built
        assert flight_recorder() == []
        # the same step with tracing on does build them: the probes work
        configure_tracing(enabled=True, annotate=False)
        built.clear()
        r = eng.submit(np.arange(1, 12, dtype=np.int32), max_new_tokens=2)
        list(eng.stream(r))
        assert built and built != [None] * len(built)

    def test_registry_increment_cost_bounded(self):
        r = MetricsRegistry()
        c = r.counter("n")
        h = r.histogram("h")

        def work():
            for _ in range(self.N):
                c.inc()
                h.record(0.001)

        work()
        best = min(self._time(work) for _ in range(3))
        per_iter_us = best / self.N * 1e6
        assert per_iter_us < 100.0, f"inc+record {per_iter_us:.2f}us/iter"


# ---------------------------------------------------------------------------
# StepTimer on the shared histograms
# ---------------------------------------------------------------------------


class TestStepTimerTelemetry:
    def test_summary_reports_tail_latency(self):
        from accelerate_tpu.profiler import StepTimer

        timer = StepTimer(warmup_steps=0)
        for v in [0.1] * 90 + [1.0] * 10:
            timer._step_hist.record(v)
        s = timer.summary()
        assert s["step_time_p50_s"] == pytest.approx(0.1, rel=0.03)
        assert s["step_time_p99_s"] == pytest.approx(1.0, rel=0.03)
        assert s["mean_step_time_s"] == pytest.approx(0.19, rel=0.01)

    def test_registry_backed_timer_publishes_series(self):
        from accelerate_tpu.profiler import StepTimer

        r = MetricsRegistry()
        timer = StepTimer(warmup_steps=0, registry=r, name="train")
        with timer.dispatch():
            pass
        timer.tick()
        timer.tick()
        snap = r.snapshot()
        assert snap["histograms"]["train_time_seconds"]["count"] == 1.0
        assert snap["histograms"]["train_dispatch_seconds"]["count"] == 1.0
        # the exporter sees the same series
        assert "train_time_seconds" in render_prometheus(r)

    def test_fresh_timer_does_not_inherit_shared_series(self):
        """Registry series are shared by name: a NEW StepTimer must be
        able to start clean (reset) without unregistering the series."""
        from accelerate_tpu.profiler import StepTimer

        r = MetricsRegistry()
        warm = StepTimer(warmup_steps=0, registry=r, name="train")
        warm.tick()
        warm.tick()
        assert warm.steps_recorded == 1
        fresh = StepTimer(warmup_steps=0, registry=r, name="train")
        fresh.reset()                       # the warmup-window pattern
        assert fresh.steps_recorded == 0
        fresh.tick()
        fresh.tick()
        assert fresh.steps_recorded == 1    # only its own samples
        # still the same registered series object for the exporter
        assert r.histogram("train_time_seconds") is fresh._step_hist

    def test_serving_metrics_percentiles_use_shared_helper(self):
        """Satellite (dedup): ServingMetrics percentiles come from the
        shared StreamingHistogram and agree with numpy.percentile."""
        from accelerate_tpu.serving.metrics import ServingMetrics

        m = ServingMetrics()
        rng = np.random.default_rng(0)
        samples = rng.lognormal(-3, 0.5, 5000)
        for v in samples:
            m.ttft_s.record(float(v))
        s = m.summary()
        for q, key in ((50, "ttft_p50_ms"), (99, "ttft_p99_ms")):
            exact = float(np.percentile(samples, q)) * 1e3
            assert s[key] == pytest.approx(exact, rel=0.03)


# ---------------------------------------------------------------------------
# tier-1 collection + import guards
# ---------------------------------------------------------------------------


def test_telemetry_tests_are_tier1_collected():
    """The ROADMAP tier-1 command runs `pytest tests/ -m 'not slow'`; this
    file must be collected by it (mirror of the guard in
    tests/test_prefetch.py)."""
    roadmap = os.path.join(os.path.dirname(__file__), os.pardir, "ROADMAP.md")
    with open(roadmap) as f:
        text = f.read()
    assert "-m 'not slow'" in text and "pytest tests/" in text, (
        "tier-1 command changed; update this guard"
    )


def test_telemetry_imports_without_jax_device_init():
    """`accelerate_tpu.telemetry` must be importable in collectors/CLI
    tools without initializing a jax backend (device init is expensive and
    can hang at backend init)."""
    code = (
        "import accelerate_tpu.telemetry as t\n"
        "t.get_registry().counter('probe').inc()\n"
        "assert t.render_prometheus(t.get_registry())\n"
        "from jax._src import xla_bridge\n"
        "assert not xla_bridge.backends_are_initialized(), "
        "'telemetry import initialized a jax backend'\n"
    )
    from accelerate_tpu.test_utils import checkout_child_env

    env = checkout_child_env({"JAX_PLATFORMS": "cpu"})
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr[-2000:]

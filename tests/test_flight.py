"""Flight recorder: SLO-triggered, cluster-correlated diagnostic capture.

Covers control/flight.py end to end -- injected-clock trigger math for every
trigger kind, cooldown suppression, the pre-sampling span ring, bundle
schema round-trip against tools/flight_check.py, on-disk retention, the
2-node correlated capture over the `flightcapture` peer verb -- plus the
satellite planes that shipped with it: the buffered WebhookTarget audit
sink (control/logging.py) and the PubSub drop disclosure (control/pubsub.py).

The end-to-end acceptance test stands up a real 2-node in-process cluster,
runs a loadgen scenario with an armed drive-fault window, and asserts the
flight gate: every node auto-captured a bundle covering the fault window,
and the healthy phase produced none.
"""

import importlib.util
import json
import os
import queue
import threading
import time
from pathlib import Path

import pytest

from minio_tpu.control import tracing
from minio_tpu.control.degrade import DegradeStats
from minio_tpu.control.flight import (
    BUNDLE_SCHEMA,
    TRIGGER_KINDS,
    FlightRecorder,
    GLOBAL_FLIGHT,
    SpanRing,
    _safe_tag,
)
from minio_tpu.control.logging import WebhookTarget
from minio_tpu.control.perf import PerfSys
from minio_tpu.control.pubsub import GLOBAL_TRACE, PubSub

_REPO = Path(__file__).resolve().parent.parent
_spec = importlib.util.spec_from_file_location(
    "flight_check", _REPO / "tools" / "flight_check.py"
)
flight_check = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(flight_check)

_LINT_SPEC = importlib.util.spec_from_file_location(
    "metrics_lint", _REPO / "tools" / "metrics_lint.py"
)
metrics_lint = importlib.util.module_from_spec(_LINT_SPEC)
_LINT_SPEC.loader.exec_module(metrics_lint)


class _Span:
    """Minimal stand-in for tracing.Span in record_span tests."""

    def __init__(self, name="op", layer="api", trace_id="t-1"):
        self.name = name
        self.layer = layer
        self.trace_id = trace_id


def _recorder(tmp_path, **kw) -> FlightRecorder:
    """A recorder with every knob pinned (no env dependence) over a private
    PerfSys/DegradeStats pair, so injected-clock tests see only their own
    traffic."""
    args = dict(
        dir=str(tmp_path),
        window_s=30.0,
        cooldown_s=60.0,
        retain=16,
        poll_s=1.0,
        err_rate=0.5,
        p99_ms=0.0,
        min_ops=10,
        deadline_burst=3,
        perf=PerfSys(),
        degrade=DegradeStats(),
    )
    args.update(kw)
    return FlightRecorder(**args)


# The injected clock: check_triggers(now) judges second int(now) - 1.
T = 1000.0


class TestTriggerMath:
    """Every trigger kind against an injected clock and private counters."""

    def test_error_spike_fires_on_closed_second(self, tmp_path):
        fr = _recorder(tmp_path, min_ops=5)
        for _ in range(6):
            fr.perf.timeseries.record("get", 0.01, ok=False, now=T - 0.8)
        for _ in range(4):
            fr.perf.timeseries.record("get", 0.01, ok=True, now=T - 0.8)
        fired = fr.check_triggers(now=T + 0.5)
        reasons = [r for r, _ in fired]
        assert reasons == ["error-spike"]
        detail = fired[0][1]
        assert detail["second"] == int(T) - 1
        assert detail["count"] == 10 and detail["errors"] == 6
        assert detail["rate"] == pytest.approx(0.6)

    def test_min_ops_floor_mutes_tiny_seconds(self, tmp_path):
        # 3 ops, 100% errors: statistically meaningless, must not fire.
        fr = _recorder(tmp_path, min_ops=5)
        for _ in range(3):
            fr.perf.timeseries.record("get", 0.01, ok=False, now=T - 0.8)
        assert fr.check_triggers(now=T + 0.5) == []

    def test_p99_threshold_fires_without_errors(self, tmp_path):
        fr = _recorder(tmp_path, min_ops=5, p99_ms=50.0)
        for _ in range(20):
            fr.perf.timeseries.record("get", 0.2, ok=True, now=T - 0.8)
        fired = fr.check_triggers(now=T + 0.5)
        reasons = [r for r, _ in fired]
        assert reasons == ["p99"]  # zero errors: no error-spike co-fire
        assert fired[0][1]["p99_ms"] >= 50.0

    def test_each_second_judged_once(self, tmp_path):
        fr = _recorder(tmp_path, min_ops=5)
        for _ in range(10):
            fr.perf.timeseries.record("get", 0.01, ok=False, now=T - 0.8)
        assert len(fr.check_triggers(now=T + 0.5)) == 1
        # Same second re-checked: already judged, and the degrade counters
        # didn't move, so nothing fires.
        assert fr.check_triggers(now=T + 0.6) == []

    def test_shed_edge_fires_after_baseline(self, tmp_path):
        fr = _recorder(tmp_path)
        # First poll only establishes the baseline -- a recorder attaching
        # to a long-lived process must not fire on history.
        fr.degrade.record_shed("read")
        assert fr.check_triggers(now=T + 0.5) == []
        fr.degrade.record_shed("read")
        fired = fr.check_triggers(now=T + 1.5)
        assert [r for r, _ in fired] == ["shed"]
        assert fired[0][1]["sheds"] == 1

    def test_breaker_open_edge(self, tmp_path):
        fr = _recorder(tmp_path)
        assert fr.check_triggers(now=T + 0.5) == []
        fr.degrade.record_breaker(tripped=True)
        fired = fr.check_triggers(now=T + 1.5)
        assert [r for r, _ in fired] == ["breaker-open"]

    def test_deadline_burst_needs_threshold(self, tmp_path):
        fr = _recorder(tmp_path, deadline_burst=3)
        assert fr.check_triggers(now=T + 0.5) == []
        fr.degrade.record_deadline_abort("erasure.read")
        fr.degrade.record_deadline_abort("erasure.read")
        assert fr.check_triggers(now=T + 1.5) == []  # 2 < burst threshold
        for _ in range(3):
            fr.degrade.record_deadline_abort("erasure.read")
        fired = fr.check_triggers(now=T + 2.5)
        assert [r for r, _ in fired] == ["deadline-burst"]
        assert fired[0][1]["aborts"] == 3

    def test_poll_once_cooldown_suppresses_second_incident(self, tmp_path):
        fr = _recorder(tmp_path, min_ops=5, cooldown_s=60.0, window_s=5.0)
        for _ in range(10):
            fr.perf.timeseries.record("get", 0.01, ok=False, now=T - 0.8)
        inc = fr.poll_once(now=T + 0.5)
        assert inc is not None and inc["reason"] == "error-spike"
        # A second spike inside the cooldown: evaluated but muted.
        for _ in range(10):
            fr.perf.timeseries.record("get", 0.01, ok=False, now=T + 4.2)
        assert fr.poll_once(now=T + 5.5) is None
        assert fr.stats()["suppressed"] == 1
        assert fr.stats()["triggers"] == {"error-spike": 1}

    def test_cofired_reasons_ride_in_detail_also(self, tmp_path):
        fr = _recorder(tmp_path, min_ops=5, p99_ms=50.0, window_s=5.0)
        for _ in range(10):
            fr.perf.timeseries.record("get", 0.2, ok=False, now=T - 0.8)
        inc = fr.poll_once(now=T + 0.5)
        assert inc["reason"] == "error-spike"  # one incident, not two
        assert inc["detail"]["also"] == ["p99"]
        assert fr.stats()["triggers"] == {"error-spike": 1}

    def test_incident_window_matches_window_knob(self, tmp_path):
        fr = _recorder(tmp_path, window_s=12.0)
        inc = fr.trigger("manual", now=T, fan_out=False)
        assert inc["t1"] == T and inc["t0"] == T - 12.0
        assert inc["reason"] in TRIGGER_KINDS


class TestSpanRing:
    def test_bounded_eviction_is_oldest_first(self):
        ring = SpanRing(32)
        for i in range(100):
            ring.append({"t": float(i)})
        assert len(ring) == 32
        assert ring.window(0, 1000) == [{"t": float(i)} for i in range(68, 100)]

    def test_maxlen_floor(self):
        assert SpanRing(2).maxlen == 16

    def test_window_filters_inclusive(self):
        ring = SpanRing(64)
        for t in (1.0, 2.0, 3.0, 4.0):
            ring.append({"t": t})
        assert [r["t"] for r in ring.window(2.0, 3.0)] == [2.0, 3.0]


class TestBundleStore:
    def test_manual_trigger_round_trips_through_flight_check(self, tmp_path):
        fr = _recorder(tmp_path)
        fr.record_span(_Span("GetObject", "api", "tr-1"), 0.005)
        fr.record_span(_Span("PutObject", "api", "tr-2"), 0.050, error="faulted")
        inc = fr.trigger("manual", detail={"via": "test"}, fan_out=False)
        metas = fr.list()
        assert len(metas) == 1
        bundle = fr.get(metas[0]["id"])
        assert flight_check.check_bundle(bundle, "test") == []
        assert bundle["flight_bundle"] == BUNDLE_SCHEMA
        assert bundle["id"] == f"{inc['incident']}__{_safe_tag(fr.node_id)}"
        names = {s["name"] for s in bundle["spans"]}
        assert names == {"GetObject", "PutObject"}
        errs = [s for s in bundle["spans"] if s.get("error")]
        assert len(errs) == 1 and errs[0]["error"] == "faulted"
        # Bare incident id resolves to the same bundle (GET /flight/{id}).
        assert fr.get(inc["incident"])["id"] == bundle["id"]

    def test_capture_is_idempotent_per_incident_and_node(self, tmp_path):
        fr = _recorder(tmp_path)
        inc = fr.trigger("manual", fan_out=False)
        assert fr.stats()["bundles_written"] == 1
        assert fr.capture(inc) is None  # replayed fanout: no-op
        assert fr.stats()["bundles_written"] == 1
        # The receiving side arms its cooldown off the incident window.
        assert fr.stats()["last_trigger_time"] >= inc["t1"]

    def test_retention_prunes_oldest_per_node(self, tmp_path):
        fr = _recorder(tmp_path, retain=2)
        incidents = [fr.trigger("manual", fan_out=False) for _ in range(4)]
        files = [n for n in os.listdir(str(tmp_path)) if n.startswith("flight-")]
        assert len(files) == 2
        assert fr.stats()["bundles_written"] == 4
        assert fr.stats()["bundles_pruned"] == 2
        # The survivors are the two NEWEST incidents.
        kept = {m["incident"] for m in fr.list()}
        assert kept == {i["incident"] for i in incidents[2:]}
        assert flight_check.check_dir(str(tmp_path), retain=2) == []

    def test_list_is_newest_first(self, tmp_path):
        fr = _recorder(tmp_path)
        a = fr.trigger("manual", fan_out=False)
        time.sleep(0.02)
        b = fr.trigger("manual", fan_out=False)
        metas = fr.list()
        assert [m["incident"] for m in metas] == [b["incident"], a["incident"]]

    def test_corrupt_bundle_files_are_skipped(self, tmp_path):
        fr = _recorder(tmp_path)
        fr.trigger("manual", fan_out=False)
        (tmp_path / "flight-garbage__local.json").write_text("{not json")
        assert len(fr.list()) == 1

    def test_flight_check_flags_a_tampered_bundle(self, tmp_path):
        fr = _recorder(tmp_path)
        inc = fr.trigger("manual", fan_out=False)
        bundle = fr.get(inc["incident"])
        bundle["reason"] = "not-a-reason"
        problems = flight_check.check_bundle(bundle, "test")
        assert problems and any("reason" in p for p in problems)


class TestPreSamplingRing:
    """Satellite: MTPU_TRACE_SAMPLE must never blind the black box."""

    def test_sampled_out_root_still_feeds_flight_ring(self, monkeypatch):
        monkeypatch.setenv("MTPU_TRACE_SAMPLE", "0")  # sample NOTHING
        GLOBAL_FLIGHT.ring.clear()
        with tracing.root_span("op", "flightlayer", "trace-flight-presample") as root:
            assert root.sampled is False
            with tracing.span("child-stage", "flightlayer"):
                pass
        recs = [
            r for r in GLOBAL_FLIGHT.ring.window(0, time.time() + 1)
            if r["trace"] == "trace-flight-presample"
        ]
        # The root landed despite the 0% sample rate; the child did not
        # (the ring holds ROOT spans only -- the bundle is a request index,
        # the full tree lives in the trace plane).
        assert [r["name"] for r in recs] == ["op"]
        assert recs[0]["layer"] == "flightlayer"

    def test_record_span_overhead_is_microseconds(self, tmp_path):
        # Tier-1 smoke for the O(1) off-lock append claim: the hot-path
        # feed must stay far under 500us per span (same budget the
        # disarmed stage-mark test in test_perf.py holds).
        fr = _recorder(tmp_path)
        span = _Span("GetObject", "api", "tr-bench")
        n = 2000
        t0 = time.perf_counter()
        for _ in range(n):
            fr.record_span(span, 0.001)
        dt = time.perf_counter() - t0
        assert dt / n < 500e-6, f"record_span cost {dt / n * 1e6:.1f}us"


class TestWebhookTargetQueue:
    """Satellite: the audit webhook never blocks the request path."""

    class _StubSession:
        def __init__(self, gate=None, fail_times=0):
            self.gate = gate
            self.fail_times = fail_times
            self.posts = []

        def post(self, endpoint, json=None, timeout=None):
            if self.gate is not None:
                self.gate.wait()
            if self.fail_times > 0:
                self.fail_times -= 1
                raise OSError("connection refused")
            self.posts.append(json)

    def _target(self, **kw) -> WebhookTarget:
        t = WebhookTarget("http://127.0.0.1:1/audit", **kw)
        t.session = self._stub  # swap before any entry is enqueued
        return t

    def test_full_queue_drops_and_counts(self):
        gate = threading.Event()  # held: the sender blocks inside post()
        self._stub = self._StubSession(gate=gate)
        t = self._target(queue_size=2)
        try:
            t.send({"n": 0})
            deadline = time.time() + 5
            while t._q.qsize() and time.time() < deadline:
                time.sleep(0.005)  # sender picked n=0 and is parked in post()
            assert t._q.qsize() == 0
            for n in (1, 2, 3):  # two fit the queue, the third drops
                t.send({"n": n})
            assert t.stats()["dropped"] == 1
        finally:
            gate.set()
            t.close()
        assert t.stats()["sent"] == 3
        assert t.stats()["failed"] == 0

    def test_retry_then_success(self):
        self._stub = self._StubSession(fail_times=1)
        t = self._target(retries=2, retry_wait_s=0.01)
        t.send({"n": 1})
        t.close()
        st = t.stats()
        assert st["sent"] == 1 and st["failed"] == 0 and st["dropped"] == 0
        assert self._stub.posts == [{"n": 1}]

    def test_exhausted_retries_count_as_failed(self):
        self._stub = self._StubSession(fail_times=100)
        t = self._target(retries=1, retry_wait_s=0.01)
        t.send({"n": 1})
        t.close()
        st = t.stats()
        assert st["failed"] == 1 and st["sent"] == 0

    def test_close_flushes_the_queue(self):
        self._stub = self._StubSession()
        t = self._target(queue_size=100)
        for n in range(20):
            t.send({"n": n})
        t.close()
        st = t.stats()
        assert st["sent"] == 20 and st["queued"] == 0 and st["dropped"] == 0

    def test_send_never_blocks_with_dead_sink(self):
        # Even with the sender wedged, send() returns immediately.
        gate = threading.Event()
        self._stub = self._StubSession(gate=gate)
        t = self._target(queue_size=1)
        try:
            t0 = time.perf_counter()
            for n in range(50):
                t.send({"n": n})
            assert time.perf_counter() - t0 < 0.5
            assert t.stats()["dropped"] >= 48
        finally:
            gate.set()
            t.close()


class TestPubSubDropDisclosure:
    """Satellite: a slow subscriber loses messages observably, and never
    stalls publishers or starves fast subscribers."""

    def test_slow_subscriber_drops_are_counted_per_hub(self):
        hub = PubSub("testhub")
        slow = hub.subscribe(maxsize=1)
        fast = hub.subscribe(maxsize=10)
        for i in range(3):
            hub.publish({"i": i})
        assert hub.dropped == 2  # slow kept 1 of 3; fast kept all
        assert slow.qsize() == 1
        assert [fast.get_nowait()["i"] for _ in range(3)] == [0, 1, 2]

    def test_hub_names_label_the_metric(self):
        from minio_tpu.control.events import EventNotifier
        from minio_tpu.control.logging import GLOBAL_LOGGER

        assert GLOBAL_TRACE.hub.name == "trace"
        assert GLOBAL_LOGGER.audit_hub.name == "audit"
        assert EventNotifier().listen_hub.name == "listen"


class TestSpecFlightGate:
    def test_parse_flight_block(self):
        from minio_tpu.loadgen.spec import parse_scenario

        sc = parse_scenario({
            "name": "t", "bucket": "b",
            "phases": [{"name": "p0", "mix": {"GET": 1.0}, "ops": 1}],
            "flight": {"phase": "p0", "max_wait_s": 5},
        })
        assert sc.flight == {"phase": "p0", "max_wait_s": 5.0}

    def test_unknown_phase_rejected(self):
        from minio_tpu.loadgen.spec import SpecError, parse_scenario

        with pytest.raises(SpecError, match="unknown phase"):
            parse_scenario({
                "name": "t", "bucket": "b",
                "phases": [{"name": "p0", "mix": {"GET": 1.0}, "ops": 1}],
                "flight": {"phase": "nope"},
            })

    def test_canonical_scenario_declares_the_gate(self):
        from minio_tpu.loadgen import load_scenario

        sc = load_scenario(str(_REPO / "scenarios" / "flight_recorder.yaml"))
        assert sc.flight == {"phase": "faulted", "max_wait_s": 10.0}
        assert sc.env.get("MTPU_FLIGHT") == "1"
        faulted = next(p for p in sc.phases if p.name == "faulted")
        assert faulted.chaos, "the gated phase must arm a fault window"


class TestClusterCorrelatedCapture:
    """An incident on one node freezes the SAME wall-clock window on every
    node via the `flightcapture` peer verb (real internode REST)."""

    def test_two_node_capture_same_window(self, tmp_path, monkeypatch):
        from minio_tpu.loadgen.cluster import InProcessCluster

        store = tmp_path / "flightstore"
        monkeypatch.setenv("MTPU_FLIGHT_DIR", str(store))
        # MTPU_FLIGHT stays 0 (conftest): the trigger THREAD is off, but
        # the capture plane is always live -- fire the incident by hand.
        cluster = InProcessCluster(
            str(tmp_path / "data"), n_nodes=2, drives_per_node=4
        )
        try:
            GLOBAL_FLIGHT.configure()  # pick up the store dir
            assert GLOBAL_FLIGHT.node_id in cluster.urls  # build wired us
            inc = GLOBAL_FLIGHT.trigger("manual", detail={"via": "test"})
            metas = [
                m for m in GLOBAL_FLIGHT.list()
                if m["incident"] == inc["incident"]
            ]
            assert {m["node"] for m in metas} == set(cluster.urls)
            # Correlation is the point: identical window on every node.
            assert {json.dumps(m["window"]) for m in metas} == {
                json.dumps({"t0": inc["t0"], "t1": inc["t1"]})
            }
            for m in metas:
                assert m["origin"] == GLOBAL_FLIGHT.node_id
            assert flight_check.check_dir(str(store)) == []
            # The flight/pubsub/audit series ride the node exposition and
            # stay lint-clean.
            text = cluster.nodes[0].metrics.render_node()
            assert metrics_lint.validate_exposition(text) == []
            for series in (
                "minio_tpu_flight_triggers_total",
                "minio_tpu_flight_bundles_written_total",
                "minio_tpu_flight_ring_spans",
                "minio_tpu_pubsub_dropped_total",
                "minio_tpu_audit_dropped_total",
            ):
                assert series in text, series
            assert 'reason="manual"' in text
        finally:
            cluster.stop()
            GLOBAL_FLIGHT.stop()
            monkeypatch.undo()
            GLOBAL_FLIGHT.configure()
            GLOBAL_FLIGHT.reset()


class TestFlightGateEndToEnd:
    """Acceptance: a loadgen run with an armed fault window auto-captures a
    bundle on EVERY node covering the fault's wall-clock window, and the
    healthy phase produces none."""

    def test_fault_window_produces_cluster_bundle_set(self, tmp_path, monkeypatch):
        from minio_tpu.loadgen.cluster import InProcessCluster
        from minio_tpu.loadgen.runner import ScenarioRunner
        from minio_tpu.loadgen.spec import parse_scenario
        from minio_tpu.loadgen.target import InProcessAdmin, S3Target

        store = tmp_path / "flightstore"
        monkeypatch.setenv("MTPU_FLIGHT", "1")
        monkeypatch.setenv("MTPU_FLIGHT_DIR", str(store))
        monkeypatch.setenv("MTPU_FLIGHT_ERR_RATE", "0.3")
        monkeypatch.setenv("MTPU_FLIGHT_MIN_OPS", "5")
        monkeypatch.setenv("MTPU_FLIGHT_COOLDOWN_S", "30")
        monkeypatch.setenv("MTPU_FLIGHT_WINDOW_S", "10")
        sc = parse_scenario({
            "name": "flight_gate_ci",
            "seed": 7,
            "bucket": "flgate",
            "cluster": {"nodes": 2, "drives_per_node": 4},
            "keyspace": {"keys": 32, "prepopulate": 32, "prefix": "fl/",
                         "zipf_theta": 0.9},
            # Over SMALL_FILE_THRESHOLD (128 KiB): sub-threshold objects
            # inline their shards in xl.meta, so a shard-read fault would
            # never touch the GET path.
            "sizes": {"kind": "fixed", "bytes": 262144},
            "slo": {"GET": {"p99_ms": 30000, "error_budget": 1.0},
                    "PUT": {"p99_ms": 30000, "error_budget": 1.0}},
            "phases": [
                {"name": "healthy",
                 "mix": {"GET": 0.7, "PUT": 0.3},
                 "concurrency": 4, "duration_s": 2, "ops": 400},
                {"name": "faulted",
                 "mix": {"GET": 0.9, "PUT": 0.1},
                 "concurrency": 8, "duration_s": 4, "ops": 1600,
                 "chaos": [{"at_s": 0.5, "for_s": 2.5,
                            "fault": {"kind": "drive-error",
                                      "ops": ["read_file",
                                              "read_file_into"],
                                      "probability": 1.0, "seed": 7}}]},
            ],
            # The gate polls for the condition asserted below (a bundle on
            # every node, window inside the faulted phase); max_wait_s only
            # bounds a run in which no trigger ever fires. The trigger must
            # not hang on the drive breakers happening to trip (5 errors in
            # a row per drive, which a slow machine's interleaved metadata
            # reads prevent): every failed GET -- refused by an open breaker
            # or aborted mid-stream after a 200 -- is an error in the ops/s
            # ring, so the error-spike trigger fires on the first closed
            # second of the fault whatever the machine's speed.
            "flight": {"phase": "faulted", "max_wait_s": 30},
        })
        # Env must be live BEFORE the cluster builds: Node.build() arms the
        # trigger engine (ensure_started re-reads every MTPU_FLIGHT_* knob).
        cluster = InProcessCluster(
            str(tmp_path / "data"), n_nodes=2, drives_per_node=4
        )
        try:
            target = S3Target(cluster.urls, cluster.root_user,
                              cluster.root_password)
            report = ScenarioRunner(sc, target, InProcessAdmin()).run()
            fl = report["flight"]
            assert fl["ok"] is True, fl
            assert fl["false_triggers"] == []
            assert sorted(fl["nodes_captured"]) == sorted(cluster.urls)
            # Every captured bundle covers the same incident window and
            # validates against the bundle schema.
            incidents = {m["incident"] for m in fl["bundles"]}
            assert len(incidents) == 1, fl["bundles"]
            for meta in fl["bundles"]:
                bundle = GLOBAL_FLIGHT.get(meta["id"])
                assert flight_check.check_bundle(bundle, meta["id"]) == []
        finally:
            cluster.stop()
            GLOBAL_FLIGHT.stop()
            monkeypatch.undo()
            GLOBAL_FLIGHT.configure()
            GLOBAL_FLIGHT.reset()

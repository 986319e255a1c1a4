"""Observability surface: span trees over the trace hub + Prometheus metrics.

Covers the request-scoped tracing subsystem (control/tracing.py) end to end
-- a distributed PUT must yield ONE span tree keyed by the x-amz-request-id,
with api/object/erasure/storage layers and the remote hops carried over the
storage REST trace header -- and the /minio/v2/metrics/{node,cluster}
exposition, validated with the pure-Python checker in tools/metrics_lint.py
(the same one CI runs, so the hand-rendered format cannot drift).
"""

import importlib.util
import queue
import socket
import threading
from pathlib import Path
from types import SimpleNamespace

import pytest

from minio_tpu.api.server import ThreadedServer
from minio_tpu.control import tracing
from minio_tpu.control.pubsub import GLOBAL_TRACE
from minio_tpu.dist.node import Node
from tests.s3client import S3TestClient

_LINT_PATH = Path(__file__).resolve().parent.parent / "tools" / "metrics_lint.py"
_spec = importlib.util.spec_from_file_location("metrics_lint", _LINT_PATH)
metrics_lint = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(metrics_lint)


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    p = s.getsockname()[1]
    s.close()
    return p


ROOT = "obsadmin"
SECRET = "obs-secret-key-123"


@pytest.fixture(scope="module")
def cluster(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("obs-cluster")
    ports = [_free_port(), _free_port()]
    urls = [f"http://127.0.0.1:{p}" for p in ports]
    endpoints = []
    for ni in range(2):
        for di in range(4):
            endpoints.append(f"{urls[ni]}{tmp}/n{ni}d{di}")
    nodes = [
        Node(endpoints, url=urls[ni], root_user=ROOT, root_password=SECRET, set_drive_count=8)
        for ni in range(2)
    ]
    servers = []
    for ni, node in enumerate(nodes):
        ts = ThreadedServer(SimpleNamespace(app=node.make_app()), port=ports[ni])
        ts.start()
        servers.append(ts)
    threads = [threading.Thread(target=n.build) for n in nodes]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert all(n.pools is not None for n in nodes), "cluster failed to build"
    clients = [S3TestClient(urls[ni], ROOT, SECRET) for ni in range(2)]
    clients[0].make_bucket("obs")
    yield {"nodes": nodes, "clients": clients, "urls": urls}
    for ts in servers:
        ts.stop()


def _drain(q: "queue.Queue") -> list[dict]:
    out = []
    while True:
        try:
            out.append(q.get_nowait())
        except queue.Empty:
            return out


class TestSpanTree:
    def test_distributed_put_single_rooted_span_tree(self, cluster):
        """One PUT through the 2-node erasure set: every span -- api root,
        object op, erasure encode, per-drive storage calls on BOTH nodes --
        shares the request id, and the remote node's storage spans chain
        under the rpc hop spans (trace header over storage REST)."""
        client = cluster["clients"][0]
        sub = GLOBAL_TRACE.subscribe()
        try:
            r = client.put_object("obs", "traced.bin", b"t" * 4096)
            assert r.status_code == 200
            request_id = r.headers["x-amz-request-id"]
            records = _drain(sub)
        finally:
            GLOBAL_TRACE.unsubscribe(sub)

        tree = tracing.build_tree(records, request_id)
        roots = tree.get("", [])
        assert len(roots) == 1, f"expected one root, got {roots}"
        assert roots[0]["layer"] == "api"
        assert roots[0]["name"] == "PutObject"

        spans = list(tracing.walk_tree(tree))
        layers = {s["layer"] for s in spans}
        assert {"api", "object", "erasure", "storage"} <= layers, layers

        # Every span in the tree is reachable from the single root.
        all_for_trace = [
            r for r in records if r.get("type") == "span" and r.get("trace") == request_id
        ]
        assert len(spans) == len(all_for_trace), "disconnected spans in trace"

        # Per-drive storage spans: a write quorum of the 8-drive set.
        storage = [s for s in spans if s["layer"] == "storage"]
        drives = {s.get("drive", "") for s in storage}
        assert len(drives) >= 4, f"expected multi-drive fan-out, got {drives}"

        # Remote hops: node 1's drives (paths .../n1d*) reached over storage
        # REST, their spans parented under this node's rpc spans.
        remote_storage = [s for s in storage if "/n1d" in s.get("drive", "")]
        assert remote_storage, "no storage spans from the remote node"
        rpc_ids = {s["span"] for s in spans if s["layer"] == "rpc"}
        assert rpc_ids, "no rpc hop spans"
        assert all(s["parent"] in rpc_ids for s in remote_storage)

    def test_no_subscriber_means_noop_spans(self):
        assert tracing.span("x", "object") is tracing.NOOP
        with tracing.span("x", "object") as sp:
            assert sp.header() == ""

    def test_span_nesting_and_header_adoption(self):
        sub = GLOBAL_TRACE.subscribe()
        try:
            with tracing.root_span("Req", "api", "TRACE1") as root:
                with tracing.span("child", "object") as child:
                    assert child.trace_id == "TRACE1"
                    assert child.parent_id == root.span_id
                    wire = child.header()
            with tracing.bind_header(wire):
                with tracing.span("far-side", "storage") as far:
                    assert far.trace_id == "TRACE1"
        finally:
            GLOBAL_TRACE.unsubscribe(sub)
        recs = _drain(sub)
        tree = tracing.build_tree(recs, "TRACE1")
        assert len(tree.get("", [])) == 1
        assert len(list(tracing.walk_tree(tree))) == 3


class TestMetricsExposition:
    def test_node_metrics_valid_and_complete(self, cluster):
        client = cluster["clients"][0]
        # Generate traffic so drive/api series exist before the scrape.
        assert client.put_object("obs", "m.bin", b"m" * 1024).status_code == 200
        assert client.get_object("obs", "m.bin").status_code == 200
        r = client.request("GET", "/minio/v2/metrics/node")
        assert r.status_code == 200
        text = r.text
        assert metrics_lint.validate_exposition(text) == []
        assert metrics_lint.lint_exposition(text) == []
        # Series absent from the seed: drive, codec/device, heal/scanner.
        assert "minio_tpu_drive_latency_ms" in text
        assert "minio_tpu_drive_calls_total" in text
        assert "minio_tpu_device_probe_done" in text
        assert "minio_tpu_heal_mrf_pending" in text
        assert "minio_tpu_scanner_cycles_completed_total" in text
        # Histogram survived the refactor.
        assert "minio_tpu_s3_request_duration_seconds_bucket" in text

    def test_cluster_metrics_aggregate_two_nodes(self, cluster):
        client = cluster["clients"][0]
        r = client.request("GET", "/minio/v2/metrics/cluster")
        assert r.status_code == 200
        text = r.text
        assert metrics_lint.validate_exposition(text) == []
        assert metrics_lint.lint_exposition(text) == []
        servers = {
            lbls["server"]
            for _ln, _name, lbls, _v in metrics_lint.parse_samples(text)
            if "server" in lbls
        }
        assert len(servers) >= 2, f"cluster view has {servers}"
        for url in cluster["urls"]:
            assert url in servers

    def test_validator_catches_breakage(self):
        bad = (
            "# HELP m_total count\n"
            "# TYPE m_total counter\n"
            'm_total{a="1"} 5\n'
            'm_total{a="1"} 6\n'  # duplicate sample
        )
        assert any("duplicate sample" in p for p in metrics_lint.validate_exposition(bad))
        nohelp = "# TYPE x_total counter\nx_total 1\n"
        assert any("TYPE without HELP" in p for p in metrics_lint.validate_exposition(nohelp))
        nonmono = (
            "# HELP h request hist\n"
            "# TYPE h histogram\n"
            'h_bucket{le="0.1"} 5\n'
            'h_bucket{le="1"} 3\n'
            'h_bucket{le="+Inf"} 6\n'
            "h_sum 1.0\n"
            "h_count 6\n"
        )
        assert any("not monotone" in p for p in metrics_lint.validate_exposition(nonmono))
        badcount = (
            "# HELP h request hist\n"
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 6\n'
            "h_sum 1.0\n"
            "h_count 7\n"
        )
        assert any("_count" in p for p in metrics_lint.validate_exposition(badcount))


class TestPerfEndpoint:
    """The always-on attribution surface: with NO trace subscriber, a PUT
    must leave non-zero stage histograms behind, served by /mtpu/admin/v1
    /perf with p50/p95/p99 per stage (the ISSUE's acceptance criterion)."""

    # > SMALL_FILE_THRESHOLD (128 KiB) so the PUT takes the streaming path
    # and exercises encode -> shard-fanout -> commit.
    BODY = b"p" * (256 << 10)

    def test_put_populates_stage_histograms_without_subscriber(self, cluster):
        client = cluster["clients"][0]
        assert not GLOBAL_TRACE.enabled()
        assert client.put_object("obs", "perf.bin", self.BODY).status_code == 200
        assert client.get_object("obs", "perf.bin").status_code == 200

        r = client.request("GET", "/mtpu/admin/v1/perf")
        assert r.status_code == 200, r.text
        doc = r.json()
        stages = doc["node"]["stages"]
        assert stages["api"]["auth"]["count"] > 0
        for stage in ("encode", "shard-fanout", "commit"):
            row = stages["object"][stage]
            assert row["count"] > 0, stage
            for k in ("p50_ms", "p95_ms", "p99_ms", "mean_ms", "total_ms"):
                assert row[k] >= 0
            assert row["p50_ms"] <= row["p95_ms"] <= row["p99_ms"]
        # GET side: the shard gather and the response stream are attributed.
        assert stages["object"]["shard-read"]["count"] > 0
        assert stages["api"]["response-write"]["count"] > 0
        # Storage calls + internode RPC feed the ledger outside spans too.
        assert "storage" in stages
        assert any(s.startswith("/") for s in stages.get("rpc-peer", {})), stages.keys()
        # Satellite: drive EWMAs + breaker state ride the same payload.
        assert doc["drives"], "no drive latency rows"
        some = next(iter(doc["drives"].values()))
        assert "api" in some and "breaker" in some
        assert "slow" in doc

    def test_cluster_view_merges_peers(self, cluster):
        client = cluster["clients"][0]
        assert client.put_object("obs", "perf2.bin", self.BODY).status_code == 200
        r = client.request("GET", "/mtpu/admin/v1/perf", query=[("cluster", "1")])
        assert r.status_code == 200, r.text
        doc = r.json()
        assert doc["peers"], "no peers consulted"
        assert all(p["ok"] for p in doc["peers"].values()), doc["peers"]
        merged = doc["cluster"]["stages"]
        node = doc["node"]["stages"]
        # The merged view contains at least everything this node recorded.
        assert merged["object"]["commit"]["count"] >= node["object"]["commit"]["count"]

    def test_perf_slow_surface_and_reset(self, cluster):
        client = cluster["clients"][0]
        r = client.request("GET", "/mtpu/admin/v1/perf/slow")
        assert r.status_code == 200, r.text
        doc = r.json()
        for k in ("budget_ms", "max_traces", "max_bytes", "max_spans_per_trace",
                  "evicted_spans", "evicted_traces"):
            assert k in doc["stats"], k
        assert isinstance(doc["traces"], list)

        # ?reset=1 opens a clean measurement window.
        r = client.request("GET", "/mtpu/admin/v1/perf", query=[("reset", "1")])
        assert r.status_code == 200 and r.json().get("reset") is True
        r = client.request("GET", "/mtpu/admin/v1/perf")
        stages = r.json()["node"]["stages"]
        # Only the reset GET itself may have recorded since: no object ops.
        assert "object" not in stages or all(
            s not in stages["object"] for s in ("encode", "shard-fanout", "commit")
        )

    def test_stage_histograms_reach_prometheus(self, cluster):
        client = cluster["clients"][0]
        assert client.put_object("obs", "perf3.bin", self.BODY).status_code == 200
        r = client.request("GET", "/minio/v2/metrics/node")
        assert r.status_code == 200
        text = r.text
        assert "minio_tpu_stage_duration_seconds_bucket" in text
        # Codec observatory: the native gauge always renders; the batching
        # series appear only when the device codec is installed (the CPU
        # test cluster serves the host codec -- see test_perf.py for the
        # device-codec exposition).
        assert "minio_tpu_native_codec_available" in text
        # The new histogram family passes the extended exposition checks
        # (monotone le, +Inf == _count, consistent boundaries per family).
        assert metrics_lint.validate_exposition(text) == []
        assert metrics_lint.lint_exposition(text) == []
        stage_samples = [
            (name, lbls, v)
            for _ln, name, lbls, v in metrics_lint.parse_samples(text)
            if name.startswith("minio_tpu_stage_duration_seconds")
        ]
        assert any(
            name.endswith("_count") and lbls.get("stage") == "commit" and v > 0
            for name, lbls, v in stage_samples
        ), "commit stage not exported"


class TestIAMCascade:
    def test_remove_user_cascades_to_children(self):
        from minio_tpu.control.iam import IAMSys
        from minio_tpu.utils import errors

        iam = IAMSys("root", "rootsecret12")
        iam.add_user("alice", "alicesecret1")
        sa = iam.new_service_account("alice")
        assert sa.access_key in iam.users
        iam.remove_user("alice")
        assert "alice" not in iam.users
        assert sa.access_key not in iam.users, "service account survived cascade"
        with pytest.raises(errors.StorageError):
            iam.remove_user("alice")


# -- one timeline: stages outside a request, the annotator, request waits -----


class _FakeAnnotation:
    def __init__(self, log, label, kw):
        self.log, self.label, self.kw = log, label, kw

    def __enter__(self):
        self.log.append(("enter", self.label, self.kw))
        return self

    def __exit__(self, *exc):
        self.log.append(("exit", self.label, self.kw))
        return False


@pytest.fixture
def annotations(monkeypatch):
    """A fake annotator installed for the test, the log of what it saw on the
    test's own thread: an earlier test's codec worker or background waker may
    still be staging on its own (codec/worker-idle, background/*), and the
    annotator is the process's."""
    log: list[tuple] = []
    me = threading.get_ident()
    monkeypatch.setattr(
        tracing, "_annotator",
        lambda label, **kw: _FakeAnnotation(log if threading.get_ident() == me else [], label, kw))
    return log


def _ledger_row(layer: str, stage: str) -> dict:
    from minio_tpu.control.perf import GLOBAL_PERF

    row = GLOBAL_PERF.ledger.snapshot()["stages"].get(layer, {}).get(stage)
    return {"count": sum(row["counts"]), "sum": row["sum"], "cpu": row["cpu"]} if row else {
        "count": 0, "sum": 0.0, "cpu": 0.0}


class TestStageAndAnnotator:
    def test_stage_records_wall_and_cpu_without_a_request(self):
        """stage() is what a span would be on a worker thread: outside any
        request and with nobody on the hub it still feeds the ledger."""
        assert tracing.current() is None and not GLOBAL_TRACE.enabled()
        before = _ledger_row("codec", "pack")
        with tracing.stage("pack", "codec") as st:
            sum(range(20000))
        after = _ledger_row("codec", "pack")
        assert after["count"] == before["count"] + 1
        assert st.wall > 0 and st.cpu > 0
        assert after["sum"] - before["sum"] == pytest.approx(st.wall)
        assert after["cpu"] - before["cpu"] == pytest.approx(st.cpu)

    def test_annotator_sees_every_context_managed_span_and_stage_in_order(self, annotations):
        with tracing.root_span("PutObject", "api", "TRACE9"):
            with tracing.span("encode", "object"):
                with tracing.stage("pack", "codec"):
                    pass
            with tracing.span("commit", "object"):
                pass
        assert [(e, label) for e, label, _ in annotations] == [
            ("enter", "api/PutObject"),
            ("enter", "object/encode"),
            ("enter", "codec/pack"),
            ("exit", "codec/pack"),
            ("exit", "object/encode"),
            ("enter", "object/commit"),
            ("exit", "object/commit"),
            ("exit", "api/PutObject"),
        ]
        # Spans carry the request's trace id; a stage has no ids.
        for _, label, kw in annotations:
            assert kw == ({} if label == "codec/pack" else {"trace": "TRACE9"})

    def test_hand_finished_span_is_not_annotated(self, annotations):
        """response-write is opened and finished by hand, possibly on two
        threads: it feeds the ledger and stays off the host timeline."""
        before = _ledger_row("api", "response-write")
        with tracing.root_span("GetObject", "api", "TRACE10"):
            wr = tracing.span("response-write", "api")
            wr.finish()
        assert _ledger_row("api", "response-write")["count"] == before["count"] + 1
        assert [label for _, label, _ in annotations] == ["api/GetObject"] * 2

    def test_nothing_is_annotated_when_unset(self, annotations):
        tracing.set_annotator(None)
        with tracing.root_span("PutObject", "api", "TRACE11"):
            with tracing.stage("pack", "codec"):
                pass
        assert annotations == []


class TestRequestWaitStages:
    """Where a streamed request waits: every named stage of a PUT and the
    two halves of a GET's response-write land in the ledger."""

    BODY = bytes(range(256)) * (3 << 10)  # 768 KiB: the streaming path

    def test_streamed_put_names_its_waits(self, cluster):
        client = cluster["clients"][0]
        rows = [("object", "window-wait"), ("api", "body-hop"),
                ("api", "payload-hash"), ("api", "body-fill")]
        before = {r: _ledger_row(*r) for r in rows}
        assert client.put_object("obs", "waits.bin", self.BODY).status_code == 200
        after = {r: _ledger_row(*r) for r in rows}
        # One record per request for the accumulated rows, however many
        # body chunks there were; a window-wait per window asked for.
        assert after[("api", "body-hop")]["count"] == before[("api", "body-hop")]["count"] + 1
        assert after[("api", "payload-hash")]["count"] == (
            before[("api", "payload-hash")]["count"] + 1)
        assert after[("api", "payload-hash")]["cpu"] > before[("api", "payload-hash")]["cpu"]
        assert after[("object", "window-wait")]["count"] > before[("object", "window-wait")]["count"]
        assert after[("api", "body-fill")]["count"] > before[("api", "body-fill")]["count"]
        for r in rows:
            assert after[r]["sum"] > before[r]["sum"], r

    def test_stream_pull_and_socket_write_sum_to_response_write(self, cluster):
        client = cluster["clients"][0]
        assert client.put_object("obs", "halves.bin", self.BODY).status_code == 200
        rows = [("api", "response-write"), ("api", "stream-pull"), ("api", "socket-write"),
                ("api", "pull-start")]
        before = {r: _ledger_row(*r) for r in rows}
        metrics = cluster["nodes"][0].metrics
        hops0, chunks0 = metrics.get_stream_hops, metrics.get_stream_chunks
        got = client.get_object("obs", "halves.bin")
        assert got.status_code == 200 and got.content == self.BODY
        # The client has its last byte before the server closes the span.
        import time

        deadline = time.monotonic() + 5
        while (_ledger_row("api", "socket-write")["count"] == before[rows[2]]["count"]
               and time.monotonic() < deadline):
            time.sleep(0.01)
        d = {r[1]: {k: _ledger_row(*r)[k] - before[r][k] for k in ("count", "sum")}
             for r in rows}
        assert d["stream-pull"]["count"] == d["socket-write"]["count"] == 1
        assert d["response-write"]["count"] == 1
        # pull-start is the part of the pulls before a worker thread had them.
        assert d["pull-start"]["count"] == 1
        assert 0 < d["pull-start"]["sum"] <= d["stream-pull"]["sum"]
        assert d["stream-pull"]["sum"] + d["socket-write"]["sum"] == pytest.approx(
            d["response-write"]["sum"], rel=0.1, abs=0.005)
        # stream-pull is one wait per read window, not per chunk: the one
        # window's hop and the hop that finds the end, for the one block's
        # K row views.
        assert metrics.get_stream_hops - hops0 == 2
        assert metrics.get_stream_chunks - chunks0 == 4
        text = metrics.render_node()
        assert f"minio_tpu_s3_get_stream_hops_total {metrics.get_stream_hops}" in text
        assert f"minio_tpu_s3_get_stream_chunks_total {metrics.get_stream_chunks}" in text


class TestProcessWatch:
    def test_gc_pause_is_recorded_outside_the_callback(self):
        """The gc hook only queues the pause (it may run inside the
        ledger's own lock); flush() -- the GIL probe's tick -- records it."""
        import gc

        from minio_tpu.control.profiler import GcWatch

        watch = GcWatch()
        watch.install()
        try:
            before = _ledger_row("runtime", "gc-pause")
            gc.collect()
            assert _ledger_row("runtime", "gc-pause")["count"] == before["count"]
            watch.flush()
            after = _ledger_row("runtime", "gc-pause")
        finally:
            watch.remove()
        assert after["count"] >= before["count"] + 1
        assert watch.collections[2] >= 1
        assert watch._on_gc not in gc.callbacks

    def test_gil_probe_ticks_feed_the_ledger(self):
        import time

        from minio_tpu.control.profiler import GilLoadProbe

        before = _ledger_row("runtime", "gil-wake-late")
        probe = GilLoadProbe(interval_s=0.002)
        probe.start()
        try:
            deadline = time.monotonic() + 5
            while probe.ticks < GilLoadProbe._CALIB_TICKS + 3 and time.monotonic() < deadline:
                time.sleep(0.01)
        finally:
            probe.stop()
        # Calibration ticks set the floor and record nothing; later ones do.
        assert _ledger_row("runtime", "gil-wake-late")["count"] > before["count"]

    def test_serving_loop_heartbeat_records_lag(self, cluster):
        import time

        before = _ledger_row("runtime", "loop-lag")
        deadline = time.monotonic() + 5
        while (_ledger_row("runtime", "loop-lag")["count"] < before["count"] + 2
               and time.monotonic() < deadline):
            time.sleep(0.05)
        assert _ledger_row("runtime", "loop-lag")["count"] >= before["count"] + 2

    def test_background_wakers_record_their_wake_ups(self, cluster):
        node = cluster["nodes"][0]
        before = _ledger_row("background", "scanner-cycle")
        node.scanner._stop.clear()
        node.scanner.cycle_seconds = 3600
        node.scanner.start()
        try:
            import time

            deadline = time.monotonic() + 20
            while (_ledger_row("background", "scanner-cycle")["count"] == before["count"]
                   and time.monotonic() < deadline):
                time.sleep(0.05)
        finally:
            node.scanner.stop()
        assert _ledger_row("background", "scanner-cycle")["count"] == before["count"] + 1


class TestDeviceProfile:
    def test_profile_with_device_returns_xplane_and_devtrace(self, cluster, monkeypatch):
        """The operator's path: profile/start?device=1 ... profile/stop gives
        a zip with the .xplane.pb and devtrace.json beside profile.txt. On
        the CPU backend there is no device plane; the host annotations of
        the PUT made meanwhile must be in the trace."""
        import io
        import json
        import zipfile

        import jax

        from minio_tpu import runtime
        from minio_tpu.control import devtrace

        client = cluster["clients"][0]
        r = client.request("POST", "/mtpu/admin/v1/profile/start", query=[("device", "1")])
        assert r.status_code == 400, "no device codec serves: device=1 must be refused"

        monkeypatch.setitem(runtime._install, "state", "serving")
        monkeypatch.setattr(tracing, "_annotator", jax.profiler.TraceAnnotation)
        r = client.request("POST", "/mtpu/admin/v1/profile/start", query=[("device", "1")])
        assert r.status_code == 200, r.text
        assert client.put_object("obs", "devtrace.bin", b"d" * (512 << 10)).status_code == 200
        r = client.request("POST", "/mtpu/admin/v1/profile/stop")
        assert r.status_code == 200, r.text
        z = zipfile.ZipFile(io.BytesIO(r.content))
        names = z.namelist()
        assert "local/profile.txt" in names and "local/devtrace.json" in names
        xplanes = [n for n in names if n.endswith(".xplane.pb")]
        assert len(xplanes) == 1
        reduced = json.loads(z.read("local/devtrace.json"))
        assert reduced["host_annotations"] > 0 and reduced["devices"] == {}
        import tempfile

        with tempfile.TemporaryDirectory() as tmp:
            z.extract(xplanes[0], tmp)
            host = {name for name, _, _ in devtrace.load(str(Path(tmp) / xplanes[0]))["host"]}
        assert {"api/PutObject", "object/object.PutObject", "object/encode",
                "object/window-wait", "api/body-fill"} <= host, sorted(host)


class TestAbortedStreamIsAnError:
    def test_get_that_dies_mid_stream_is_an_error_in_the_ops_ring(self, cluster):
        """A GET whose shard reads fail under the lazy body stream answered
        200 and then lost its connection: the client saw an error, so the
        ops/s ring (what the flight recorder's error-spike trigger reads)
        must count one -- not an ok because the status line said 200."""
        import time

        import requests

        from minio_tpu.chaos.faults import REGISTRY, FaultSpec
        from minio_tpu.control.perf import GLOBAL_PERF

        def get_errors() -> int:
            return sum(e["classes"].get("get", {}).get("errors", 0)
                       for e in GLOBAL_PERF.timeseries.snapshot()["series"])

        client = cluster["clients"][0]
        body = bytes(range(256)) * (3 << 10)
        assert client.put_object("obs", "dies.bin", body).status_code == 200
        before = get_errors()
        fid = REGISTRY.arm(FaultSpec.from_dict({
            "kind": "drive-error", "ops": ["read_file", "read_file_into"],
            "probability": 1.0, "seed": 1}))
        try:
            try:
                r = client.get_object("obs", "dies.bin")
                failed = r.status_code >= 400 or r.content != body
            except requests.exceptions.RequestException:
                failed = True  # the connection was closed under the body
        finally:
            REGISTRY.disarm(fid)
        assert failed, "every shard read failed, the GET cannot have succeeded"
        deadline = time.monotonic() + 5
        while get_errors() == before and time.monotonic() < deadline:
            time.sleep(0.02)
        assert get_errors() == before + 1

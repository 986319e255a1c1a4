"""Control-plane tests: config, events, scanner, usage, heal manager,
metrics, lifecycle, pubsub."""

import os
import time

import pytest

from minio_tpu.control import config as cfg_mod
from minio_tpu.control import events as ev_mod
from minio_tpu.control import metrics as met_mod
from minio_tpu.control.healmgr import HealManager, MRFQueue
from minio_tpu.control.lifecycle import Lifecycle
from minio_tpu.control.pubsub import PubSub, TraceSys
from minio_tpu.control.scanner import DataScanner
from minio_tpu.utils import errors
from tests.harness import ErasureHarness

NS = "http://s3.amazonaws.com/doc/2006-03-01/"


class TestConfig:
    def test_defaults_and_set(self):
        c = cfg_mod.ConfigSys()
        assert c.get(cfg_mod.SUBSYS_SCANNER, "delay") == "10"
        assert c.set(cfg_mod.SUBSYS_SCANNER, "delay", "20") is True  # dynamic
        assert c.get_int(cfg_mod.SUBSYS_SCANNER, "delay") == 20
        c.unset(cfg_mod.SUBSYS_SCANNER, "delay")
        assert c.get_int(cfg_mod.SUBSYS_SCANNER, "delay") == 10
        with pytest.raises(errors.InvalidArgument):
            c.get("nope", "nope")
        with pytest.raises(errors.InvalidArgument):
            c.set(cfg_mod.SUBSYS_SCANNER, "bogus", "1")

    def test_env_override_wins(self):
        c = cfg_mod.ConfigSys()
        os.environ["MINIO_TPU_SCANNER_DELAY"] = "99"
        try:
            assert c.get_int(cfg_mod.SUBSYS_SCANNER, "delay") == 99
        finally:
            del os.environ["MINIO_TPU_SCANNER_DELAY"]

    def test_dump(self):
        c = cfg_mod.ConfigSys()
        d = c.dump()
        assert d[cfg_mod.SUBSYS_ENCODER]["max_batch"] == "32"


class TestEvents:
    def test_rule_matching(self):
        r = ev_mod.Rule(events=["s3:ObjectCreated:*"], prefix="logs/", suffix=".txt")
        assert r.matches("s3:ObjectCreated:Put", "logs/a.txt")
        assert not r.matches("s3:ObjectRemoved:Delete", "logs/a.txt")
        assert not r.matches("s3:ObjectCreated:Put", "other/a.txt")
        assert not r.matches("s3:ObjectCreated:Put", "logs/a.json")

    def test_parse_notification_xml(self):
        xml = f"""<NotificationConfiguration xmlns="{NS}">
          <QueueConfiguration>
            <Queue>arn:minio:sqs::primary:webhook</Queue>
            <Event>s3:ObjectCreated:*</Event>
            <Filter><S3Key>
              <FilterRule><Name>prefix</Name><Value>img/</Value></FilterRule>
            </S3Key></Filter>
          </QueueConfiguration>
        </NotificationConfiguration>"""
        rules = ev_mod.parse_notification_xml(xml)
        assert len(rules) == 1
        assert rules[0].target_ids == ["webhook"]
        assert rules[0].prefix == "img/"

    def test_emit_to_target_with_queue(self, tmp_path):
        sent = []

        class FakeTarget:
            id = "webhook"

            def send(self, record):
                sent.append(record)

        n = ev_mod.EventNotifier()
        n.register_target(FakeTarget())
        n.set_bucket_rules_from_xml(
            "bkt",
            f'<NotificationConfiguration xmlns="{NS}"><QueueConfiguration>'
            "<Queue>arn:minio:sqs::1:webhook</Queue><Event>s3:ObjectCreated:*</Event>"
            "</QueueConfiguration></NotificationConfiguration>",
        )
        n.emit(ev_mod.Event(name="s3:ObjectCreated:Put", bucket="bkt", object_name="x", size=3))
        n.emit(ev_mod.Event(name="s3:ObjectRemoved:Delete", bucket="bkt", object_name="x"))
        assert len(sent) == 1
        assert sent[0]["EventName"] == "s3:ObjectCreated:Put"
        assert sent[0]["Records"][0]["s3"]["object"]["size"] == 3

    def test_queue_store_retries_and_spools(self, tmp_path):
        fails = {"n": 2}
        delivered = []

        def send(record):
            if fails["n"] > 0:
                fails["n"] -= 1
                raise RuntimeError("broker down")
            delivered.append(record)

        q = ev_mod.TargetQueue(send, queue_dir=str(tmp_path / "spool"))
        q.put({"EventName": "e1"})
        deadline = time.time() + 5
        while not delivered and time.time() < deadline:
            time.sleep(0.05)
        assert delivered and delivered[0]["EventName"] == "e1"
        assert q.pending() == 0
        q.close()

    def test_listen_hub(self):
        n = ev_mod.EventNotifier()
        sub = n.listen_hub.subscribe()
        n.emit(ev_mod.Event(name="s3:ObjectCreated:Put", bucket="b", object_name="k"))
        rec = sub.get(timeout=1)
        assert rec["Key"] == "b/k"


class TestLifecycle:
    def test_parse_and_eval(self):
        xml = f"""<LifecycleConfiguration xmlns="{NS}">
          <Rule><ID>exp</ID><Status>Enabled</Status>
            <Filter><Prefix>tmp/</Prefix></Filter>
            <Expiration><Days>1</Days></Expiration></Rule>
          <Rule><ID>keep</ID><Status>Disabled</Status>
            <Filter><Prefix></Prefix></Filter>
            <Expiration><Days>1</Days></Expiration></Rule>
        </LifecycleConfiguration>"""
        lc = Lifecycle.from_xml(xml)
        assert len(lc.rules) == 2
        old = time.time() - 2 * 86400
        assert lc.eval("tmp/x", old) == "expire"
        assert lc.eval("tmp/x", time.time()) == ""
        assert lc.eval("other/x", old) == ""  # prefix mismatch

    def test_transition_rule(self):
        xml = f"""<LifecycleConfiguration xmlns="{NS}">
          <Rule><ID>t</ID><Status>Enabled</Status><Prefix></Prefix>
            <Transition><Days>1</Days><StorageClass>COLD</StorageClass></Transition>
          </Rule></LifecycleConfiguration>"""
        lc = Lifecycle.from_xml(xml)
        assert lc.eval("x", time.time() - 2 * 86400) == "transition:COLD"


class TestScannerAndHeal:
    @pytest.fixture
    def hz(self, tmp_path):
        h = ErasureHarness(tmp_path, n_disks=8)
        h.layer.make_bucket("scanb")
        return h

    def test_usage_accounting(self, hz):
        for i in range(5):
            hz.layer.put_object("scanb", f"dir/obj{i}", b"x" * 1000)

        class OnePool:
            pools = [None]

        # DataScanner expects a pools-shaped layer; wrap the single set.
        layer = _PoolsShim(hz)
        sc = DataScanner(layer, heal_sample=10**9)
        sc.scan_cycle()
        s = sc.usage.summary()
        assert s["objectsCount"] == 5
        assert s["objectsTotalSize"] == 5000
        assert s["bucketsUsage"]["scanb"]["objectsCount"] == 5

    def test_scanner_heals_damage(self, hz):
        data = b"d" * 200_000
        hz.layer.put_object("scanb", "obj", data)
        hz.delete_shard(0, "scanb", "obj") or hz.delete_object_dir(0, "scanb", "obj")
        layer = _PoolsShim(hz)
        sc = DataScanner(layer, heal_sample=1)  # check everything
        sc.scan_cycle()
        res = hz.layer.heal_object("scanb", "obj", dry_run=True)
        assert res.disks_healed == 0  # already repaired by the scan

    def test_mrf_queue(self, hz):
        hz.layer.put_object("scanb", "obj", b"mrf" * 50_000)
        hz.delete_object_dir(2, "scanb", "obj")
        layer = _PoolsShim(hz)
        mrf = MRFQueue(layer)
        mrf.add("scanb", "obj")
        deadline = time.time() + 5
        while mrf.healed == 0 and time.time() < deadline:
            time.sleep(0.05)
        mrf.stop()
        assert mrf.healed == 1
        assert hz.layer.heal_object("scanb", "obj", dry_run=True).disks_healed == 0

    def test_heal_sequence(self, hz):
        for i in range(3):
            hz.layer.put_object("scanb", f"o{i}", b"x" * 150_000)
        hz.delete_object_dir(1, "scanb", "o0")
        layer = _PoolsShim(hz)
        hm = HealManager(layer)
        seq = hm.start_sequence()
        deadline = time.time() + 10
        while hm.get_status(seq).running and time.time() < deadline:
            time.sleep(0.05)
        st = hm.get_status(seq)
        assert not st.running
        assert st.scanned == 3
        assert st.healed == 1


class _PoolsShim:
    """Adapts the single-set harness to the pools-shaped layer API the
    control plane consumes."""

    def __init__(self, hz):
        from minio_tpu.object.sets import ErasureSets

        self._sets = ErasureSets(list(hz.layer.disks), len(hz.layer.disks))
        # Reuse the SAME set object so offline state matches.
        self._sets.sets = [hz.layer]
        self.pools = [self._sets]
        self.hz = hz

    def list_buckets(self):
        return self.hz.layer.list_buckets()

    def heal_object(self, *a, **k):
        return self.hz.layer.heal_object(*a, **k)

    def heal_bucket(self, bucket):
        pass

    def delete_object(self, bucket, name, opts=None):
        return self.hz.layer.delete_object(bucket, name, opts)

    def list_multipart_uploads(self, bucket, prefix=""):
        return self.hz.layer.multipart.list_multipart_uploads(bucket, prefix)

    def abort_multipart_upload(self, bucket, object_name, upload_id):
        return self.hz.layer.multipart.abort_multipart_upload(bucket, object_name, upload_id)


class TestMetrics:
    def test_render(self):
        m = met_mod.MetricsSys()
        m.record_http("GET", 200)
        m.record_api("GetObject", 0.01, True, tx=100)
        m.record_api("PutObject", 0.5, False, rx=200)
        out = m.render()
        assert 'minio_tpu_http_requests_total{method="GET",status="200"} 1' in out
        assert 'minio_tpu_s3_requests_total{api="GetObject"} 1' in out
        assert 'minio_tpu_s3_requests_errors_total{api="PutObject"} 1' in out
        # The dead record_encode series are gone with their recorder.
        assert "minio_tpu_encode_blocks_total" not in out


class TestPubSub:
    def test_zero_overhead_when_unsubscribed(self):
        t = TraceSys()
        assert not t.enabled()
        t.publish("http", path="/x")  # no-op
        sub = t.subscribe()
        assert t.enabled()
        t.publish("http", path="/y")
        item = sub.get(timeout=1)
        assert item["path"] == "/y"
        t.unsubscribe(sub)
        assert not t.enabled()

    def test_slow_subscriber_drops(self):
        ps = PubSub()
        q = ps.subscribe(maxsize=2)
        for i in range(5):
            ps.publish(i)
        assert q.qsize() == 2  # overflow dropped, publisher never blocked


class TestAbortIncompleteMultipart:
    def test_stale_uploads_aborted(self, tmp_path):
        import time as _t

        from minio_tpu.control.bucket_meta import BucketMetadataSys
        from minio_tpu.control.lifecycle import Lifecycle
        from tests.harness import ErasureHarness

        hz = ErasureHarness(tmp_path, n_disks=8)
        hz.layer.make_bucket("mpab")
        uid = hz.layer.multipart.new_multipart_upload("mpab", "stale/obj")
        hz.layer.multipart.put_object_part("mpab", "stale/obj", uid, 1, b"x" * 1000)
        fresh_uid = hz.layer.multipart.new_multipart_upload("mpab", "fresh/obj")

        xml = f"""<LifecycleConfiguration xmlns="{NS}">
          <Rule><ID>a</ID><Status>Enabled</Status><Prefix>stale/</Prefix>
            <AbortIncompleteMultipartUpload><DaysAfterInitiation>1</DaysAfterInitiation>
            </AbortIncompleteMultipartUpload></Rule></LifecycleConfiguration>"""
        lc = Lifecycle.from_xml(xml)
        assert lc.eval_abort_mpu("stale/obj", _t.time() - 2 * 86400)
        assert not lc.eval_abort_mpu("stale/obj", _t.time() - 3600)
        assert not lc.eval_abort_mpu("other/obj", 0)

        # Wire through the scanner: backdate the upload, give the bucket the
        # lifecycle, run a cycle.
        layer = _PoolsShim(hz)
        meta = BucketMetadataSys(layer)
        meta.update("mpab", lifecycle_xml=xml)

        # Backdate the stale upload's initiation time on every drive.
        import json as _json
        import os as _os

        for d in hz.dirs:
            root = _os.path.join(d, ".minio_tpu.sys", "multipart", "mpab")
            for dirpath, _, files in _os.walk(root):
                for f in files:
                    if f == "upload.json":
                        p = _os.path.join(dirpath, f)
                        doc = _json.loads(open(p, "rb").read())
                        doc["created"] = _t.time() - 3 * 86400
                        open(p, "w").write(_json.dumps(doc))

        sc = DataScanner(layer, heal_sample=10**9, bucket_meta=meta)
        sc.scan_cycle()
        remaining = {u["upload_id"] for u in hz.layer.multipart.list_multipart_uploads("mpab")}
        assert uid not in remaining  # stale/ upload aborted
        assert fresh_uid in remaining  # fresh/ prefix not covered by the rule
        assert sc.uploads_aborted >= 1


def test_metrics_duration_histogram():
    from minio_tpu.control.metrics import MetricsSys

    m = MetricsSys()
    m.record_api("GetObject", 0.003, True)
    m.record_api("GetObject", 0.2, True)
    m.record_api("GetObject", 42.0, False)
    out = m.render()
    assert 'minio_tpu_s3_request_duration_seconds_bucket{api="GetObject",le="0.005"} 1' in out
    assert 'minio_tpu_s3_request_duration_seconds_bucket{api="GetObject",le="0.25"} 2' in out
    assert 'minio_tpu_s3_request_duration_seconds_bucket{api="GetObject",le="+Inf"} 3' in out
    assert 'minio_tpu_s3_request_duration_seconds_count{api="GetObject"} 3' in out


def test_notification_rules_rehydrate_on_boot(tmp_path):
    """A restart must reload persisted bucket notification configs into the
    notifier — the rules live in memory, the config in bucket metadata; a
    fresh process otherwise silently stops delivering events."""
    import os as os_mod

    from minio_tpu.dist.node import Node
    from minio_tpu.object.codec import HostCodec

    dirs = []
    for i in range(4):
        d = str(tmp_path / f"nb{i}")
        os_mod.makedirs(d)
        dirs.append(d)
    node = Node(dirs, root_user="nbroot", root_password="nbsecret123", codec=HostCodec())
    node.build()
    node.pools.make_bucket("evb")
    xml = (
        '<NotificationConfiguration xmlns="http://s3.amazonaws.com/doc/2006-03-01/">'
        "<QueueConfiguration><Id>q1</Id><Queue>arn:minio:sqs::primary:webhook</Queue>"
        "<Event>s3:ObjectCreated:*</Event></QueueConfiguration>"
        "</NotificationConfiguration>"
    )
    node.s3.bucket_meta.update("evb", notification_xml=xml)
    node.notifier.set_bucket_rules_from_xml("evb", xml)
    assert node.notifier.bucket_rules.get("evb")

    # Fresh process over the same drives: rules must come back on boot.
    node2 = Node(dirs, root_user="nbroot", root_password="nbsecret123", codec=HostCodec())
    node2.build()
    rules = node2.notifier.bucket_rules.get("evb")
    assert rules, "notification rules lost across restart"

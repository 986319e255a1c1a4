"""mtpulint rule/engine tests: every rule has a firing and a non-firing
fixture, plus suppression- and baseline-handling coverage.

Fixtures are tiny synthetic trees under tmp_path (the engine resolves
relpaths against whatever root it is given), so each test pins exactly one
behavior without depending on the real minio_tpu sources. The real tree is
gated separately by tests/test_static_analysis.py."""

from __future__ import annotations

import textwrap

from tools.mtpulint import (
    apply_baseline,
    format_baseline,
    lint_tree,
    load_baseline,
)
from tools.mtpulint.rules import (
    CondWaitLoopRule,
    DeadlineRebindRule,
    DoubleReleaseRule,
    HotPathCopyRule,
    InterfaceConformanceRule,
    LockBlockingIORule,
    LockOrderRule,
    MetricsRenderedRule,
    RawTransportRule,
    ReleaseOnAllPathsRule,
    ResourceLeakRule,
    SharedPublishRule,
    StageKeyRule,
    SwallowedExceptRule,
    TypedErrorsRule,
    UnjoinedThreadRule,
    UnlockedGlobalRule,
    UnsyncedCommitRule,
    ViewEscapeRule,
)


def run_rule(tmp_path, files: dict[str, str], rule) -> list:
    for rel, src in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(src).lstrip("\n"))
    return lint_tree(str(tmp_path), ["minio_tpu"], [rule])


# -- swallowed-except ---------------------------------------------------------


def test_swallowed_except_fires_on_silent_broad_handler(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": """
            def f():
                try:
                    g()
                except Exception:
                    pass
        """,
    }, SwallowedExceptRule())
    assert [f.rule for f in findings] == ["swallowed-except"]
    assert findings[0].line == 4


def test_swallowed_except_fires_on_bare_except_and_bare_return(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/x.py": """
            def f():
                try:
                    g()
                except:
                    return
        """,
    }, SwallowedExceptRule())
    assert len(findings) == 1 and "bare except" in findings[0].message


def test_swallowed_except_quiet_when_narrow_or_observable(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": """
            def f(log):
                try:
                    g()
                except ValueError:
                    pass
                try:
                    g()
                except Exception:
                    log.warning("g failed")
                try:
                    g()
                except Exception:
                    raise
        """,
    }, SwallowedExceptRule())
    assert findings == []


def test_swallowed_except_ignores_cold_paths(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            def f():
                try:
                    g()
                except Exception:
                    pass
        """,
    }, SwallowedExceptRule())
    assert findings == []


# -- raw-transport ------------------------------------------------------------


def test_raw_transport_fires_on_import_and_call(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/peer.py": """
            import requests

            def f(url):
                return requests.get(url)
        """,
    }, RawTransportRule())
    assert [f.line for f in findings] == [1, 4]
    assert all(f.rule == "raw-transport" for f in findings)


def test_raw_transport_allows_transport_py_itself(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/transport.py": """
            import requests
            import socket
        """,
    }, RawTransportRule())
    assert findings == []


# -- deadline-rebind ----------------------------------------------------------


def test_deadline_rebind_fires_when_transport_loses_markers(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/transport.py": """
            def call(url):
                return url
        """,
    }, DeadlineRebindRule())
    msgs = " ".join(f.message for f in findings)
    assert len(findings) == 3
    assert "deadline.remaining()" in msgs
    assert "DEADLINE_HEADER" in msgs
    assert "DeadlineExceeded" in msgs


def test_deadline_rebind_fires_on_server_without_bind(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/some_rest.py": """
            def handler(request):
                tok = request.headers.get(TOKEN_HEADER)
                return tok
        """,
    }, DeadlineRebindRule())
    assert len(findings) == 1
    assert "bind_header" in findings[0].message


def test_deadline_rebind_quiet_on_complete_plumbing(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/transport.py": """
            def call(headers, deadline):
                if deadline.remaining() <= 0:
                    raise DeadlineExceeded("spent")
                headers[DEADLINE_HEADER] = "1.5"
        """,
        "minio_tpu/dist/some_rest.py": """
            def handler(request):
                tok = request.headers.get(TOKEN_HEADER)
                deadline.bind_header(request.headers.get("X-Mtpu-Deadline"))
                return tok
        """,
    }, DeadlineRebindRule())
    assert findings == []


# -- lock-blocking-io ---------------------------------------------------------


def test_lock_blocking_io_fires_on_sleep_and_open_under_lock(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            import time

            def f(self, path):
                with self._lock:
                    time.sleep(1)
                    fh = open(path)
                return fh
        """,
    }, LockBlockingIORule())
    assert sorted(f.line for f in findings) == [5, 6]


def test_lock_blocking_io_quiet_outside_lock_or_in_nested_def(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/x.py": """
            import time

            def f(self, pool):
                time.sleep(1)
                with self._lock:
                    def deferred():
                        time.sleep(1)
                    pool.submit(deferred)
                with self.items:
                    time.sleep(1)
        """,
    }, LockBlockingIORule())
    assert findings == []


# -- resource-leak ------------------------------------------------------------


def test_resource_leak_fires_on_unclosed_open(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            def f(path):
                fh = open(path)
                return fh.name
        """,
    }, ResourceLeakRule())
    assert [f.rule for f in findings] == ["resource-leak"]


def test_resource_leak_quiet_on_with_finally_and_escape(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            def ok_with(path):
                with open(path) as f:
                    return f.read()

            def ok_finally(path):
                f = open(path)
                try:
                    return f.read()
                finally:
                    f.close()

            def ok_escape(path):
                return open(path)

            def ok_handoff(path, sink):
                sink.adopt(open(path))
        """,
    }, ResourceLeakRule())
    assert findings == []


# -- stage-key ----------------------------------------------------------------

_PERF_FIXTURE = """
    STAGES = frozenset({("api", "auth"), ("object", "encode")})
    DYNAMIC_STAGE_LAYERS = frozenset({"rpc"})
"""


def test_stage_key_fires_on_unregistered_literal(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/perf.py": _PERF_FIXTURE,
        "minio_tpu/object/x.py": """
            def f():
                with tracing.span("typo-stage", "api"):
                    pass
        """,
    }, StageKeyRule())
    assert len(findings) == 1
    assert "('api', 'typo-stage')" in findings[0].message


def test_stage_key_quiet_on_registered_and_dynamic(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/perf.py": _PERF_FIXTURE,
        "minio_tpu/object/x.py": """
            def f(GLOBAL_PERF, name):
                with tracing.span("auth", "api"):
                    pass
                GLOBAL_PERF.ledger.record("rpc", name, 0.1)
                GLOBAL_PERF.ledger.record("rpc", "peer-call", 0.1)
        """,
    }, StageKeyRule())
    assert findings == []


def test_stage_key_covers_worker_thread_stages(tmp_path):
    """tracing.stage(name, layer) -- the worker-thread helper -- is held to
    the registry like a span: one typo fires, a registered key is quiet."""
    findings = run_rule(tmp_path, {
        "minio_tpu/control/perf.py": _PERF_FIXTURE,
        "minio_tpu/object/x.py": """
            def f(name):
                with tracing.stage("encode", "object"):
                    pass
                with tracing.stage(name, "rpc"):
                    pass
                with tracing.stage("pakc", "object"):
                    pass
        """,
    }, StageKeyRule())
    assert len(findings) == 1
    assert "('object', 'pakc')" in findings[0].message


def test_stage_key_reports_missing_registry(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/perf.py": "X = 1\n",
    }, StageKeyRule())
    assert len(findings) == 1
    assert "registry literal not found" in findings[0].message


# -- metrics-rendered ---------------------------------------------------------

_DEGRADE_FIXTURE = """
    class DegradeStats:
        def hit(self):
            self.mystery_counter += 1
"""


def test_metrics_rendered_fires_on_unexported_counter(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/degrade.py": _DEGRADE_FIXTURE,
        "minio_tpu/control/metrics.py": "def render():\n    return ''\n",
    }, MetricsRenderedRule())
    assert len(findings) == 1
    assert "'mystery_counter'" in findings[0].message


def test_metrics_rendered_quiet_when_rendered(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/degrade.py": _DEGRADE_FIXTURE,
        "minio_tpu/control/metrics.py": """
            def render(snap):
                return snap["mystery_counter"]
        """,
    }, MetricsRenderedRule())
    assert findings == []


# -- typed-errors -------------------------------------------------------------


def test_typed_errors_fires_on_untyped_raise(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": """
            def f():
                raise Exception("boom")

            def g():
                raise RuntimeError("boom")
        """,
    }, TypedErrorsRule())
    assert sorted(f.line for f in findings) == [2, 5]


def test_typed_errors_quiet_on_typed_raise(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": """
            def f():
                raise S3Error("NoSuchKey")
        """,
    }, TypedErrorsRule())
    assert findings == []


# -- unlocked-global ----------------------------------------------------------


def test_unlocked_global_fires_on_bare_mutation(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/models/x.py": """
            _CACHE = {}

            def put(k, v):
                _CACHE[k] = v
        """,
    }, UnlockedGlobalRule())
    assert [f.rule for f in findings] == ["unlocked-global"]


def test_unlocked_global_quiet_when_locked_or_marked(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/models/x.py": """
            import threading

            _CACHE = {}
            _CACHE_LOCK = threading.Lock()
            _TABLE = {"a": 1}  # mtpulint: immutable -- built once at import

            def put(k, v):
                with _CACHE_LOCK:
                    _CACHE[k] = v

            def get(k):
                return _TABLE.get(k)
        """,
    }, UnlockedGlobalRule())
    assert findings == []


# -- suppressions -------------------------------------------------------------

_SWALLOW = """
    def f():
        try:
            g()
        except Exception:{inline}
            pass
"""


def test_inline_suppression_same_line(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": _SWALLOW.format(
            inline="  # mtpulint: disable=swallowed-except"
        ),
    }, SwallowedExceptRule())
    assert findings == []


def test_suppression_comment_above_with_justification(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": """
            def f():
                try:
                    g()
                # mtpulint: disable=swallowed-except -- g() is fire-and-forget
                # and failures are observed by its own retry loop.
                except Exception:
                    pass
        """,
    }, SwallowedExceptRule())
    assert findings == []


def test_suppression_for_other_rule_does_not_hide(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": _SWALLOW.format(
            inline="  # mtpulint: disable=typed-errors"
        ),
    }, SwallowedExceptRule())
    assert len(findings) == 1


def test_file_level_suppression(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": "# mtpulint: disable-file=swallowed-except\n"
        + textwrap.dedent(_SWALLOW.format(inline="")),
    }, SwallowedExceptRule())
    assert findings == []


def test_parse_error_is_reported_as_finding(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/x.py": "def f(:\n",
    }, SwallowedExceptRule())
    assert [f.rule for f in findings] == ["parse-error"]


# -- baseline -----------------------------------------------------------------


def _mk(relpath, rule, line):
    from tools.mtpulint import Finding

    return Finding(rule=rule, relpath=relpath, line=line, message="m")


def test_load_baseline_parses_and_skips_junk(tmp_path):
    p = tmp_path / "baseline.txt"
    p.write_text(
        "# comment\n"
        "\n"
        "minio_tpu/api/x.py::swallowed-except::2\n"
        "not-a-valid-line\n"
        "minio_tpu/api/x.py::swallowed-except::1\n"  # additive duplicate
    )
    assert load_baseline(str(p)) == {("minio_tpu/api/x.py", "swallowed-except"): 3}


def test_load_baseline_missing_file_is_empty(tmp_path):
    assert load_baseline(str(tmp_path / "nope.txt")) == {}


def test_apply_baseline_grandfathers_up_to_quota(tmp_path):
    findings = [
        _mk("a.py", "r", 1),
        _mk("a.py", "r", 5),
        _mk("a.py", "r", 9),
    ]
    new, stale = apply_baseline(findings, {("a.py", "r"): 2})
    assert [f.line for f in new] == [9]
    assert stale == []


def test_apply_baseline_reports_stale_entries(tmp_path):
    new, stale = apply_baseline([_mk("a.py", "r", 1)], {("a.py", "r"): 3})
    assert new == []
    assert len(stale) == 1 and "shrink the baseline" in stale[0]


def test_format_baseline_round_trips(tmp_path):
    findings = [_mk("a.py", "r", 1), _mk("a.py", "r", 2), _mk("b.py", "q", 7)]
    text = format_baseline(findings, header="# hdr")
    p = tmp_path / "baseline.txt"
    p.write_text(text)
    assert load_baseline(str(p)) == {("a.py", "r"): 2, ("b.py", "q"): 1}


# -- lock-order ---------------------------------------------------------------


_SAN_WITH_ORDER = """
    LOCK_ORDER = (
        "A._outer_lock",
        "A._inner_lock",
    )
"""


def test_lock_order_fires_on_declared_order_violation(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/sanitizer.py": _SAN_WITH_ORDER,
        "minio_tpu/storage/x.py": """
            class A:
                def f(self):
                    with self._inner_lock:
                        with self._outer_lock:
                            pass
        """,
    }, LockOrderRule())
    assert [f.rule for f in findings] == ["lock-order"]
    assert "LOCK_ORDER" in findings[0].message


def test_lock_order_quiet_when_nesting_matches_declaration(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/sanitizer.py": _SAN_WITH_ORDER,
        "minio_tpu/storage/x.py": """
            class A:
                def f(self):
                    with self._outer_lock:
                        with self._inner_lock:
                            pass
        """,
    }, LockOrderRule())
    assert findings == []


def test_lock_order_detects_cross_module_cycle(tmp_path):
    # a.py takes X then Y; b.py takes Y then X -- a cycle even with no
    # declared order covering either lock.
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/a.py": """
            class P:
                def f(self):
                    with self._x_lock:
                        with self._y_lock:
                            pass
        """,
        "minio_tpu/dist/b.py": """
            class P:
                def g(self):
                    with self._y_lock:
                        with self._x_lock:
                            pass
        """,
    }, LockOrderRule())
    assert len(findings) == 1
    assert "cycle" in findings[0].message


def test_lock_order_ignores_non_lock_context_managers(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/a.py": """
            class P:
                def f(self):
                    with self.session:
                        with self._x_lock:
                            pass
                def g(self):
                    with self._x_lock:
                        with self.session:
                            pass
        """,
    }, LockOrderRule())
    assert findings == []


def test_lock_order_nested_def_resets_held_stack(tmp_path):
    # The inner function body runs later, not under the outer with.
    findings = run_rule(tmp_path, {
        "minio_tpu/dist/a.py": """
            class P:
                def f(self):
                    with self._x_lock:
                        def cb():
                            with self._y_lock:
                                pass
                        return cb
                def g(self):
                    with self._y_lock:
                        with self._x_lock:
                            pass
        """,
    }, LockOrderRule())
    assert findings == []


# -- unjoined-thread ----------------------------------------------------------


def test_unjoined_thread_fires_without_stop_path(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run, daemon=True)
                    self._t.start()
        """,
    }, UnjoinedThreadRule())
    assert [f.rule for f in findings] == ["unjoined-thread"]


def test_unjoined_thread_quiet_when_class_stop_joins(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run, daemon=True)
                    self._t.start()

                def stop(self):
                    self._t.join(timeout=5.0)
        """,
    }, UnjoinedThreadRule())
    assert findings == []


def test_unjoined_thread_quiet_when_joined_in_same_function(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            def scatter(fns):
                ts = [threading.Thread(target=f, daemon=True) for f in fns]
                for t in ts:
                    t.start()
                for t in ts:
                    t.join()
        """,
    }, UnjoinedThreadRule())
    assert findings == []


def test_unjoined_thread_ignores_non_daemon(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    self._t = threading.Thread(target=self._run)
                    self._t.start()
        """,
    }, UnjoinedThreadRule())
    assert findings == []


def test_unjoined_thread_inline_suppression(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    # mtpulint: disable=unjoined-thread -- process-lifetime
                    # singleton by design.
                    self._t = threading.Thread(target=self._run, daemon=True)
                    self._t.start()
        """,
    }, UnjoinedThreadRule())
    assert findings == []


# -- cond-wait-loop -----------------------------------------------------------


def test_cond_wait_loop_fires_on_bare_wait(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/parallel/x.py": """
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()

                def get(self):
                    with self._cv:
                        if not self.items:
                            self._cv.wait()
                        return self.items.pop()
        """,
    }, CondWaitLoopRule())
    assert [f.rule for f in findings] == ["cond-wait-loop"]


def test_cond_wait_loop_quiet_inside_while(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/parallel/x.py": """
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()

                def get(self):
                    with self._cv:
                        while not self.items:
                            self._cv.wait()
                        return self.items.pop()
        """,
    }, CondWaitLoopRule())
    assert findings == []


def test_cond_wait_loop_exempts_wait_for_and_events(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/parallel/x.py": """
            import threading

            class Q:
                def __init__(self):
                    self._cv = threading.Condition()
                    self._stop = threading.Event()

                def get(self):
                    with self._cv:
                        self._cv.wait_for(lambda: self.items)
                    self._stop.wait()
        """,
    }, CondWaitLoopRule())
    assert findings == []


# -- shared-publish -----------------------------------------------------------


def test_shared_publish_fires_on_unlocked_augassign_in_worker(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    self.count += 1
        """,
    }, SharedPublishRule())
    assert [f.rule for f in findings] == ["shared-publish"]
    assert "self.count" in findings[0].message


def test_shared_publish_quiet_under_lock(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    with self._lock:
                        self.count += 1
        """,
    }, SharedPublishRule())
    assert findings == []


def test_shared_publish_follows_helper_calls(tmp_path):
    # _run -> self._tick(): the AugAssign lives in a helper reached only
    # transitively from the thread target.
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    self._tick()

                def _tick(self):
                    self.stats["n"] += 1
        """,
    }, SharedPublishRule())
    assert len(findings) == 1
    assert "self.stats[...]" in findings[0].message


def test_shared_publish_exempts_atomic_publishes_and_request_path(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import threading

            class W:
                def start(self):
                    t = threading.Thread(target=self._run, daemon=True)
                    t.start()

                def _run(self):
                    self.last = 1          # plain assignment: atomic publish
                    self.items.append(2)   # append: atomic under the GIL

                def serve(self):
                    self.requests += 1     # not reachable from the worker
        """,
    }, SharedPublishRule())
    assert findings == []

# -- hot-path-copy ------------------------------------------------------------


def test_hot_path_copy_fires_on_bytes_join_and_augassign(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/erasure.py": """
            def f(view, parts):
                blob = bytes(view)
                joined = b"".join(parts)
                out = bytearray()
                for p in parts:
                    out += p
                return blob, joined, out
        """,
    }, HotPathCopyRule())
    assert [f.rule for f in findings] == ["hot-path-copy"] * 3
    assert sorted(f.line for f in findings) == [2, 3, 6]


def test_hot_path_copy_quiet_on_text_allocs_and_counters(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/api/streaming.py": """
            import os

            def f(raw, names, blocks):
                header = bytes(raw[:12]).decode("latin-1")   # text parse
                zeros = bytes(64)                            # alloc, not a copy
                path = os.path.join("a", "b")                # not a byte join
                csv = ",".join(names)                        # str join
                total = 0
                for b in blocks:
                    total += len(b)                          # int counter
                return header, zeros, path, csv, total
        """,
    }, HotPathCopyRule())
    assert findings == []


def test_hot_path_copy_augassign_tracks_per_scope_accumulators(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/local.py": """
            def f(parts):
                out = []
                for p in parts:
                    out += [p]
                return out

            def g(parts):
                out = b""
                for p in parts:
                    out += p
                return out
        """,
    }, HotPathCopyRule())
    assert [f.rule for f in findings] == ["hot-path-copy"]
    assert findings[0].line == 10


def test_hot_path_copy_scoped_to_data_plane_files(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/metrics.py": """
            def f(view):
                return bytes(view)
        """,
    }, HotPathCopyRule())
    assert findings == []


def test_hot_path_copy_fires_in_memcache(tmp_path):
    # The hot-read tier (object/memcache.py) is GET-path scope: a cache
    # hit that materializes the cached bytes instead of handing out views
    # is exactly the copy the tier exists to avoid.
    findings = run_rule(tmp_path, {
        "minio_tpu/object/memcache.py": """
            def serve(entry):
                buf = bytearray()
                for c in entry.chunks():
                    buf += c
                return bytes(buf)
        """,
    }, HotPathCopyRule())
    assert [f.rule for f in findings] == ["hot-path-copy"] * 2
    assert sorted(f.line for f in findings) == [4, 5]


def test_hot_path_copy_suppressed_with_justification(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/memcache.py": """
            def serve(entry):
                buf = bytearray()
                for c in entry.chunks():
                    buf += c  # mtpulint: disable=hot-path-copy -- buffered convenience API
                return bytes(buf)  # mtpulint: disable=hot-path-copy -- buffered convenience API
        """,
    }, HotPathCopyRule())
    assert findings == []


# -- unsynced-commit ----------------------------------------------------------


def test_unsynced_commit_fires_on_bare_replace(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            import os

            def save(p, data):
                tmp = p + ".tmp"
                with open(tmp, "w") as f:
                    f.write(data)
                os.replace(tmp, p)
        """,
    }, UnsyncedCommitRule())
    assert [f.rule for f in findings] == ["unsynced-commit"]
    assert findings[0].line == 7


def test_unsynced_commit_quiet_with_barrier_in_function(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            import os

            def save(p, data):
                with open(p + ".tmp", "w") as f:
                    f.write(data)
                    os.fsync(f.fileno())
                os.replace(p + ".tmp", p)

            def rename(self, src, dst):
                self._sync_path(src)
                os.rename(src, dst)
                _sync_dir(dst)
        """,
    }, UnsyncedCommitRule())
    assert findings == []


def test_unsynced_commit_fsync_mode_call_is_not_a_barrier(tmp_path):
    # fsync_mode() only *reads* the knob; it must not satisfy the rule.
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            import os

            def save(p):
                mode = fsync_mode()
                os.replace(p + ".tmp", p)
        """,
    }, UnsyncedCommitRule())
    assert len(findings) == 1


def test_unsynced_commit_nested_def_scopes_are_independent(tmp_path):
    # The outer function's barrier does not cover a nested commit closure:
    # the closure runs later, possibly after the barrier's effect is moot.
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/x.py": """
            import os

            def outer(p, fd):
                os.fsync(fd)

                def commit():
                    os.replace(p + ".tmp", p)
                return commit
        """,
    }, UnsyncedCommitRule())
    assert len(findings) == 1


def test_unsynced_commit_scoped_and_suppressible(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/control/x.py": """
            import os

            def save(p):
                os.replace(p + ".tmp", p)
        """,
        "minio_tpu/object/y.py": """
            import os

            def save(p):
                # mtpulint: disable=unsynced-commit -- best-effort file
                os.replace(p + ".tmp", p)
        """,
    }, UnsyncedCommitRule())
    assert findings == []


# -- release-on-all-paths -----------------------------------------------------


def test_release_on_all_paths_fires_when_never_released(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                pb = pool.acquire()
                fill(data, pb.view())
        """,
    }, ReleaseOnAllPathsRule())
    assert [f.rule for f in findings] == ["release-on-all-paths"]
    assert "never released" in findings[0].message
    assert findings[0].line == 2


def test_release_on_all_paths_fires_on_straight_line_release(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                pb = pool.acquire()
                fill(data, pb.view())  # a raise here leaks the window
                pb.release()
        """,
    }, ReleaseOnAllPathsRule())
    assert len(findings) == 1
    assert "straight-line" in findings[0].message


def test_release_on_all_paths_quiet_with_finally_or_handler(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                pb = pool.acquire()
                try:
                    fill(data, pb.view())
                finally:
                    pb.release()

            def g(pool, data):
                pb = pool.acquire()
                try:
                    filled = fill(data, pb.view())
                except BaseException:
                    pb.release()
                    raise
                pb.release()
                return filled
        """,
    }, ReleaseOnAllPathsRule())
    assert findings == []


def test_release_on_all_paths_quiet_on_ownership_transfer(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                pb = pool.acquire()
                return stream_windows(data, pool, pb)

            def g(pool, bufs):
                pb = pool.acquire()
                bufs.add(pb)
        """,
    }, ReleaseOnAllPathsRule())
    assert findings == []


def test_release_on_all_paths_ignores_locks_and_semaphores(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(lk, sem):
                got = lk.acquire(writer=True, timeout=30)
                ok = sem.acquire(blocking=False)
        """,
    }, ReleaseOnAllPathsRule())
    assert findings == []


def test_release_on_all_paths_suppressed_with_justification(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                # mtpulint: disable=release-on-all-paths -- test harness leak on purpose
                pb = pool.acquire()
                fill(data, pb.view())
        """,
    }, ReleaseOnAllPathsRule())
    assert findings == []


# -- double-release -----------------------------------------------------------


def test_double_release_fires_on_sequential_releases(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool):
                pb = pool.acquire()
                pb.release()
                pb.release()
        """,
    }, DoubleReleaseRule())
    assert [f.rule for f in findings] == ["double-release"]
    assert findings[0].line == 4


def test_double_release_fires_on_unguarded_finally(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data):
                pb = pool.acquire()
                try:
                    fill(data, pb.view())
                    pb.release()
                finally:
                    pb.release()
        """,
    }, DoubleReleaseRule())
    assert len(findings) == 1
    assert "finally" in findings[0].message


def test_double_release_quiet_with_none_rebind_guard(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, bufs, data):
                pb = pool.acquire()
                try:
                    fill(data, pb.view())
                    bufs.add(pb)
                    pb = None
                finally:
                    if pb is not None:
                        pb.release()
        """,
    }, DoubleReleaseRule())
    assert findings == []


def test_double_release_quiet_with_retain_between(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool):
                pb = pool.acquire()
                pb.release()
                pb.retain()
                pb.release()
        """,
    }, DoubleReleaseRule())
    assert findings == []


# -- view-escape --------------------------------------------------------------


def test_view_escape_fires_on_self_assign_and_return(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            class C:
                def f(self, pool):
                    pb = pool.acquire()
                    v = pb.view(0, 64)
                    self.window = v
                    pb.release()

            def g(pool):
                pb = pool.acquire()
                v = pb.view()
                pb.release()
                return v
        """,
    }, ViewEscapeRule())
    assert [f.rule for f in findings] == ["view-escape", "view-escape"]
    assert findings[0].line == 5
    assert "stored outside" in findings[0].message
    assert "returned" in findings[1].message


def test_view_escape_fires_on_container_append_and_submit(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, batch, ex):
                pb = pool.acquire()
                batch.append(pb.view(0, 32))
                ex.submit(consume, pb.view(32, 64))
                pb.release()
        """,
    }, ViewEscapeRule())
    assert len(findings) == 2
    assert "container" in findings[0].message
    assert "submit" in findings[1].message


def test_view_escape_fires_on_closure_capture(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, ex):
                pb = pool.acquire()
                v = pb.view()

                def worker():
                    return consume(v)

                ex.submit(worker)
                pb.release()
        """,
    }, ViewEscapeRule())
    assert len(findings) == 1
    assert "closure" in findings[0].message


def test_view_escape_quiet_with_retain_or_plain_calls(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool, data, batch):
                pb = pool.acquire()
                filled = fill(data, pb.view())   # synchronous use: fine
                pb.retain()
                batch.append(pb.view(0, filled)) # rides the retained buffer
                pb.release()
                return filled
        """,
    }, ViewEscapeRule())
    assert findings == []


def test_view_escape_suppressed_with_justification(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/object/x.py": """
            def f(pool):
                pb = pool.acquire()
                v = pb.view()
                # mtpulint: disable=view-escape -- caller releases via the window object
                return v
        """,
    }, ViewEscapeRule())
    assert findings == []


# -- interface-conformance ----------------------------------------------------

_IFACE_SRC = """
    import abc

    class StorageAPI(abc.ABC):
        @abc.abstractmethod
        def read_all(self, volume, path): ...

        @abc.abstractmethod
        def write_all(self, volume, path, data): ...

        def read_file_into(self, volume, path, offset, buf):
            return 0
"""


def test_interface_conformance_fires_on_missing_methods(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/interface.py": _IFACE_SRC,
        "minio_tpu/storage/wrap.py": """
            class PartialWrapper:
                def __init__(self, inner):
                    self.__dict__["inner"] = inner

                def read_all(self, volume, path):
                    return self.inner.read_all(volume, path)
        """,
    }, InterfaceConformanceRule())
    missing = sorted(f.message.split("StorageAPI.")[1].split(" ")[0] for f in findings)
    assert [f.rule for f in findings] == ["interface-conformance"] * 2
    assert missing == ["read_file_into", "write_all"]


def test_interface_conformance_quiet_with_getattr_delegation(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/interface.py": _IFACE_SRC,
        "minio_tpu/chaos/wrap.py": """
            class Delegating:
                def __init__(self, inner):
                    self.inner = inner

                def __getattr__(self, name):
                    return getattr(self.inner, name)

                def read_all(self, volume, path):
                    return self.inner.read_all(volume, path)
        """,
    }, InterfaceConformanceRule())
    assert findings == []


def test_interface_conformance_ignores_non_wrappers(tmp_path):
    findings = run_rule(tmp_path, {
        "minio_tpu/storage/interface.py": _IFACE_SRC,
        "minio_tpu/storage/other.py": """
            class NotAWrapper:
                def __init__(self, path):
                    self.path = path
        """,
    }, InterfaceConformanceRule())
    assert findings == []

"""mtpulint rules: the project invariants, one class each.

Every rule encodes a structural property PRs 1-4 established and a refactor
could silently drop: error transport (swallowed-except, typed-errors),
deadline plumbing (raw-transport, deadline-rebind), lock hygiene
(lock-blocking-io, unlocked-global), resource lifetime (resource-leak),
durability barriers (unsynced-commit), the observability seams
(stage-key, metrics-rendered), and buffer lifetime on the zero-copy plane
(release-on-all-paths, double-release, view-escape, interface-conformance
-- the static half of bufsan, see minio_tpu/control/bufsan.py). Rules are
AST-based
-- they see structure, not text -- so renames and reformatting can't dodge
them, and suppressions (`# mtpulint: disable=<rule>`) are visible decisions
in the diff rather than regex blind spots.
"""

from __future__ import annotations

import ast

from .engine import FileContext, Finding, ProjectContext, Rule

# Hot-path packages: where a swallowed error means silent data-plane damage.
HOT_PATHS = (
    "minio_tpu/api/",
    "minio_tpu/object/",
    "minio_tpu/dist/",
    "minio_tpu/storage/",
    "minio_tpu/chaos/",
)

TRANSPORT = "minio_tpu/dist/transport.py"
PERF = "minio_tpu/control/perf.py"
METRICS = "minio_tpu/control/metrics.py"
DEGRADE = "minio_tpu/control/degrade.py"
PROFILER = "minio_tpu/control/profiler.py"
SELFTEST = "minio_tpu/control/selftest.py"
POOLMGR = "minio_tpu/object/poolmgr.py"
REBALANCE = "minio_tpu/control/rebalance.py"
FLIGHT = "minio_tpu/control/flight.py"
LOGGING = "minio_tpu/control/logging.py"
PUBSUB = "minio_tpu/control/pubsub.py"


def _call_name(node: ast.Call) -> str:
    """Dotted best-effort name of a call: `a.b.c(...)` -> 'a.b.c',
    `f(...)` -> 'f'. Unresolvable pieces render as '?'."""
    parts: list[str] = []
    cur = node.func
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
    else:
        parts.append("?")
    return ".".join(reversed(parts))


def _str_const(node: ast.AST | None) -> str | None:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


# ---------------------------------------------------------------------------
# swallowed-except
# ---------------------------------------------------------------------------


class SwallowedExceptRule(Rule):
    """Broad `except` that swallows silently on a hot path.

    A handler for bare/`Exception`/`BaseException` whose body neither
    re-raises, returns, logs, counts, nor calls anything is a black hole:
    the error happened, nobody will ever know. Narrow the type, or make the
    swallow observable (log + metric)."""

    id = "swallowed-except"
    title = "broad except swallows without logging or re-raising"
    scope = HOT_PATHS

    BROAD = {"Exception", "BaseException"}

    def _is_broad(self, handler: ast.ExceptHandler) -> bool:
        t = handler.type
        if t is None:
            return True
        if isinstance(t, ast.Name):
            return t.id in self.BROAD
        if isinstance(t, ast.Tuple):
            return any(
                isinstance(e, ast.Name) and e.id in self.BROAD for e in t.elts
            )
        return False

    def _is_silent(self, handler: ast.ExceptHandler) -> bool:
        """Silent = nothing in the body raises, returns, or calls anything.
        A bare `return`/`continue`/`pass` body observes nothing."""
        for stmt in handler.body:
            for node in ast.walk(stmt):
                if isinstance(node, (ast.Raise, ast.Call, ast.Yield, ast.YieldFrom)):
                    return False
                if isinstance(node, ast.Return) and node.value is not None:
                    return False
        return True

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.ExceptHandler):
                    continue
                if self._is_broad(node) and self._is_silent(node):
                    what = "bare except" if node.type is None else "broad except"
                    yield Finding(
                        self.id,
                        ctx.relpath,
                        node.lineno,
                        f"{what} swallows silently -- narrow the type, or "
                        "log-and-count before continuing",
                    )


# ---------------------------------------------------------------------------
# raw-transport
# ---------------------------------------------------------------------------


class RawTransportRule(Rule):
    """Raw `requests`/`socket` traffic outside dist/transport.py.

    All internode RPC must ride RestClient.call: that is where the deadline
    budget caps the socket timeout, the X-Mtpu-Deadline header is stamped,
    chaos faults inject, and per-peer histograms record. A module opening
    its own HTTP session or socket re-introduces the unbounded hop. External
    backends (the S3 gateway) are the one legitimate exception -- suppress
    with a justification comment."""

    id = "raw-transport"
    title = "raw requests/socket use outside dist/transport.py"
    scope = ("minio_tpu/dist/", "minio_tpu/storage/", "minio_tpu/object/")

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            if ctx.relpath == TRANSPORT:
                continue
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Import):
                    for alias in node.names:
                        if alias.name.split(".")[0] in ("requests", "socket"):
                            yield self._finding(ctx, node, f"import {alias.name}")
                elif isinstance(node, ast.ImportFrom):
                    if (node.module or "").split(".")[0] in ("requests", "socket"):
                        yield self._finding(ctx, node, f"from {node.module} import ...")
                elif isinstance(node, ast.Call):
                    name = _call_name(node)
                    root = name.split(".")[0]
                    if root in ("requests", "socket") and "." in name:
                        yield self._finding(ctx, node, f"{name}(...)")

    def _finding(self, ctx, node, what: str) -> Finding:
        return Finding(
            self.id,
            ctx.relpath,
            node.lineno,
            f"{what} -- internode traffic must ride dist/transport.py "
            "RestClient so the deadline/chaos/metrics seams apply",
        )


# ---------------------------------------------------------------------------
# deadline-rebind
# ---------------------------------------------------------------------------


class DeadlineRebindRule(Rule):
    """The deadline budget must ride EVERY hop (tools/deadline_lint.py,
    generalized to the AST).

    Two obligations:
      1. dist/transport.py keeps the plumbing: a `deadline.remaining()`
         check, a DEADLINE_HEADER stamp on outgoing requests
         (`headers[DEADLINE_HEADER] = ...`), and a DeadlineExceeded raise.
      2. Every internode REST *server* module (one that authenticates
         TOKEN_HEADER on inbound requests) re-binds the propagated budget
         with `deadline.bind_header(...)` -- a hop that drops the header
         resets the budget to infinity for everything downstream."""

    id = "deadline-rebind"
    title = "deadline propagation plumbing dropped"
    scope = ("minio_tpu/",)

    def check(self, project: ProjectContext):
        tctx = project.get(TRANSPORT)
        if tctx is not None:
            yield from self._check_transport(tctx)
        for ctx in project.iter_files(*self.scope):
            if ctx.relpath == TRANSPORT:
                continue
            if self._authenticates_token(ctx) and not self._rebinds(ctx):
                yield Finding(
                    self.id,
                    ctx.relpath,
                    1,
                    "authenticates TOKEN_HEADER (REST server) but never calls "
                    "deadline.bind_header -- inbound budgets are dropped here",
                )

    def _check_transport(self, ctx):
        has_remaining = False
        has_stamp = False
        has_exceeded = False
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and _call_name(node).endswith(
                "deadline.remaining"
            ):
                has_remaining = True
            if isinstance(node, ast.Assign):
                for tgt in node.targets:
                    if (
                        isinstance(tgt, ast.Subscript)
                        and isinstance(tgt.slice, ast.Name)
                        and tgt.slice.id == "DEADLINE_HEADER"
                    ):
                        has_stamp = True
            if isinstance(node, ast.Raise) and node.exc is not None:
                name = ""
                if isinstance(node.exc, ast.Call):
                    name = _call_name(node.exc)
                elif isinstance(node.exc, (ast.Name, ast.Attribute)):
                    cur = node.exc
                    name = cur.attr if isinstance(cur, ast.Attribute) else cur.id
                if "DeadlineExceeded" in name:
                    has_exceeded = True
        if not has_remaining:
            yield Finding(self.id, ctx.relpath, 1,
                          "missing deadline.remaining() budget check before the hop")
        if not has_stamp:
            yield Finding(self.id, ctx.relpath, 1,
                          "missing headers[DEADLINE_HEADER] stamp on outgoing RPCs")
        if not has_exceeded:
            yield Finding(self.id, ctx.relpath, 1,
                          "missing DeadlineExceeded raise for a spent budget")

    @staticmethod
    def _authenticates_token(ctx) -> bool:
        for node in ast.walk(ctx.tree):
            if (
                isinstance(node, ast.Call)
                and _call_name(node).endswith("headers.get")
                and node.args
                and isinstance(node.args[0], ast.Name)
                and node.args[0].id == "TOKEN_HEADER"
            ):
                return True
        return False

    @staticmethod
    def _rebinds(ctx) -> bool:
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Call) and _call_name(node).endswith(
                "deadline.bind_header"
            ):
                return True
        return False


# ---------------------------------------------------------------------------
# lock-blocking-io
# ---------------------------------------------------------------------------


class LockBlockingIORule(Rule):
    """Blocking I/O inside a `with <lock>:` body.

    A sleep, HTTP call, or file open while holding a mutex convoys every
    other thread that needs it -- the exact pattern behind the refresh-
    daemon redesign in dist/locks.py. Do the I/O outside, publish results
    under the lock."""

    id = "lock-blocking-io"
    title = "blocking I/O while holding a lock"
    scope = ("minio_tpu/storage/", "minio_tpu/dist/", "minio_tpu/control/")

    _LOCK_HINTS = ("lock", "mutex", "_mu", "sem")
    _BLOCKING_EXACT = {
        "time.sleep", "sleep", "open", "subprocess.run", "subprocess.Popen",
        "subprocess.check_call", "subprocess.check_output",
        "socket.create_connection", "tempfile.NamedTemporaryFile",
    }
    _BLOCKING_PREFIX = ("requests.",)
    _BLOCKING_SUFFIX = (".read_file", ".write_all", ".create_file", ".append_file")

    def _is_lock_expr(self, expr: ast.AST) -> bool:
        name = ""
        if isinstance(expr, ast.Attribute):
            name = expr.attr
        elif isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Call):
            # with self._locks[i] / with lock() styles resolve via the callee
            return self._is_lock_expr(expr.func)
        elif isinstance(expr, ast.Subscript):
            return self._is_lock_expr(expr.value)
        low = name.lower()
        return any(h in low for h in self._LOCK_HINTS)

    def _is_blocking(self, call: ast.Call) -> bool:
        name = _call_name(call)
        if name in self._BLOCKING_EXACT:
            return True
        if any(name.startswith(p) for p in self._BLOCKING_PREFIX):
            return True
        return any(name.endswith(s) for s in self._BLOCKING_SUFFIX)

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for node in ast.walk(ctx.tree):
                if not isinstance(node, (ast.With, ast.AsyncWith)):
                    continue
                if not any(
                    self._is_lock_expr(item.context_expr) for item in node.items
                ):
                    continue
                for stmt in node.body:
                    for sub in ast.walk(stmt):
                        # Deferred work (nested defs) runs after release.
                        if isinstance(
                            sub, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                        ):
                            break
                        if isinstance(sub, ast.Call) and self._is_blocking(sub):
                            yield Finding(
                                self.id,
                                ctx.relpath,
                                sub.lineno,
                                f"{_call_name(sub)}(...) inside a `with lock:` "
                                "body -- do the I/O outside, publish under "
                                "the lock",
                            )


# ---------------------------------------------------------------------------
# resource-leak
# ---------------------------------------------------------------------------


class ResourceLeakRule(Rule):
    """open()/NamedTemporaryFile() without `with` or a closing try/finally.

    A handle that leaks on the exception path pins an fd (and on staged
    writes, a .tmp file) until GC happens to run -- under load that is fd
    exhaustion. Acceptable shapes: `with open(...)`, `f = open(...)` later
    entered as `with f:` or closed via `f.close()` in a `finally:`, or the
    handle escaping as a return value / argument (ownership transferred)."""

    id = "resource-leak"
    title = "file handle not closed on all paths"
    scope = HOT_PATHS

    _OPENERS = {
        "open", "tempfile.NamedTemporaryFile", "tempfile.TemporaryFile",
        "NamedTemporaryFile", "TemporaryFile", "io.open",
    }

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for fn in ast.walk(ctx.tree):
                if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    yield from self._check_function(ctx, fn)

    def _check_function(self, ctx, fn):
        with_exprs: set[int] = set()     # id() of calls used as with-items
        owned: set[int] = set()          # id() of calls whose result escapes
        assigns: dict[int, str] = {}     # id(call) -> simple target name
        calls: list[ast.Call] = []

        for node in ast.walk(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    for sub in ast.walk(item.context_expr):
                        if isinstance(sub, ast.Call):
                            with_exprs.add(id(sub))
            if isinstance(node, ast.Call):
                if isinstance(node.func, ast.Call):
                    pass
                for arg in list(node.args) + [kw.value for kw in node.keywords]:
                    for sub in ast.walk(arg):
                        if isinstance(sub, ast.Call):
                            owned.add(id(sub))
            if isinstance(node, ast.Return) and node.value is not None:
                for sub in ast.walk(node.value):
                    if isinstance(sub, ast.Call):
                        owned.add(id(sub))
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt = node.targets[0]
                if isinstance(tgt, ast.Name):
                    for sub in ast.walk(node.value):
                        if isinstance(sub, ast.Call):
                            assigns[id(sub)] = tgt.id
            if isinstance(node, ast.Call) and self._is_opener(node):
                calls.append(node)

        closed_names = self._names_closed_or_withed(fn)
        for call in calls:
            if id(call) in with_exprs or id(call) in owned:
                continue
            name = assigns.get(id(call))
            if name is not None and name in closed_names:
                continue
            yield Finding(
                self.id,
                ctx.relpath,
                call.lineno,
                f"{_call_name(call)}(...) result is neither entered as "
                "`with` nor closed in a try/finally -- leaks the handle "
                "on the exception path",
            )

    def _is_opener(self, call: ast.Call) -> bool:
        return _call_name(call) in self._OPENERS

    @staticmethod
    def _names_closed_or_withed(fn) -> set[str]:
        """Names later entered as `with <name>:` anywhere in the function,
        or `.close()`d inside a `finally:` block."""
        names: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if isinstance(item.context_expr, ast.Name):
                        names.add(item.context_expr.id)
            if isinstance(node, ast.Try):
                for stmt in node.finalbody:
                    for sub in ast.walk(stmt):
                        if (
                            isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and sub.func.attr == "close"
                            and isinstance(sub.func.value, ast.Name)
                        ):
                            names.add(sub.func.value.id)
        return names


# ---------------------------------------------------------------------------
# stage-key
# ---------------------------------------------------------------------------


class StageKeyRule(Rule):
    """Every literal stage mark must name a registered (layer, stage) key.

    control/perf.py declares STAGES (the literal registry) and
    DYNAMIC_STAGE_LAYERS (layers whose stage names are computed at runtime:
    per-peer endpoints, per-storage-API names). A mark outside both would
    silently mint a new unaggregated ledger series no dashboard knows about
    -- register it (and its dashboard row) or fix the typo."""

    id = "stage-key"
    title = "stage mark not registered in control/perf.py"
    scope = ("minio_tpu/",)

    def _load_registry(self, project):
        stages: set[tuple[str, str]] = set()
        dynamic: set[str] = set()
        ctx = project.get(PERF)
        if ctx is None:
            return None, None
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                tgt, value = node.target, node.value
            else:
                continue
            if not isinstance(tgt, ast.Name):
                continue
            if tgt.id == "STAGES":
                for sub in ast.walk(value):
                    if isinstance(sub, ast.Tuple) and len(sub.elts) == 2:
                        layer = _str_const(sub.elts[0])
                        stage = _str_const(sub.elts[1])
                        if layer is not None and stage is not None:
                            stages.add((layer, stage))
            elif tgt.id == "DYNAMIC_STAGE_LAYERS":
                for sub in ast.walk(value):
                    s = _str_const(sub)
                    if s is not None:
                        dynamic.add(s)
        return (stages or None), (dynamic or None)

    def check(self, project: ProjectContext):
        stages, dynamic = self._load_registry(project)
        if stages is None:
            ctx = project.get(PERF)
            if ctx is not None:
                yield Finding(
                    self.id, PERF, 1,
                    "STAGES registry literal not found in control/perf.py",
                )
            return
        dynamic = dynamic or set()
        layers = {l for l, _ in stages} | dynamic
        for ctx in project.iter_files("minio_tpu/"):
            if ctx.relpath in (PERF, "minio_tpu/control/tracing.py"):
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call):
                    continue
                name = _call_name(node)
                if name.endswith(("tracing.span", "tracing.root_span", "tracing.stage")):
                    if len(node.args) < 2:
                        continue
                    stage_arg, layer_arg = node.args[0], node.args[1]
                elif name.endswith("ledger.record"):
                    if len(node.args) < 2:
                        continue
                    layer_arg, stage_arg = node.args[0], node.args[1]
                else:
                    continue
                layer = _str_const(layer_arg)
                stage = _str_const(stage_arg)
                if layer is None:
                    continue  # computed layer: nothing checkable statically
                if stage is None:
                    if layer not in layers:
                        yield Finding(
                            self.id, ctx.relpath, node.lineno,
                            f"dynamic stage mark in unregistered layer "
                            f"{layer!r} -- add it to DYNAMIC_STAGE_LAYERS "
                            "in control/perf.py",
                        )
                elif (layer, stage) not in stages and layer not in dynamic:
                    yield Finding(
                        self.id, ctx.relpath, node.lineno,
                        f"stage key ({layer!r}, {stage!r}) not in the "
                        "control/perf.py STAGES registry",
                    )


# ---------------------------------------------------------------------------
# metrics-rendered
# ---------------------------------------------------------------------------


class MetricsRenderedRule(Rule):
    """Counters bumped in control/degrade.py and control/perf.py must be
    rendered by control/metrics.py.

    A counter nobody exports is a measurement nobody sees: the increment
    costs a lock on the hot path and buys zero observability. Every public
    `self.<name> += ...` / keyed-dict bump in DegradeStats,
    SlowRequestCapture, the profiling plane's CopyLedger, the
    self-measurement plane's SelfTestStats, the flight recorder, the
    pub/sub hubs' drop accounting, and the webhook log sink's queue
    counters must appear (as a string key or attribute) in the exposition
    renderer."""

    id = "metrics-rendered"
    title = "counter incremented but never rendered in control/metrics.py"
    scope = (DEGRADE, PERF, PROFILER, SELFTEST, POOLMGR, REBALANCE, FLIGHT,
             LOGGING, PUBSUB)

    _COUNTER_CLASSES = {
        "DegradeStats", "SlowRequestCapture", "CopyLedger", "SelfTestStats",
        "PoolLifecycleStats", "ThrottleBudget", "FlightRecorder", "PubSub",
        "WebhookTarget",
    }

    def _counters(self, ctx) -> list[tuple[str, int]]:
        out: list[tuple[str, int]] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.ClassDef):
                continue
            if node.name not in self._COUNTER_CLASSES:
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.AugAssign) or not isinstance(
                    sub.op, ast.Add
                ):
                    continue
                tgt = sub.target
                name = None
                if (
                    isinstance(tgt, ast.Attribute)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id == "self"
                ):
                    name = tgt.attr
                elif (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Attribute)
                    and isinstance(tgt.value.value, ast.Name)
                    and tgt.value.value.id == "self"
                ):
                    name = tgt.value.attr
                if name and not name.startswith("_"):
                    out.append((name, sub.lineno))
        # keyed bumps written as self.d[k] = self.d.get(k, 0) + 1
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign) or len(node.targets) != 1:
                continue
            tgt = node.targets[0]
            if (
                isinstance(tgt, ast.Subscript)
                and isinstance(tgt.value, ast.Attribute)
                and isinstance(tgt.value.value, ast.Name)
                and tgt.value.value.id == "self"
                and not tgt.value.attr.startswith("_")
                and isinstance(node.value, ast.BinOp)
                and isinstance(node.value.op, ast.Add)
            ):
                out.append((tgt.value.attr, node.lineno))
        return out

    @staticmethod
    def _rendered_tokens(metrics_ctx) -> set[str]:
        tokens: set[str] = set()
        for node in ast.walk(metrics_ctx.tree):
            if isinstance(node, ast.Constant) and isinstance(node.value, str):
                tokens.add(node.value)
            if isinstance(node, ast.Attribute):
                tokens.add(node.attr)
        return tokens

    def check(self, project: ProjectContext):
        metrics_ctx = project.get(METRICS)
        if metrics_ctx is None:
            return
        tokens = self._rendered_tokens(metrics_ctx)
        seen: set[str] = set()
        for relpath in self.scope:
            ctx = project.get(relpath)
            if ctx is None:
                continue
            for name, lineno in self._counters(ctx):
                if name in seen:
                    continue
                seen.add(name)
                if name not in tokens:
                    yield Finding(
                        self.id, ctx.relpath, lineno,
                        f"counter {name!r} is incremented here but "
                        "control/metrics.py never renders it",
                    )


# ---------------------------------------------------------------------------
# typed-errors
# ---------------------------------------------------------------------------


class TypedErrorsRule(Rule):
    """API handlers must raise typed errors, never `raise Exception(...)`.

    api/errors.py maps exception TYPES onto S3 wire codes; an untyped raise
    can only ever surface as a 500 InternalError with a leaked str(e). Use
    S3Error / utils.errors types so the client sees the right code."""

    id = "typed-errors"
    title = "untyped raise in an API module"
    scope = ("minio_tpu/api/",)

    _UNTYPED = {"Exception", "BaseException", "RuntimeError"}

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Raise) or node.exc is None:
                    continue
                exc = node.exc
                name = None
                if isinstance(exc, ast.Call) and isinstance(exc.func, ast.Name):
                    name = exc.func.id
                elif isinstance(exc, ast.Name):
                    name = exc.id
                if name in self._UNTYPED:
                    yield Finding(
                        self.id, ctx.relpath, node.lineno,
                        f"raise {name}(...) in an API module -- raise "
                        "S3Error or a typed utils.errors class so the "
                        "client sees a real S3 code",
                    )


# ---------------------------------------------------------------------------
# unlocked-global
# ---------------------------------------------------------------------------


class UnlockedGlobalRule(Rule):
    """Mutable module globals mutated outside a lock.

    A module-level dict/list/set written from request or worker threads
    without a lock is a check-then-act race (two threads both miss a cache
    entry and both build it). Either guard every mutation with a module
    lock, or mark the binding `# mtpulint: immutable` when it is write-once
    at import time."""

    id = "unlocked-global"
    title = "mutable module global mutated without a lock"
    scope = ("minio_tpu/",)

    _MUTABLE_CTORS = {
        "dict", "list", "set", "OrderedDict", "defaultdict", "deque",
        "collections.OrderedDict", "collections.defaultdict",
        "collections.deque",
    }
    _MUTATORS = {
        "append", "add", "update", "pop", "popitem", "clear", "extend",
        "insert", "remove", "discard", "setdefault", "appendleft",
    }
    _LOCK_HINTS = ("lock", "mutex", "_mu", "sem")

    def _module_mutables(self, ctx) -> dict[str, int]:
        """Module-level `NAME = {}/[]/set()/...` bindings -> lineno."""
        out: dict[str, int] = {}
        body = getattr(ctx.tree, "body", [])
        for node in body:
            targets = []
            value = None
            if isinstance(node, ast.Assign):
                targets, value = node.targets, node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets, value = [node.target], node.value
            if value is None:
                continue
            mutable = isinstance(value, (ast.Dict, ast.List, ast.Set)) or (
                isinstance(value, ast.Call)
                and _call_name(value) in self._MUTABLE_CTORS
            )
            if not mutable:
                continue
            for tgt in targets:
                if isinstance(tgt, ast.Name) and not self._marked_immutable(
                    ctx, node.lineno
                ):
                    out[tgt.id] = node.lineno
        return out

    @staticmethod
    def _marked_immutable(ctx, lineno: int) -> bool:
        lines = ctx.lines
        if 1 <= lineno <= len(lines) and "immutable" in lines[lineno - 1]:
            return True
        return lineno >= 2 and "immutable" in lines[lineno - 2]

    def _is_lock_expr(self, expr: ast.AST) -> bool:
        name = ""
        if isinstance(expr, ast.Attribute):
            name = expr.attr
        elif isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Subscript):
            return self._is_lock_expr(expr.value)
        low = name.lower()
        return any(h in low for h in self._LOCK_HINTS)

    def _mutation_at(self, node, names: set[str]):
        """(name, lineno) when THIS node (not its subtree) mutates a
        watched global: subscript assign/del/augassign, or a mutator-method
        call (`g.append(...)`, `g.setdefault(...)`, ...)."""
        if isinstance(node, (ast.Assign, ast.AugAssign)):
            tgts = node.targets if isinstance(node, ast.Assign) else [node.target]
            for tgt in tgts:
                if (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id in names
                ):
                    return (tgt.value.id, node.lineno)
        if isinstance(node, ast.Delete):
            for tgt in node.targets:
                if (
                    isinstance(tgt, ast.Subscript)
                    and isinstance(tgt.value, ast.Name)
                    and tgt.value.id in names
                ):
                    return (tgt.value.id, node.lineno)
        if (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in self._MUTATORS
            and isinstance(node.func.value, ast.Name)
            and node.func.value.id in names
        ):
            return (node.func.value.id, node.lineno)
        return None

    def _mutations(self, fn, names: set[str]):
        """(name, lineno, locked) for every mutation of a watched global
        inside `fn`, where locked = lexically inside a `with <lock>:` body
        at any nesting depth. Each node is visited exactly once, carrying
        the innermost lock state down the tree."""

        def scan(node, locked: bool):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                body_locked = locked or any(
                    self._is_lock_expr(i.context_expr) for i in node.items
                )
                for item in node.items:
                    yield from scan(item.context_expr, locked)
                for child in node.body:
                    yield from scan(child, body_locked)
                return
            hit = self._mutation_at(node, names)
            if hit is not None:
                yield (*hit, locked)
            for child in ast.iter_child_nodes(node):
                yield from scan(child, locked)

        for stmt in fn.body:
            yield from scan(stmt, False)

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            mutables = self._module_mutables(ctx)
            if not mutables:
                continue
            names = set(mutables)
            for node in ast.walk(ctx.tree):
                if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                for name, lineno, locked in self._mutations(node, names):
                    if not locked:
                        yield Finding(
                            self.id, ctx.relpath, lineno,
                            f"module global {name!r} mutated outside a "
                            "lock -- guard it, or mark the binding "
                            "`# mtpulint: immutable` if write-once",
                        )


# ---------------------------------------------------------------------------
# lock-order
# ---------------------------------------------------------------------------


SANITIZER = "minio_tpu/control/sanitizer.py"

_LOCK_HINTS = ("lock", "mutex", "_mu", "sem")


def _class_spans(ctx) -> list[tuple[int, int, str]]:
    spans = []
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ClassDef):
            spans.append((node.lineno, node.end_lineno or node.lineno, node.name))
    return spans


def _enclosing_class(spans, lineno: int) -> str | None:
    best = None
    for lo, hi, name in spans:
        if lo <= lineno <= hi and (best is None or lo > best[0]):
            best = (lo, name)
    return best[1] if best else None


class LockOrderRule(Rule):
    """Nested `with lock:` pairs must agree on one global acquisition order.

    The static half of mtpusan's lock-order graph: every lexically nested
    lock pair (`with A: ... with B:`) contributes an A->B edge, named by the
    qualified form `ClassName.attr` (module locks: `filestem.name`). Two
    checks over the cross-module digraph:
      * a cycle (A->B somewhere, B->A somewhere else) is a potential
        deadlock even if no run has wedged yet;
      * a pair that contradicts the declared LOCK_ORDER table in
        control/sanitizer.py (outermost first) is a hierarchy violation.
    The runtime sanitizer catches orders composed dynamically through
    calls; this rule catches the lexical ones before the code ever runs."""

    id = "lock-order"
    title = "nested lock acquisition order inverted"
    scope = ("minio_tpu/",)

    def _lock_name(self, expr: ast.AST, ctx, spans, lineno: int) -> str | None:
        """Qualified lock-class name for a with-item, or None if not a lock
        (or not statically nameable)."""
        if isinstance(expr, ast.Subscript):
            return self._lock_name(expr.value, ctx, spans, lineno)
        attr = None
        if (
            isinstance(expr, ast.Attribute)
            and isinstance(expr.value, ast.Name)
            and expr.value.id == "self"
        ):
            attr = expr.attr
            owner = _enclosing_class(spans, lineno)
            if owner is None:
                return None
        elif isinstance(expr, ast.Name):
            attr = expr.id
            owner = ctx.relpath.rsplit("/", 1)[-1][:-3]  # file stem
        else:
            return None
        low = attr.lower()
        if not any(h in low for h in _LOCK_HINTS):
            return None
        return f"{owner}.{attr}"

    def _declared_order(self, project) -> list[str]:
        ctx = project.get(SANITIZER)
        if ctx is None:
            return []
        for node in ast.walk(ctx.tree):
            tgt = value = None
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, value = node.targets[0], node.value
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                tgt, value = node.target, node.value
            if isinstance(tgt, ast.Name) and tgt.id == "LOCK_ORDER":
                return [
                    s for s in (
                        _str_const(e) for e in ast.walk(value)
                        if isinstance(e, ast.Constant)
                    ) if s
                ]
        return []

    def _edges(self, project):
        """Every lexically nested (outer, inner) lock pair in scope, with
        the inner acquisition's location."""
        for ctx in project.iter_files(*self.scope):
            if ctx.relpath == SANITIZER:
                continue
            spans = _class_spans(ctx)

            def scan(node, held):
                if isinstance(node, (ast.With, ast.AsyncWith)):
                    inner_held = list(held)
                    for item in node.items:
                        name = self._lock_name(
                            item.context_expr, ctx, spans, node.lineno
                        )
                        if name is not None:
                            for outer in inner_held:
                                yield (outer, name, ctx, node.lineno)
                            inner_held.append(name)
                    for child in node.body:
                        yield from scan(child, inner_held)
                    return
                # A nested def's body runs later, outside these withs.
                child_held = (
                    []
                    if isinstance(
                        node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)
                    )
                    else held
                )
                for child in ast.iter_child_nodes(node):
                    yield from scan(child, child_held)

            yield from scan(ctx.tree, [])

    def check(self, project: ProjectContext):
        order = self._declared_order(project)
        rank = {name: i for i, name in enumerate(order)}
        graph: dict[str, set[str]] = {}
        first_at: dict[tuple[str, str], tuple] = {}
        for outer, inner, ctx, lineno in self._edges(project):
            if outer == inner:
                continue
            graph.setdefault(outer, set()).add(inner)
            first_at.setdefault((outer, inner), (ctx, lineno))
            if outer in rank and inner in rank and rank[outer] > rank[inner]:
                yield Finding(
                    self.id, ctx.relpath, lineno,
                    f"acquires {inner!r} while holding {outer!r}, but "
                    "LOCK_ORDER in control/sanitizer.py declares "
                    f"{inner!r} before {outer!r} -- invert the nesting or "
                    "amend the declared order",
                )
        seen_cycles: set[frozenset] = set()
        for (a, b), (ctx, lineno) in sorted(
            first_at.items(), key=lambda kv: (kv[1][0].relpath, kv[1][1])
        ):
            path = self._find_path(graph, b, a)
            if path is None:
                continue
            cycle = frozenset([a] + path)
            if cycle in seen_cycles:
                continue
            seen_cycles.add(cycle)
            yield Finding(
                self.id, ctx.relpath, lineno,
                "lock-order cycle: " + " -> ".join([a] + path)
                + " -- threads taking these in opposite orders can "
                "deadlock; pick one global order",
            )

    @staticmethod
    def _find_path(graph, src: str, dst: str) -> list[str] | None:
        prev = {src: src}
        frontier = [src]
        while frontier:
            nxt = []
            for u in frontier:
                for v in graph.get(u, ()):
                    if v in prev:
                        continue
                    prev[v] = u
                    if v == dst:
                        path = [v]
                        while path[-1] != src:
                            path.append(prev[path[-1]])
                        return list(reversed(path))
                    nxt.append(v)
            frontier = nxt
        return None


# ---------------------------------------------------------------------------
# unjoined-thread
# ---------------------------------------------------------------------------


class UnjoinedThreadRule(Rule):
    """`Thread(daemon=True)` without a registered stop/join path.

    daemon=True means "the interpreter may kill this mid-write at exit" --
    acceptable only for workers that also have an orderly shutdown. A
    daemon thread started in a function that never joins anything, inside a
    class with no stop/close/shutdown method that joins, is a worker nobody
    can ever wait out: tests leak it, teardown races it, and mtpusan's
    leaked-thread detector will fire at runtime. Give the owner a stop path
    that joins, or suppress with the justification for a process-lifetime
    daemon."""

    id = "unjoined-thread"
    title = "daemon thread started without a stop/join path"
    scope = ("minio_tpu/",)

    STOP_NAMES = {
        "stop", "close", "shutdown", "stop_all", "cancel", "join",
        "wait_all", "drain",
    }

    @staticmethod
    def _is_thread_ctor(call: ast.Call) -> bool:
        name = _call_name(call)
        return name == "Thread" or name.endswith(".Thread")

    @staticmethod
    def _daemon_true(call: ast.Call) -> bool:
        for kw in call.keywords:
            if (
                kw.arg == "daemon"
                and isinstance(kw.value, ast.Constant)
                and kw.value.value is True
            ):
                return True
        return False

    @staticmethod
    def _has_join(node) -> bool:
        for sub in ast.walk(node):
            if (
                isinstance(sub, ast.Call)
                and isinstance(sub.func, ast.Attribute)
                and sub.func.attr == "join"
            ):
                return True
        return False

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            fn_spans = [
                (n.lineno, n.end_lineno or n.lineno, n)
                for n in ast.walk(ctx.tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
            ]
            cls_spans = [
                (n.lineno, n.end_lineno or n.lineno, n)
                for n in ast.walk(ctx.tree)
                if isinstance(n, ast.ClassDef)
            ]
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.Call) or not self._is_thread_ctor(node):
                    continue
                if not self._daemon_true(node):
                    continue
                if self._joined_somewhere(node.lineno, fn_spans, cls_spans, ctx):
                    continue
                yield Finding(
                    self.id, ctx.relpath, node.lineno,
                    "Thread(daemon=True) started here but neither this "
                    "function nor any stop/close/shutdown method on the "
                    "owning class ever join()s -- register a join path, or "
                    "suppress with the process-lifetime justification",
                )

    def _joined_somewhere(self, lineno, fn_spans, cls_spans, ctx) -> bool:
        fn = self._innermost(fn_spans, lineno)
        if fn is not None and self._has_join(fn):
            return True
        cls = self._innermost(cls_spans, lineno)
        if cls is not None:
            for stmt in cls.body:
                if (
                    isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and stmt.name in self.STOP_NAMES
                    and self._has_join(stmt)
                ):
                    return True
            return False
        if fn is None:
            # Module-level start: any module-level join path counts.
            return self._has_join(ctx.tree)
        return False

    @staticmethod
    def _innermost(spans, lineno):
        best = None
        for lo, hi, node in spans:
            if lo <= lineno <= hi and (best is None or lo > best[0]):
                best = (lo, node)
        return best[1] if best else None


# ---------------------------------------------------------------------------
# cond-wait-loop
# ---------------------------------------------------------------------------


class CondWaitLoopRule(Rule):
    """`Condition.wait()` must sit inside a `while predicate:` loop.

    Spurious wakeups and stolen notifies are real: a bare `if pred: wait()`
    (or a naked wait) resumes with the predicate false and corrupts
    whatever the waiter does next. Re-check the predicate in a `while`
    loop, or use `wait_for(predicate)` which loops internally. Only names
    assigned a Condition are checked -- `Event.wait` is level-triggered
    and exempt."""

    id = "cond-wait-loop"
    title = "Condition.wait() outside a while-predicate loop"
    scope = ("minio_tpu/",)

    _COND_CTORS = {
        "threading.Condition", "Condition", "san_condition",
    }

    def _condition_names(self, ctx) -> set[str]:
        """Attr/var names bound to a Condition anywhere in the file."""
        names: set[str] = set()
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Assign):
                continue
            if not (
                isinstance(node.value, ast.Call)
                and _call_name(node.value) in self._COND_CTORS
            ):
                continue
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    names.add(tgt.id)
                elif isinstance(tgt, ast.Attribute):
                    names.add(tgt.attr)
        return names

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            conds = self._condition_names(ctx)
            if not conds:
                continue

            def scan(node, in_while: bool):
                if isinstance(node, ast.While):
                    for child in ast.iter_child_nodes(node):
                        yield from scan(child, True)
                    return
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                    # A nested def's body executes outside this loop.
                    for child in ast.iter_child_nodes(node):
                        yield from scan(child, False)
                    return
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "wait"
                    and not in_while
                ):
                    holder = node.func.value
                    hname = (
                        holder.attr if isinstance(holder, ast.Attribute)
                        else holder.id if isinstance(holder, ast.Name) else None
                    )
                    if hname in conds:
                        yield node
                for child in ast.iter_child_nodes(node):
                    yield from scan(child, in_while)

            for call in scan(ctx.tree, False):
                yield Finding(
                    self.id, ctx.relpath, call.lineno,
                    "Condition.wait() outside a `while predicate:` loop -- "
                    "spurious wakeups break this; loop on the predicate or "
                    "use wait_for()",
                )


# ---------------------------------------------------------------------------
# shared-publish
# ---------------------------------------------------------------------------


class SharedPublishRule(Rule):
    """Read-modify-write on shared state from a worker thread, outside any
    lock.

    Methods reachable from a `Thread(target=self.X)` run concurrently with
    request threads; `self.counter += 1` there is a lost-update race (the
    GIL makes single writes atomic, but += is load/add/store). Guard the
    update with a lock. Plain assignments and list.append are exempt --
    they are single atomic publishes under the GIL."""

    id = "shared-publish"
    title = "unlocked read-modify-write on shared state in a worker thread"
    scope = ("minio_tpu/",)

    @staticmethod
    def _worker_methods(cls: ast.ClassDef) -> set[str]:
        """Method names reachable from a Thread(target=self.X) started
        anywhere in the class, expanded transitively through self.Y()
        calls."""
        methods = {
            s.name: s for s in cls.body
            if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
        }
        roots: set[str] = set()
        for node in ast.walk(cls):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node)
            if not (name == "Thread" or name.endswith(".Thread")):
                continue
            for kw in node.keywords:
                if (
                    kw.arg == "target"
                    and isinstance(kw.value, ast.Attribute)
                    and isinstance(kw.value.value, ast.Name)
                    and kw.value.value.id == "self"
                    and kw.value.attr in methods
                ):
                    roots.add(kw.value.attr)
        # Transitive closure through self.method() calls.
        frontier = list(roots)
        while frontier:
            m = frontier.pop()
            for node in ast.walk(methods[m]):
                if (
                    isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and isinstance(node.func.value, ast.Name)
                    and node.func.value.id == "self"
                    and node.func.attr in methods
                    and node.func.attr not in roots
                ):
                    roots.add(node.func.attr)
                    frontier.append(node.func.attr)
        return roots

    @staticmethod
    def _is_lock_expr(expr: ast.AST) -> bool:
        name = ""
        if isinstance(expr, ast.Attribute):
            name = expr.attr
        elif isinstance(expr, ast.Name):
            name = expr.id
        elif isinstance(expr, ast.Subscript):
            return SharedPublishRule._is_lock_expr(expr.value)
        low = name.lower()
        return any(h in low for h in _LOCK_HINTS)

    @classmethod
    def _shared_target(cls, node: ast.AugAssign, globals_declared: set[str]):
        tgt = node.target
        if (
            isinstance(tgt, ast.Attribute)
            and isinstance(tgt.value, ast.Name)
            and tgt.value.id == "self"
        ):
            return f"self.{tgt.attr}"
        if (
            isinstance(tgt, ast.Subscript)
            and isinstance(tgt.value, ast.Attribute)
            and isinstance(tgt.value.value, ast.Name)
            and tgt.value.value.id == "self"
        ):
            return f"self.{tgt.value.attr}[...]"
        if isinstance(tgt, ast.Name) and tgt.id in globals_declared:
            return tgt.id
        return None

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for cls in ast.walk(ctx.tree):
                if not isinstance(cls, ast.ClassDef):
                    continue
                workers = self._worker_methods(cls)
                if not workers:
                    continue
                methods = {
                    s.name: s for s in cls.body
                    if isinstance(s, (ast.FunctionDef, ast.AsyncFunctionDef))
                }
                for name in sorted(workers):
                    yield from self._check_method(ctx, methods[name])

    def _check_method(self, ctx, fn):
        globals_declared: set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Global):
                globals_declared.update(node.names)

        def scan(node, locked: bool):
            if isinstance(node, (ast.With, ast.AsyncWith)):
                body_locked = locked or any(
                    self._is_lock_expr(i.context_expr) for i in node.items
                )
                for child in node.body:
                    yield from scan(child, body_locked)
                return
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                return
            if isinstance(node, ast.AugAssign) and not locked:
                what = self._shared_target(node, globals_declared)
                if what is not None:
                    yield (node, what)
            for child in ast.iter_child_nodes(node):
                yield from scan(child, locked)

        for stmt in fn.body:
            for node, what in scan(stmt, False):
                yield Finding(
                    self.id, ctx.relpath, node.lineno,
                    f"{what} read-modify-written in worker method "
                    f"{fn.name!r} outside any lock -- += is load/add/store, "
                    "concurrent updates lose increments; guard it",
                )


# ---------------------------------------------------------------------------
# unsynced-commit
# ---------------------------------------------------------------------------


class UnsyncedCommitRule(Rule):
    """Atomic rename-commit without a durability barrier in the same function.

    The crash-consistency plane (storage/local.py, MTPU_FSYNC) publishes
    every durable artifact the same way: write a staged tmp file, sync it,
    `os.replace`/`os.rename` into place, sync the parent directory. An
    `os.replace` in storage/ or object/ whose enclosing function never calls
    any sync primitive (os.fsync, os.fdatasync, the `_sync_*` helpers) is a
    commit that a crash can tear: the rename may hit disk before the data
    it publishes. Add the barrier (gated on the fsync mode where the path
    is hot), or suppress with the justification for a best-effort file
    (e.g. a rebuildable cache entry)."""

    id = "unsynced-commit"
    title = "rename/replace commit without a sync barrier in the same function"
    scope = ("minio_tpu/storage/", "minio_tpu/object/")

    _COMMIT_CALLS = {"os.replace", "os.rename", "os.renames"}
    # Names that merely *mention* sync without performing one.
    _NON_BARRIER = {"fsync_mode"}

    @classmethod
    def _is_barrier(cls, name: str) -> bool:
        last = name.rsplit(".", 1)[-1]
        if last in cls._NON_BARRIER:
            return False
        return "sync" in last.lower()

    @classmethod
    def _shallow(cls, node: ast.AST):
        """Pre-order walk that stays inside one function scope: nested defs
        get their own pass, so each commit is judged against the barriers
        of its innermost function only."""
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        for child in ast.iter_child_nodes(node):
            yield from cls._shallow(child)

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for fn in ast.walk(ctx.tree):
                if not isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                commits: list[ast.Call] = []
                barriered = False
                for stmt in fn.body:
                    for node in self._shallow(stmt):
                        if not isinstance(node, ast.Call):
                            continue
                        name = _call_name(node)
                        if name in self._COMMIT_CALLS:
                            commits.append(node)
                        elif self._is_barrier(name):
                            barriered = True
                if barriered:
                    continue
                for call in commits:
                    yield Finding(
                        self.id, ctx.relpath, call.lineno,
                        f"{_call_name(call)}(...) publishes a file but "
                        f"{fn.name!r} never calls a sync barrier -- a crash "
                        "can commit the rename before the data; sync the "
                        "staged file (and parent dir), or suppress with the "
                        "best-effort justification",
                    )


# ---------------------------------------------------------------------------
# hot-path-copy
# ---------------------------------------------------------------------------


class HotPathCopyRule(Rule):
    """Byte-copying constructs on the zero-copy data plane.

    PR 9 rebuilt the socket -> sigv4 -> erasure-stage -> shard-fanout
    pipeline around pooled buffers and memoryviews; a casual `bytes(view)`,
    `b"".join(parts)`, or `buf += chunk` quietly reintroduces an
    O(object size) copy that the copy ledger then reports as a regression.
    Sites that MUST materialize (header text being decoded, inline blobs
    outliving a pooled window, client-side test helpers, legacy whole-file
    bitrot algorithms) carry a justified
    `# mtpulint: disable=hot-path-copy -- why`."""

    id = "hot-path-copy"
    title = "byte-copying construct on the zero-copy data plane"
    scope = (
        "minio_tpu/api/streaming.py",
        "minio_tpu/object/erasure.py",
        "minio_tpu/object/memcache.py",
        "minio_tpu/storage/local.py",
    )

    @staticmethod
    def _bytesish(value: ast.AST | None) -> bool:
        """Is this initializer a byte accumulator? (b"..." literal, or a
        bytes()/bytearray() construction.)"""
        if isinstance(value, ast.Constant) and isinstance(value.value, bytes):
            return True
        return (
            isinstance(value, ast.Call)
            and isinstance(value.func, ast.Name)
            and value.func.id in ("bytes", "bytearray")
        )

    @classmethod
    def _shallow(cls, node: ast.AST):
        """Pre-order walk that does not descend into nested function scopes
        (each scope tracks its own accumulator names)."""
        yield node
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
            return
        for child in ast.iter_child_nodes(node):
            yield from cls._shallow(child)

    def _check_calls(self, ctx: FileContext):
        parents: dict[ast.AST, ast.AST] = {}
        for parent in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(parent):
                parents[child] = parent
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            func = node.func
            # b"".join(parts): materializes a contiguous copy of every part.
            if (
                isinstance(func, ast.Attribute)
                and func.attr == "join"
                and isinstance(func.value, ast.Constant)
                and isinstance(func.value.value, bytes)
            ):
                yield Finding(
                    self.id, ctx.relpath, node.lineno,
                    'b"".join(...) copies every part into one contiguous '
                    "buffer -- hand the pieces to a scatter write "
                    "(append_iov) or stream them",
                )
                continue
            # bytes(buffer): a full copy of whatever the buffer holds.
            if (
                isinstance(func, ast.Name)
                and func.id == "bytes"
                and node.args
                and not isinstance(node.args[0], ast.Constant)
            ):
                parent = parents.get(node)
                if isinstance(parent, ast.Attribute) and parent.attr == "decode":
                    continue  # small header text being decoded, not payload
                yield Finding(
                    self.id, ctx.relpath, node.lineno,
                    "bytes(...) copies the underlying buffer -- pass the "
                    "memoryview through, or justify the materialization",
                )

    def _check_augments(self, ctx: FileContext):
        scopes = [ctx.tree] + [
            n for n in ast.walk(ctx.tree)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
        ]
        for scope in scopes:
            body = scope.body if not isinstance(scope, ast.Module) else scope.body
            nodes = [n for stmt in body for n in self._shallow(stmt)]
            accumulators = {
                t.id
                for n in nodes
                if isinstance(n, ast.Assign) and self._bytesish(n.value)
                for t in n.targets
                if isinstance(t, ast.Name)
            }
            for n in nodes:
                if (
                    isinstance(n, ast.AugAssign)
                    and isinstance(n.op, ast.Add)
                    and isinstance(n.target, ast.Name)
                    and n.target.id in accumulators
                ):
                    yield Finding(
                        self.id, ctx.relpath, n.lineno,
                        f"{n.target.id!r} += concatenation re-copies the "
                        "accumulated payload -- collect views and scatter-"
                        "write, or stream through the pooled pipeline",
                    )

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            yield from self._check_calls(ctx)
            yield from self._check_augments(ctx)


# ---------------------------------------------------------------------------
# bufsan static half: buffer-lifetime dataflow over the zero-copy plane.
# The runtime complement lives in minio_tpu/control/bufsan.py (MTPU_BUFSAN=1);
# these rules prove the discipline about paths the sanitized replay never ran.
# ---------------------------------------------------------------------------

STORAGE_IFACE = "minio_tpu/storage/interface.py"

# Everywhere pooled buffers flow today, plus the control-plane probe that
# borrows the pool (selftest netperf) and utils/ itself.
BUFFER_PATHS = HOT_PATHS + (
    "minio_tpu/control/selftest.py",
    "minio_tpu/utils/",
)


def _is_poolish(expr: ast.AST) -> bool:
    """Does this expression look like a BufferPool? Matched by the naming
    convention the tree actually uses -- `pool`, `self._pool`,
    `window_pool()`, `shard_pool()`, `BufferPool(...)` -- so `lk.acquire()`
    (locks) and `sem.acquire()` (semaphores) never enter the dataflow."""
    if isinstance(expr, ast.Name):
        return "pool" in expr.id.lower()
    if isinstance(expr, ast.Attribute):
        return "pool" in expr.attr.lower()
    if isinstance(expr, ast.Call):
        last = _call_name(expr).rsplit(".", 1)[-1]
        return "pool" in last.lower() or last == "BufferPool"
    return False


# Both end the buffer's life: release() recycles the storage, discard()
# drops it (exception paths where a traceback may pin foreign views).
RELEASE_METHODS = ("release", "discard")


def _is_buffer_acquire(value: ast.AST | None) -> bool:
    """`<pool>.acquire(...)` or a `*acquire*buf*` helper call."""
    if not isinstance(value, ast.Call):
        return False
    func = value.func
    if isinstance(func, ast.Attribute) and func.attr == "acquire":
        return _is_poolish(func.value)
    if isinstance(func, ast.Name):
        low = func.id.lower()
        return "acquire" in low and "buf" in low
    return False


def _shallow_nodes(root: ast.AST):
    """Pre-order walk of a function body that does not descend into nested
    function scopes (each scope owns its own buffer lifecycle)."""
    for stmt in root.body:
        stack = [stmt]
        while stack:
            node = stack.pop()
            yield node
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                continue
            stack.extend(reversed(list(ast.iter_child_nodes(node))))


def _method_call(node: ast.AST, name: str, method: str) -> bool:
    """Is `node` the call `name.method(...)`?"""
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == method
        and isinstance(node.func.value, ast.Name)
        and node.func.value.id == name
    )


def _escaping_names(expr: ast.AST | None) -> set[str]:
    """Names whose VALUE escapes through `expr` (returned/stored as-is):
    direct names and names inside tuple/list/dict/set/conditional
    containers. Does NOT descend into calls -- `bytes(v)` / `len(v)`
    compute FROM the view, they do not leak it."""
    out: set[str] = set()
    if expr is None:
        return out
    if isinstance(expr, ast.Name):
        out.add(expr.id)
    elif isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
        for e in expr.elts:
            out |= _escaping_names(e)
    elif isinstance(expr, ast.Dict):
        for e in expr.values:
            out |= _escaping_names(e)
    elif isinstance(expr, ast.Starred):
        out |= _escaping_names(expr.value)
    elif isinstance(expr, ast.IfExp):
        out |= _escaping_names(expr.body) | _escaping_names(expr.orelse)
    elif isinstance(expr, ast.NamedExpr):
        out |= _escaping_names(expr.value)
    return out


class _BufferFlow:
    """Per-function buffer-lifetime facts shared by the three bufsan rules:
    which names were acquired from a pool, where they are released (and
    whether any release sits on an exception edge), which were retained,
    and which were handed off (bare argument to a call, returned, yielded,
    or stored into an attribute/container)."""

    CONTAINER_METHODS = {"append", "add", "put", "put_nowait", "appendleft"}

    def __init__(self, func: ast.AST):
        self.func = func
        self.acquired: dict[str, int] = {}          # name -> first acquire line
        self.releases: dict[str, list[ast.Call]] = {}
        self.protected: set[str] = set()            # release on an except/finally edge
        self.retained: set[str] = set()
        self.transferred: set[str] = set()
        self._collect()

    def _collect(self) -> None:
        nodes = list(_shallow_nodes(self.func))
        for node in nodes:
            if isinstance(node, ast.Assign) and _is_buffer_acquire(node.value):
                for t in node.targets:
                    if isinstance(t, ast.Name):
                        self.acquired.setdefault(t.id, node.lineno)
        if not self.acquired:
            return
        for node in nodes:
            if isinstance(node, ast.Call):
                self._note_call(node)
            elif isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
                for name in _escaping_names(node.value):
                    if name in self.acquired:
                        self.transferred.add(name)
            elif isinstance(node, ast.Assign):
                if any(
                    isinstance(t, (ast.Attribute, ast.Subscript))
                    for t in node.targets
                ):
                    for name in _escaping_names(node.value):
                        if name in self.acquired:
                            self.transferred.add(name)
        # Exception-edge coverage: a release reachable from an except
        # handler or finally body covers the raise paths of its try.
        for node in nodes:
            if not isinstance(node, ast.Try):
                continue
            edges = list(node.finalbody)
            for h in node.handlers:
                edges.extend(h.body)
            for stmt in edges:
                for sub in ast.walk(stmt):
                    for name in self.acquired:
                        if any(
                            _method_call(sub, name, m) for m in RELEASE_METHODS
                        ):
                            self.protected.add(name)

    def _note_call(self, node: ast.Call) -> None:
        func = node.func
        if isinstance(func, ast.Attribute) and isinstance(func.value, ast.Name):
            owner = func.value.id
            if owner in self.acquired:
                if func.attr in RELEASE_METHODS:
                    self.releases.setdefault(owner, []).append(node)
                    return
                if func.attr == "retain":
                    self.retained.add(owner)
                    return
                if func.attr == "view":
                    return  # view creation is not a handoff of the buffer
        # A tracked buffer passed as a bare argument is an ownership
        # transfer: `_stream_windows(data, pool, pb, filled)`,
        # `_Window(view, pb)`, `bufs.add(pb)` all hand the release
        # obligation to the callee.
        for arg in list(node.args) + [kw.value for kw in node.keywords]:
            if isinstance(arg, ast.Name) and arg.id in self.acquired:
                self.transferred.add(arg.id)


def _iter_functions(ctx: FileContext):
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class ReleaseOnAllPathsRule(Rule):
    """Every pooled-buffer acquire() must reach release() on every path.

    The pool's pigeonhole (outstanding == 0 after every request) only holds
    when each `pb = pool.acquire()` either releases on the exception edges
    too -- a release inside an `except`/`finally` -- or hands the buffer
    off (bare argument to a call, returned, yielded, stored) to an owner
    that takes over the obligation. A straight-line release with neither is
    one raise away from leaking the window forever."""

    id = "release-on-all-paths"
    title = "pooled buffer acquire() without release on every path"
    scope = BUFFER_PATHS

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for func in _iter_functions(ctx):
                flow = _BufferFlow(func)
                for name, lineno in flow.acquired.items():
                    if name in flow.retained or name in flow.transferred:
                        continue
                    if not flow.releases.get(name):
                        yield Finding(
                            self.id, ctx.relpath, lineno,
                            f"{name!r} is acquired from a pool but never "
                            "released or handed off in this function -- "
                            "the window leaks and outstanding never drains",
                        )
                    elif name not in flow.protected:
                        yield Finding(
                            self.id, ctx.relpath, lineno,
                            f"{name!r} is only released on the straight-line "
                            "path -- a raise between acquire() and release() "
                            "leaks the window; release in a finally/except "
                            "or hand the buffer off",
                        )


class DoubleReleaseRule(Rule):
    """release() twice on the same pooled buffer.

    The second release corrupts whoever re-acquired the storage (or raises
    under the pool's refcount guard, torching an unrelated request). Two
    shapes: back-to-back unconditional releases in one statement list, and
    a try-body release repeated unguarded in the finally (the correct
    pattern rebinds `pb = None` after the handoff and guards the finally
    with `if pb is not None`)."""

    id = "double-release"
    title = "pooled buffer released twice on one path"
    scope = BUFFER_PATHS

    def _sequential(self, flow: _BufferFlow):
        """Two top-level `name.release()` statements in one body list with
        no rebind/retain between them."""
        for node in [flow.func, *_shallow_nodes(flow.func)]:
            for field in ("body", "orelse", "finalbody"):
                body = getattr(node, field, None)
                if not isinstance(body, list):
                    continue
                seen: set[str] = set()
                for stmt in body:
                    if isinstance(stmt, ast.Assign):
                        for t in stmt.targets:
                            if isinstance(t, ast.Name):
                                seen.discard(t.id)
                        continue
                    if not isinstance(stmt, ast.Expr):
                        continue
                    call = stmt.value
                    for name in flow.acquired:
                        if _method_call(call, name, "retain"):
                            seen.discard(name)
                        elif any(
                            _method_call(call, name, m) for m in RELEASE_METHODS
                        ):
                            if name in seen:
                                yield name, stmt.lineno
                            seen.add(name)

    def _try_finally(self, flow: _BufferFlow):
        """Unconditional release in a try body + unguarded release at the
        top of its finally: both run on the success path."""
        for node in _shallow_nodes(flow.func):
            if not isinstance(node, ast.Try) or not node.finalbody:
                continue
            for name in flow.acquired:
                in_try = any(
                    isinstance(stmt, ast.Expr)
                    and any(
                        _method_call(stmt.value, name, m)
                        for m in RELEASE_METHODS
                    )
                    for stmt in node.body
                )
                rebound = any(
                    isinstance(stmt, ast.Assign)
                    and any(
                        isinstance(t, ast.Name) and t.id == name
                        for t in stmt.targets
                    )
                    for stmt in node.body
                )
                if not in_try or rebound:
                    continue
                for stmt in node.finalbody:
                    if isinstance(stmt, ast.Expr) and any(
                        _method_call(stmt.value, name, m)
                        for m in RELEASE_METHODS
                    ):
                        yield name, stmt.lineno

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for func in _iter_functions(ctx):
                flow = _BufferFlow(func)
                if not flow.acquired:
                    continue
                seen_lines: set[tuple[str, int]] = set()
                for name, lineno in self._sequential(flow):
                    seen_lines.add((name, lineno))
                    yield Finding(
                        self.id, ctx.relpath, lineno,
                        f"{name!r} released twice on the same path -- the "
                        "second release corrupts the refcount of whoever "
                        "re-acquired the storage",
                    )
                for name, lineno in self._try_finally(flow):
                    if (name, lineno) in seen_lines:
                        continue
                    yield Finding(
                        self.id, ctx.relpath, lineno,
                        f"{name!r} released in the try body AND unguarded in "
                        "its finally -- rebind to None after the handoff and "
                        "guard the finally with `if {0} is not None`".format(name),
                    )


class ViewEscapeRule(Rule):
    """A memoryview over a pooled buffer escaping its owner's scope.

    bufpool's contract: views must not outlive the buffer's last release.
    A view that is returned/yielded, stored on `self` or in a container,
    shipped to a thread/lane submit, or captured by a closure survives
    past the release that recycles the storage underneath it -- the holder
    then silently reads ANOTHER request's bytes. Legitimate long-lived
    views ride a `retain()`ed buffer (the _Window pattern: view and buffer
    handed off together)."""

    id = "view-escape"
    title = "pooled-buffer view escapes without a retain()"
    scope = BUFFER_PATHS

    SUBMITISH = ("submit", "Thread", "start_new_thread", "run_in_executor")

    def _is_view_of(self, node: ast.AST, flow: _BufferFlow) -> str | None:
        """Owner name when `node` is `<buf>.view(...)` or
        `memoryview(<buf>.data)` over a tracked buffer."""
        if not isinstance(node, ast.Call):
            return None
        func = node.func
        if (
            isinstance(func, ast.Attribute)
            and func.attr == "view"
            and isinstance(func.value, ast.Name)
            and func.value.id in flow.acquired
        ):
            return func.value.id
        if (
            isinstance(func, ast.Name)
            and func.id == "memoryview"
            and node.args
            and isinstance(node.args[0], ast.Attribute)
            and node.args[0].attr == "data"
            and isinstance(node.args[0].value, ast.Name)
            and node.args[0].value.id in flow.acquired
        ):
            return node.args[0].value.id
        return None

    def check(self, project: ProjectContext):
        for ctx in project.iter_files(*self.scope):
            for func in _iter_functions(ctx):
                flow = _BufferFlow(func)
                if not flow.acquired:
                    continue
                # vname -> owning buffer name, for named view bindings.
                views: dict[str, str] = {}
                for node in _shallow_nodes(func):
                    if isinstance(node, ast.Assign):
                        owner = self._is_view_of(node.value, flow)
                        if owner is not None:
                            for t in node.targets:
                                if isinstance(t, ast.Name):
                                    views[t.id] = owner

                def owner_of(expr: ast.AST) -> str | None:
                    direct = self._is_view_of(expr, flow)
                    if direct is not None:
                        return direct
                    if isinstance(expr, ast.Name):
                        return views.get(expr.id)
                    return None

                def escapees(expr: ast.AST | None):
                    direct = self._is_view_of(expr, flow) if expr is not None else None
                    if direct is not None:
                        yield direct, expr
                    for name in _escaping_names(expr):
                        if name in views:
                            yield views[name], expr

                findings: dict[tuple[int, str], str] = {}

                def note(owner: str, node: ast.AST, how: str) -> None:
                    if owner in flow.retained:
                        return
                    findings.setdefault((node.lineno, owner), how)

                for node in _shallow_nodes(func):
                    if isinstance(node, (ast.Return, ast.Yield, ast.YieldFrom)):
                        for owner, val in escapees(getattr(node, "value", None)):
                            note(owner, node, "returned/yielded")
                    elif isinstance(node, ast.Assign):
                        if any(
                            isinstance(t, (ast.Attribute, ast.Subscript))
                            for t in node.targets
                        ):
                            for owner, val in escapees(node.value):
                                note(owner, node, "stored outside the scope")
                    elif isinstance(node, ast.Call):
                        callee = _call_name(node)
                        last = callee.rsplit(".", 1)[-1]
                        args = list(node.args) + [kw.value for kw in node.keywords]
                        if last in _BufferFlow.CONTAINER_METHODS:
                            for a in args:
                                o = owner_of(a)
                                if o is not None:
                                    note(o, node, "appended to a container")
                        elif any(s in last for s in self.SUBMITISH):
                            for a in args:
                                for sub in ast.walk(a):
                                    o = owner_of(sub)
                                    if o is not None:
                                        note(o, node, "passed to a thread/lane submit")
                    elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef, ast.Lambda)):
                        # Closure capture: the nested scope outlives this one.
                        inner = (
                            node.body if isinstance(node.body, list) else [node.body]
                        )
                        for stmt in inner:
                            for sub in ast.walk(stmt if isinstance(stmt, ast.AST) else node):
                                if isinstance(sub, ast.Name) and sub.id in views:
                                    note(views[sub.id], node, "captured by a closure")
                for (lineno, owner), how in sorted(findings.items()):
                    yield Finding(
                        self.id, ctx.relpath, lineno,
                        f"view over pooled buffer {owner!r} {how} without a "
                        f"retain() -- when {owner!r} is released the storage "
                        "recycles and the view reads another request's "
                        "bytes; retain() the buffer for the view's lifetime "
                        "(and release with it), or copy the bytes out",
                    )


class InterfaceConformanceRule(Rule):
    """StorageAPI wrappers must forward the FULL storage interface.

    MeteredDrive / FaultyDisk / HealthGatedDrive sit in every drive stack;
    a wrapper that pins an `inner` drive but neither defines `__getattr__`
    nor implements every StorageAPI method silently drops whatever the
    interface grew since the wrapper was written (`read_file_into`,
    `append_iov`) -- callers fall back to slow paths or AttributeError at
    runtime. The interface roster is read from storage/interface.py, so the
    rule tracks StorageAPI growth automatically."""

    id = "interface-conformance"
    title = "StorageAPI wrapper missing interface methods"
    scope = ("minio_tpu/storage/", "minio_tpu/chaos/")

    @staticmethod
    def _iface_methods(project: ProjectContext) -> set[str]:
        ctx = project.get(STORAGE_IFACE)
        if ctx is None:
            return set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.ClassDef) and node.name == "StorageAPI":
                return {
                    n.name
                    for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                    and not n.name.startswith("_")
                }
        return set()

    @staticmethod
    def _wraps_inner(cls: ast.ClassDef) -> bool:
        """Does __init__ pin an `inner` drive? Both idioms count:
        `self.inner = inner` and `self.__dict__["inner"] = inner` (the
        __setattr__-forwarding form the real wrappers use)."""
        for node in cls.body:
            if not isinstance(node, ast.FunctionDef) or node.name != "__init__":
                continue
            for sub in ast.walk(node):
                if not isinstance(sub, ast.Assign):
                    continue
                for t in sub.targets:
                    if isinstance(t, ast.Attribute) and t.attr == "inner":
                        return True
                    if (
                        isinstance(t, ast.Subscript)
                        and isinstance(t.value, ast.Attribute)
                        and t.value.attr == "__dict__"
                        and _str_const(t.slice) == "inner"
                    ):
                        return True
        return False

    def check(self, project: ProjectContext):
        methods = self._iface_methods(project)
        if not methods:
            return
        for ctx in project.iter_files(*self.scope):
            if ctx.relpath == STORAGE_IFACE:
                continue
            for node in ast.walk(ctx.tree):
                if not isinstance(node, ast.ClassDef) or not self._wraps_inner(node):
                    continue
                defined = {
                    n.name
                    for n in node.body
                    if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))
                }
                if "__getattr__" in defined:
                    continue
                for missing in sorted(methods - defined):
                    yield Finding(
                        self.id, ctx.relpath, node.lineno,
                        f"wrapper {node.name!r} neither defines __getattr__ "
                        f"nor forwards StorageAPI.{missing} -- the drive "
                        "stack silently loses the method",
                    )


ALL_RULES: list[Rule] = [
    SwallowedExceptRule(),
    RawTransportRule(),
    DeadlineRebindRule(),
    LockBlockingIORule(),
    ResourceLeakRule(),
    StageKeyRule(),
    MetricsRenderedRule(),
    TypedErrorsRule(),
    UnlockedGlobalRule(),
    LockOrderRule(),
    UnjoinedThreadRule(),
    CondWaitLoopRule(),
    SharedPublishRule(),
    UnsyncedCommitRule(),
    HotPathCopyRule(),
    ReleaseOnAllPathsRule(),
    DoubleReleaseRule(),
    ViewEscapeRule(),
    InterfaceConformanceRule(),
]

# deadline_lint.py's historical surface: the two rules that together are the
# old regex lint, runnable standalone by the shim and chaos_check.
DEADLINE_RULES: list[Rule] = [
    RawTransportRule(),
    DeadlineRebindRule(),
]

"""The engine rule set: every architectural invariant as one
declarative rule.

Four of these re-express the ad-hoc chokepoint guards that used to be
standalone regex tests (rpc/exchange/spool/mesh); the rest are the
concurrency-discipline rules the threaded engine grew to need. Each
rule carries its own allowlist-honesty check where applicable: if the
exempted implementation file stops matching the policed idiom, the
rule reports itself vacuous instead of silently passing forever.

Regex rules scan raw text (docstrings included — prose must not spell
the policed idiom with a literal call paren); AST rules skip strings
by construction."""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Tuple

from presto_tpu.analysis.framework import (
    PKG_ROOT, Finding, Package, Rule, SourceFile, honesty_finding,
    regex_findings, register,
)

# =====================================================================
# 1. rpc-chokepoint — protocol/transport.py is the only place that
#    opens an outbound HTTP connection (urlopen OR http.client dials)
# =====================================================================

_URLOPEN_DIRECT = re.compile(r"urllib\s*\.\s*request\s*\.\s*urlopen")
_URLOPEN_IMPORT = re.compile(
    r"from\s+urllib\s*\.\s*request\s+import\s+[^\n]*\burlopen\b")
#: dialing http.client directly (the pooled transport's own idiom)
#: bypasses the pool, retry classification, breakers, fault injection
#: AND the header providers that sign internal requests
_HTTPCONN_DIRECT = re.compile(
    r"http\s*\.\s*client\s*\.\s*HTTPS?Connection\s*\(")
_HTTPCONN_IMPORT = re.compile(
    r"from\s+http\s*\.\s*client\s+import\s+[^\n]*"
    r"\bHTTPS?Connection\b")

_TRANSPORT = "presto_tpu/protocol/transport.py"


class RpcChokepointRule(Rule):
    name = "rpc-chokepoint"
    description = (
        "every HTTP request rides protocol/transport.HttpClient so "
        "retry policies, error classification, circuit breakers, "
        "keep-alive pooling, request signing and fault injection "
        "apply uniformly; a raw urlopen or http.client dial anywhere "
        "else opts that call site out of all of it")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg,
            (_URLOPEN_DIRECT, _URLOPEN_IMPORT,
             _HTTPCONN_DIRECT, _HTTPCONN_IMPORT),
            "raw HTTP dial outside protocol/transport.py — route this "
            "through transport.HttpClient",
            allowed=(_TRANSPORT,))
        # honesty: the allowlisted file must still contain the policed
        # dial idiom (today the pooled HTTPConnection transport; the
        # urlopen form also counts so the check spans both eras)
        out.extend(honesty_finding(
            self, pkg, _TRANSPORT,
            (_HTTPCONN_DIRECT, _URLOPEN_DIRECT),
            "the pooled-connection transport"))
        return out


register(RpcChokepointRule())

# =====================================================================
# 2. exchange-chokepoint — exchange.py/exchange_client.py are the only
#    consumers of /results/ page GETs
# =====================================================================

#: an f-string literal interpolating into a /results/ path = building a
#: results GET/DELETE url client-side (the server's route regexes use
#: groups, not interpolation, so they never match)
_RESULTS_URL = re.compile(r"""f["'][^"'\n]*/results/\{""")
_PAGESTREAM = re.compile(r"\bPageStream\s*\(")

_EXCHANGE_ALLOWED = ("presto_tpu/protocol/exchange.py",
                     "presto_tpu/protocol/exchange_client.py")


class ExchangeChokepointRule(Rule):
    name = "exchange-chokepoint"
    description = (
        "only protocol/exchange.py + exchange_client.py may consume "
        "/results/ page streams; any other consumer bypasses the "
        "bounded exchange buffer, truncation-before-ack validation "
        "and the spool fallback")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_RESULTS_URL, _PAGESTREAM),
            "page-protocol consumption outside protocol/exchange*.py — "
            "route through exchange.ExchangeClient/stream_pages",
            allowed=_EXCHANGE_ALLOWED)
        out.extend(honesty_finding(
            self, pkg, "presto_tpu/protocol/exchange_client.py",
            (_RESULTS_URL,), "results-url construction"))
        out.extend(honesty_finding(
            self, pkg, "presto_tpu/protocol/exchange.py",
            (_PAGESTREAM,), "PageStream construction"))
        return out


register(ExchangeChokepointRule())

# =====================================================================
# 3. spool-chokepoint — spool/ is the single task-output file writer
#    in the distributed-execution layers (server/, protocol/)
# =====================================================================

_WRITE_PATTERNS = (
    re.compile(r"""open\s*\([^)\n]*,\s*["'][wax]b?\+?["']"""),
    re.compile(r"tempfile\s*\.\s*(mkstemp|mkdtemp|NamedTemporaryFile|"
               r"TemporaryFile|TemporaryDirectory)"),
    re.compile(r"from\s+tempfile\s+import\b"),
    re.compile(r"os\s*\.\s*(open|mkstemp)\s*\("),
)


class SpoolChokepointRule(Rule):
    name = "spool-chokepoint"
    description = (
        "task output in server/ and protocol/ must go through "
        "presto_tpu/spool (TaskSpoolWriter/FrameFile) so atomic "
        "commit manifests, checksums and GC cover every byte; exec/ "
        "keeps its own node-local spill files and is out of scope")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, _WRITE_PATTERNS,
            "file-writing call site in a distributed-execution layer — "
            "task output must ride presto_tpu/spool",
            prefixes=("presto_tpu/server/", "presto_tpu/protocol/"))
        # honesty: the spool package must itself still match the write
        # idioms this rule polices
        spool = [f for f in pkg.walk("presto_tpu/spool/")]
        if spool and not any(
                p.search(f.text) for f in spool for p in _WRITE_PATTERNS):
            out.append(Finding(
                self.name, "presto_tpu/spool/files.py", 1,
                "presto_tpu/spool no longer matches the write patterns "
                "this rule scans for — update the rule's patterns"))
        return out


register(SpoolChokepointRule())

# =====================================================================
# 4. mesh-chokepoint — parallel/shuffle.py is the single ICI
#    collective call site
# =====================================================================

_COLLECTIVE_CALL = re.compile(
    r"\blax\s*\.\s*(all_to_all|all_gather)\s*\(")
_COLLECTIVE_IMPORT = re.compile(
    r"from\s+jax\s*\.\s*lax\s+import\s+[^\n]*\b(all_to_all|all_gather)\b")

_SHUFFLE = "presto_tpu/parallel/shuffle.py"


class MeshChokepointRule(Rule):
    name = "mesh-chokepoint"
    description = (
        "every cross-device exchange rides parallel/shuffle.py's "
        "page-level helpers (repartition_page/all_gather_page) — the "
        "packed same-dtype layout, overflow-retry counters and wire-"
        "byte metrics all live there")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_COLLECTIVE_CALL, _COLLECTIVE_IMPORT),
            "raw ICI collective outside parallel/shuffle.py — exchange "
            "pages via repartition_page/all_gather_page",
            allowed=(_SHUFFLE,))
        shuffle = pkg.get(_SHUFFLE)
        if shuffle is None:
            out.append(Finding(
                self.name, _SHUFFLE, 1,
                "allowlisted file is missing — the collective "
                "chokepoint moved? update the rule"))
        else:
            kinds = {m.group(1)
                     for m in _COLLECTIVE_CALL.finditer(shuffle.text)}
            if kinds != {"all_to_all", "all_gather"}:
                out.append(Finding(
                    self.name, _SHUFFLE, 1,
                    f"allowlist gone vacuous: shuffle.py calls "
                    f"{sorted(kinds) or 'no collectives'}, expected "
                    f"both all_to_all and all_gather — update the rule"))
        return out


register(MeshChokepointRule())

# =====================================================================
# 5. metric-name-grammar — every registered metric name is Prometheus-
#    valid and registered from exactly one call site
# =====================================================================

#: registration call with a literal first argument — matches the bare
#: helpers, aliased imports (_counter, _obs_gauge, ...) and registry
#: methods (REGISTRY.counter)
_METRIC_CALL = re.compile(
    r"\b[A-Za-z_.]*(?:counter|gauge|histogram)\s*\(\s*[\"']"
    r"([^\"']+)[\"']")

#: the registry module itself holds class definitions and docstring
#: examples, not registrations
_METRIC_EXCLUDED = ("presto_tpu/obs/metrics.py",)


class MetricNameRule(Rule):
    name = "metric-name-grammar"
    description = (
        "every metric name registered anywhere in the package must "
        "match the Prometheus grammar and appear at exactly one call "
        "site — an invalid name corrupts /v1/metrics at scrape time, "
        "a duplicate aliases two meanings onto one series")

    def run(self, pkg: Package) -> Iterable[Finding]:
        from presto_tpu.obs.metrics import METRIC_NAME_RE
        sites: Dict[str, List[Tuple[SourceFile, int]]] = {}
        for f in pkg.walk("presto_tpu/"):
            if f.relpath in _METRIC_EXCLUDED:
                continue
            for m in _METRIC_CALL.finditer(f.text):
                sites.setdefault(m.group(1), []).append(
                    (f, f.line_at(m.start())))
        out: List[Finding] = []
        for mname, where in sorted(sites.items()):
            if not METRIC_NAME_RE.match(mname):
                for f, line in where:
                    out.append(self.finding(
                        f, line,
                        f"invalid Prometheus metric name {mname!r}"))
            if len(where) > 1:
                locs = ", ".join(f"{f.relpath}:{ln}" for f, ln in where)
                f, line = where[1]
                out.append(self.finding(
                    f, line,
                    f"metric {mname!r} registered from {len(where)} "
                    f"call sites ({locs}) — move it to one module-"
                    f"level registration"))
        return out


register(MetricNameRule())

# =====================================================================
# 6. thread-discipline — every spawned thread is attributable
# =====================================================================

#: the one sanctioned spawn helper (names presto-tpu-<role>-<purpose>)
_THREADS_HELPER = "presto_tpu/utils/threads.py"


def _is_thread_ctor(call: ast.Call) -> bool:
    fn = call.func
    if isinstance(fn, ast.Attribute) and fn.attr == "Thread" \
            and isinstance(fn.value, ast.Name) \
            and fn.value.id == "threading":
        return True
    return isinstance(fn, ast.Name) and fn.id == "Thread"


class ThreadDisciplineRule(Rule):
    name = "thread-discipline"
    description = (
        "every threading.Thread must be constructed with both name= "
        "and daemon= (or spawned via utils/threads.spawn, which names "
        "it presto-tpu-<role>-<purpose>) so stuck-thread dumps are "
        "attributable and shutdown behavior is uniform")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for f in pkg.walk("presto_tpu/"):
            if f.relpath == _THREADS_HELPER or f.tree is None:
                continue
            for node in ast.walk(f.tree):
                if not (isinstance(node, ast.Call)
                        and _is_thread_ctor(node)):
                    continue
                kw = {k.arg for k in node.keywords}
                missing = [k for k in ("name", "daemon") if k not in kw]
                if missing:
                    out.append(self.finding(
                        f, node.lineno,
                        f"thread spawned without {'/'.join(missing)} — "
                        f"use presto_tpu.utils.threads.spawn (names it "
                        f"presto-tpu-<role>-<purpose>) or pass both"))
        return out


register(ThreadDisciplineRule())

# =====================================================================
# 7. no-blocking-under-lock — no sleeps / transport calls / thread
#    joins lexically inside a `with <lock>:` body
# =====================================================================

#: a with-item whose terminal name segment looks like a mutex or
#: condition variable
_LOCKISH = re.compile(
    r"(?i)(?:^|_)(?:lock|mutex|cond|condition)$|lock$|^state_change$")

#: method names that issue a network RPC (the transport chokepoint's
#: public surface + the announcer's one-shot)
_RPC_METHODS = {"request", "get_json", "post", "urlopen",
                "announce_once"}


def _terminal_name(expr: ast.AST) -> Optional[str]:
    if isinstance(expr, ast.Name):
        return expr.id
    if isinstance(expr, ast.Attribute):
        return expr.attr
    return None


def _lockish(expr: ast.AST) -> bool:
    n = _terminal_name(expr)
    return n is not None and bool(_LOCKISH.search(n))


def _is_thread_join(call: ast.Call) -> bool:
    """`x.join()` / `x.join(5)` / `x.join(timeout=...)` — a string
    join always takes a non-numeric positional iterable, so those
    shapes are thread (or process) joins."""
    if not (isinstance(call.func, ast.Attribute)
            and call.func.attr == "join"):
        return False
    if call.keywords:
        return all(k.arg == "timeout" for k in call.keywords) \
            and not call.args
    if not call.args:
        return True
    return len(call.args) == 1 \
        and isinstance(call.args[0], ast.Constant) \
        and isinstance(call.args[0].value, (int, float))


def _blocking_reason(call: ast.Call,
                     lock_expr: ast.AST) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Attribute):
        if fn.attr == "sleep" and isinstance(fn.value, ast.Name) \
                and fn.value.id == "time":
            return "time.sleep under a lock"
        if fn.attr in _RPC_METHODS:
            return f".{fn.attr}() RPC under a lock"
        if fn.attr == "wait" \
                and ast.dump(fn.value) != ast.dump(lock_expr):
            return (".wait() on a different object than the held "
                    "lock (a condition wait only releases its own "
                    "lock)")
    if _is_thread_join(call):
        return ".join() under a lock"
    return None


class _UnderLockVisitor(ast.NodeVisitor):
    """Walk a with-body without descending into nested function or
    lambda bodies — those run later, not under the lock."""

    def __init__(self, rule: Rule, f: SourceFile, lock_expr: ast.AST,
                 out: List[Finding]):
        self.rule, self.f = rule, f
        self.lock_expr, self.out = lock_expr, out

    def visit_FunctionDef(self, node):   # noqa: N802 — ast API
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Call(self, node):          # noqa: N802 — ast API
        reason = _blocking_reason(node, self.lock_expr)
        if reason is not None:
            self.out.append(self.rule.finding(
                self.f, node.lineno,
                f"{reason} — hoist it out of the `with "
                f"{_terminal_name(self.lock_expr)}:` body"))
        self.generic_visit(node)


class NoBlockingUnderLockRule(Rule):
    name = "no-blocking-under-lock"
    description = (
        "no time.sleep, transport RPC, thread join, or foreign .wait "
        "lexically inside a `with <lock>:` body — a blocked holder "
        "stalls every other thread contending the lock (the exchange "
        "fetchers and breaker paths are exactly where this bites)")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for f in pkg.walk("presto_tpu/"):
            if f.tree is None:
                continue
            for node in ast.walk(f.tree):
                if not isinstance(node, (ast.With, ast.AsyncWith)):
                    continue
                for item in node.items:
                    if not _lockish(item.context_expr):
                        continue
                    v = _UnderLockVisitor(self, f, item.context_expr,
                                          out)
                    for stmt in node.body:
                        v.visit(stmt)
        return out


register(NoBlockingUnderLockRule())

# =====================================================================
# 8. lock-leak — bare .acquire() without with/try-finally
# =====================================================================

#: receivers the leak rule covers: locks, conditions, semaphores
_ACQUIRABLE = re.compile(
    r"(?i)(?:^|_)(?:lock|mutex|cond|condition|sem|semaphore|permits?)s?$"
    r"|lock$")


def _release_targets(try_node: ast.Try) -> List[str]:
    out = []
    for stmt in try_node.finalbody:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "release":
                out.append(ast.dump(node.func.value))
    return out


def _trailing_acquires(stmt: ast.stmt) -> List[ast.Call]:
    """Acquire calls a following try/finally can cover: a bare
    acquire expression statement, or — the guarded-acquire idiom —
    an acquire as the LAST statement of an if/else branch whose
    matching release in the try's finally carries the same guard."""
    if isinstance(stmt, ast.Expr) \
            and isinstance(stmt.value, ast.Call) \
            and isinstance(stmt.value.func, ast.Attribute) \
            and stmt.value.func.attr == "acquire":
        return [stmt.value]
    if isinstance(stmt, ast.If):
        out = []
        for branch in (stmt.body, stmt.orelse):
            if branch:
                out.extend(_trailing_acquires(branch[-1]))
        return out
    return []


class LockLeakRule(Rule):
    name = "lock-leak"
    description = (
        "a bare lock/semaphore .acquire() must be immediately followed "
        "by try/finally that releases the same object (or use `with`) "
        "— any exception between acquire and release leaks the lock "
        "and wedges every future contender")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for f in pkg.walk("presto_tpu/"):
            if f.tree is None:
                continue
            safe: set = set()
            # pass 1: expression-statement acquire immediately followed
            # by a try whose finally releases the same receiver
            for node in ast.walk(f.tree):
                for body in (getattr(node, "body", None),
                             getattr(node, "orelse", None),
                             getattr(node, "finalbody", None)):
                    if not isinstance(body, list):
                        continue
                    for i, stmt in enumerate(body):
                        for call in _trailing_acquires(stmt):
                            if i + 1 < len(body) \
                                    and isinstance(body[i + 1], ast.Try) \
                                    and ast.dump(call.func.value) in \
                                    _release_targets(body[i + 1]):
                                safe.add(id(call))
            # pass 2: flag every uncovered acquire on a lock-like
            # receiver
            for node in ast.walk(f.tree):
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and node.func.attr == "acquire"):
                    continue
                rname = _terminal_name(node.func.value)
                if rname is None or not _ACQUIRABLE.search(rname):
                    continue
                if id(node) not in safe:
                    out.append(self.finding(
                        f, node.lineno,
                        f"bare {rname}.acquire() without an immediate "
                        f"try/finally release — use `with {rname}:` or "
                        f"follow with try/finally"))
        return out


register(LockLeakRule())

# =====================================================================
# 9. no-jax-in-control-plane — server/, protocol/, spool/, obs/ stay
#    importable and fast on device-less nodes
# =====================================================================

_CONTROL_PLANE = ("presto_tpu/server/", "presto_tpu/protocol/",
                  "presto_tpu/spool/", "presto_tpu/obs/",
                  "presto_tpu/net/")


def _module_level_stmts(tree: ast.Module) -> Iterable[ast.stmt]:
    """Top-level statements, descending into module-level if/try
    blocks (conditional imports) but never into defs/classes."""
    stack = list(tree.body)
    while stack:
        stmt = stack.pop()
        yield stmt
        if isinstance(stmt, (ast.If, ast.Try)):
            for body in (stmt.body, stmt.orelse,
                         getattr(stmt, "finalbody", []),
                         *[h.body for h in
                           getattr(stmt, "handlers", [])]):
                stack.extend(body)


class NoJaxInControlPlaneRule(Rule):
    name = "no-jax-in-control-plane"
    description = (
        "server/, protocol/, spool/ and obs/ must not import jax at "
        "module level — the coordinator and the wire protocol must "
        "import fast on device-less nodes; the device path may "
        "lazy-import inside the function that needs it")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for prefix in _CONTROL_PLANE:
            for f in pkg.walk(prefix):
                if f.tree is None:
                    continue
                for stmt in _module_level_stmts(f.tree):
                    mods: List[str] = []
                    if isinstance(stmt, ast.Import):
                        mods = [a.name for a in stmt.names]
                    elif isinstance(stmt, ast.ImportFrom):
                        mods = [stmt.module or ""]
                    for mod in mods:
                        if mod == "jax" or mod.startswith("jax."):
                            out.append(self.finding(
                                f, stmt.lineno,
                                f"module-level `import {mod}` in the "
                                f"control plane — lazy-import inside "
                                f"the device-path function instead"))
        return out


register(NoJaxInControlPlaneRule())

# =====================================================================
# 10. no-spawn-in-request-handler — HTTP handler bodies never spawn
#     execution threads; all statement execution goes through the
#     admission dispatcher's bounded pool
# =====================================================================

#: `handle` is the App-contract router (net/aio_server.py shells); the
#: do_* names are the http.server handler surface test doubles still
#: use
_HANDLER_METHODS = ("do_GET", "do_POST", "do_DELETE", "do_PUT",
                    "do_HEAD", "handle")


def _is_spawn_call(call: ast.Call) -> bool:
    fn = call.func
    if isinstance(fn, ast.Name) and fn.id == "spawn":
        return True
    return isinstance(fn, ast.Attribute) and fn.attr == "spawn"


class _HandlerBodyVisitor(ast.NodeVisitor):
    """Collect spawn()/Thread() calls in the LEXICAL body of a handler
    method — nested function definitions are someone else's body (a
    closure handed to the dispatcher is exactly the sanctioned
    pattern)."""

    def __init__(self):
        self.calls: List[ast.Call] = []

    def visit_FunctionDef(self, node):      # noqa: N802 — ast API
        pass                                # do not descend

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Call(self, node):             # noqa: N802 — ast API
        if _is_spawn_call(node) or _is_thread_ctor(node):
            self.calls.append(node)
        self.generic_visit(node)


class NoSpawnInRequestHandlerRule(Rule):
    name = "no-spawn-in-request-handler"
    description = (
        "HTTP request handlers (do_GET/do_POST/do_DELETE/...) must "
        "not call threads.spawn or construct Thread objects — "
        "per-request thread creation is unbounded under load; route "
        "execution through the admission dispatcher's bounded pool")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for f in pkg.walk("presto_tpu/"):
            if f.tree is None:
                continue
            for node in ast.walk(f.tree):
                if not (isinstance(node, ast.FunctionDef)
                        and node.name in _HANDLER_METHODS):
                    continue
                v = _HandlerBodyVisitor()
                for stmt in node.body:
                    v.visit(stmt)
                for call in v.calls:
                    out.append(self.finding(
                        f, call.lineno,
                        f"thread spawned inside {node.name} — accept "
                        f"cheaply and hand execution to the admission "
                        f"dispatcher pool instead"))
        return out


register(NoSpawnInRequestHandlerRule())

# =====================================================================
# 10b. no-blocking-in-event-loop — async def bodies never block the
#      loop thread (sleep via asyncio, blocking work via run_blocking)
# =====================================================================


def _loop_blocking_reason(call: ast.Call) -> Optional[str]:
    """Why `call` would stall the event loop, or None. One blocked
    coroutine freezes EVERY parked long-poll on the server."""
    fn = call.func
    if isinstance(fn, ast.Attribute):
        if fn.attr == "sleep" and isinstance(fn.value, ast.Name) \
                and fn.value.id == "time":
            return "time.sleep on the event loop — await " \
                   "asyncio.sleep instead"
        if fn.attr in _RPC_METHODS:
            return (f".{fn.attr}() blocking RPC on the event loop — "
                    f"dispatch it through server.run_blocking")
    if isinstance(fn, ast.Name) and fn.id == "urlopen":
        return "urlopen on the event loop — dispatch it through " \
               "server.run_blocking"
    if _is_thread_join(call):
        return ".join() on the event loop — a thread join parks the " \
               "loop and every coroutine on it"
    return None


class _AsyncBodyVisitor(ast.NodeVisitor):
    """Walk an async def's LEXICAL body without descending into nested
    defs/lambdas — an inline sync helper handed to run_blocking runs on
    the executor, not the loop."""

    def __init__(self, rule: Rule, f: SourceFile, out: List[Finding]):
        self.rule, self.f, self.out = rule, f, out

    def visit_FunctionDef(self, node):   # noqa: N802 — ast API
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Call(self, node):          # noqa: N802 — ast API
        reason = _loop_blocking_reason(node)
        if reason is not None:
            self.out.append(self.rule.finding(self.f, node.lineno,
                                              reason))
        self.generic_visit(node)


class NoBlockingInEventLoopRule(Rule):
    name = "no-blocking-in-event-loop"
    description = (
        "async def bodies must not call time.sleep, a blocking "
        "transport RPC/urlopen, or a thread join — the event loop "
        "serves every connection on one thread, so one blocking call "
        "stalls all of them; sleep with asyncio.sleep and push "
        "blocking work through the server's bounded executor")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for f in pkg.walk("presto_tpu/"):
            if f.tree is None:
                continue
            for node in ast.walk(f.tree):
                if not isinstance(node, ast.AsyncFunctionDef):
                    continue
                v = _AsyncBodyVisitor(self, f, out)
                for stmt in node.body:
                    v.visit(stmt)
        return out


register(NoBlockingInEventLoopRule())

# =====================================================================
# 11. no-planner-in-data-plane — ops/ and parallel/ never consult the
#     planner's estimator or rule engine
# =====================================================================

_DATA_PLANE = ("presto_tpu/ops/", "presto_tpu/parallel/")

#: planner modules the data plane must not reach (cost/history
#: estimation and the iterative rule engine); plan.nodes stays legal —
#: kernels legitimately pattern-match on plan node types
_PLANNER_MODULES = ("presto_tpu.plan.stats", "presto_tpu.plan.iterative")


class NoPlannerInDataPlaneRule(Rule):
    name = "no-planner-in-data-plane"
    description = (
        "ops/ and parallel/ (the per-batch device hot paths) must not "
        "import plan.stats or plan.iterative at ANY level — cardinality "
        "estimation and rule rewriting are planning-time work; an "
        "estimator call inside a kernel re-prices the plan once per "
        "batch and drags HBO state into traced code")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out: List[Finding] = []
        for prefix in _DATA_PLANE:
            for f in pkg.walk(prefix):
                if f.tree is None:
                    continue
                for node in ast.walk(f.tree):
                    mods: List[str] = []
                    if isinstance(node, ast.Import):
                        mods = [a.name for a in node.names]
                    elif isinstance(node, ast.ImportFrom):
                        mod = node.module or ""
                        mods = [mod]
                        # `from presto_tpu.plan import stats` names the
                        # module in the alias list, not in `module`
                        if mod == "presto_tpu.plan":
                            mods += [f"{mod}.{a.name}"
                                     for a in node.names]
                    for mod in mods:
                        if any(mod == p or mod.startswith(p + ".")
                               for p in _PLANNER_MODULES):
                            out.append(self.finding(
                                f, node.lineno,
                                f"planner import `{mod}` in the data "
                                f"plane — estimate at planning time and "
                                f"pass the decision in as plain data"))
        return out


register(NoPlannerInDataPlaneRule())

# =====================================================================
# 12. membership-chokepoint — cluster.py's _membership() is the only
#     mutator of the dead/drained sets
# =====================================================================

#: a direct mutation of the coordinator's dead/drained membership sets;
#: every such write must sit inside TpuCluster._membership() under
#: _membership_lock (the chokepoint lines there carry suppressions) so
#: a failure-detector sweep can never interleave with a scheduler's
#: placement snapshot and observe half-applied membership
_MEMBERSHIP_MUTATION = re.compile(
    r"\.\s*(?:dead|drained)\s*\.\s*"
    r"(?:add|discard|remove|clear|update|pop)\s*\(")

_CLUSTER = "presto_tpu/server/cluster.py"


class MembershipChokepointRule(Rule):
    name = "membership-chokepoint"
    description = (
        "every mutation of the coordinator's dead/drained worker sets "
        "flows through TpuCluster._membership() under _membership_lock "
        "— a bare .dead.add / .drained.discard elsewhere races the "
        "failure detector against placement snapshots (the "
        "check_workers membership-mutation race)")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_MEMBERSHIP_MUTATION,),
            "dead/drained set mutated outside the _membership() "
            "chokepoint — pass dead_add/dead_remove/drained_add/"
            "drained_remove to _membership() instead",
            prefixes=("presto_tpu/server/",))
        # honesty: the chokepoint itself must still mutate the sets via
        # the idiom this rule polices (its lines carry suppressions)
        out.extend(honesty_finding(
            self, pkg, _CLUSTER, (_MEMBERSHIP_MUTATION,),
            "the membership chokepoint"))
        return out


register(MembershipChokepointRule())

# =====================================================================
# 12b. journal-chokepoint — QueryJournal is the only coordinator
#      query-state persistence path
# =====================================================================

#: a bare JSONL-style append (json.dumps into .write, or a manual
#: line + "\n" write) — coordinator query state persisted outside the
#: QueryJournal would be invisible to crash recovery AND to peer
#: coordinators adopting queries from the shared journal
_JOURNAL_JSONL = re.compile(
    r"\.write\s*\(\s*(?:json\s*\.\s*dumps|[\w.]+\s*\+\s*[\"']\\n[\"'])")

_JOURNAL = "presto_tpu/server/journal.py"


class JournalChokepointRule(Rule):
    name = "journal-chokepoint"
    description = (
        "all coordinator query-state persistence in presto_tpu/server/ "
        "flows through QueryJournal — a bare JSONL write elsewhere "
        "creates a second durability log that crash recovery and "
        "multi-coordinator adoption never read (the HA split-brain "
        "hazard)")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_JOURNAL_JSONL,),
            "JSONL-style write outside QueryJournal — append through "
            "the journal (server/journal.py) so recovery and peer "
            "adoption see it",
            allowed=(_JOURNAL,),
            prefixes=("presto_tpu/server/",))
        # honesty: the journal itself must still persist via the idiom
        # this rule polices — an allowlist pointing at a file that no
        # longer writes JSONL is a stale exemption
        out.extend(honesty_finding(
            self, pkg, _JOURNAL, (_JOURNAL_JSONL,),
            "the query-journal chokepoint"))
        return out


register(JournalChokepointRule())

# =====================================================================
# 13. metric-docs-sync — the README metric catalog and the registered
#     metric set agree in both directions
# =====================================================================

#: the catalog section opener in README.md; entries follow as a bullet
#: list (blank lines allowed) until the first non-bullet paragraph
_CATALOG_HEADER = re.compile(r"^Metric catalog \(prefix `presto_tpu_`")

_BACKTICK_TOKEN = re.compile(r"`([^`\n]+)`")

#: a {a,b,c} alternation inside a catalog token (never token-final —
#: token-final braces are label annotations and are stripped first)
_ALTERNATION = re.compile(r"\{([A-Za-z0-9_]*(?:,[A-Za-z0-9_]*)+)\}")

_README = "README.md"


def _catalog_entries(text: str) -> Tuple[Optional[int],
                                         List[Tuple[str, int]]]:
    """Parse the README metric catalog: returns (header line or None,
    [(metric name, line)]). Token grammar: backticked, optional
    trailing ``{label,...}`` annotation (stripped), inner ``{a,b}``
    alternations expanded, ``presto_tpu_`` prefix implied."""
    lines = text.splitlines()
    header_at: Optional[int] = None
    out: List[Tuple[str, int]] = []
    for i, line in enumerate(lines, start=1):
        if header_at is None:
            if _CATALOG_HEADER.match(line.strip()):
                header_at = i
            continue
        stripped = line.strip()
        if stripped and not stripped.startswith(("-", "`")) \
                and not line.startswith(" "):
            break                          # first paragraph after list
        for m in _BACKTICK_TOKEN.finditer(line):
            tok = m.group(1)
            if " " in tok or "/" in tok or "." in tok:
                continue                   # prose in backticks, not a name
            # token-final braces are a label annotation UNLESS the name
            # is incomplete without them (`result_cache_{bytes,entries}`
            # — the char before `{` is `_`, so it's an alternation)
            tok = re.sub(r"(?<=[A-Za-z0-9])\{[A-Za-z0-9_,=]*\}$", "",
                         tok)
            variants = [tok]
            while any("{" in v for v in variants):
                nxt: List[str] = []
                for v in variants:
                    am = _ALTERNATION.search(v)
                    if am is None:
                        if "{" in v:       # unbalanced/unknown braces
                            break
                        nxt.append(v)
                        continue
                    for opt in am.group(1).split(","):
                        nxt.append(v[:am.start()] + opt + v[am.end():])
                variants = nxt
            for v in variants:
                if not v:
                    continue
                if not v.startswith("presto_tpu_"):
                    v = "presto_tpu_" + v
                out.append((v, i))
    return header_at, out


class MetricDocsSyncRule(Rule):
    name = "metric-docs-sync"
    description = (
        "every metric name registered in code must appear in the "
        "README metric catalog and every catalog entry must still be "
        "registered — an undocumented series is invisible to the ops "
        "runbook, a stale entry sends an operator hunting for a "
        "series that no longer exists")

    def _readme_text(self, pkg: Package) -> Optional[str]:
        f = pkg.get(_README)
        if f is not None:
            return f.text
        path = PKG_ROOT.parent / _README
        try:
            return path.read_text()
        except OSError:
            return None

    def run(self, pkg: Package) -> Iterable[Finding]:
        registered: Dict[str, Tuple[SourceFile, int]] = {}
        for f in pkg.walk("presto_tpu/"):
            if f.relpath in _METRIC_EXCLUDED:
                continue
            for m in _METRIC_CALL.finditer(f.text):
                registered.setdefault(
                    m.group(1), (f, f.line_at(m.start())))
        text = self._readme_text(pkg)
        if text is None:
            return [Finding(self.name, _README, 1,
                            "README.md is missing — the metric catalog "
                            "has nowhere to live")]
        header_at, entries = _catalog_entries(text)
        if header_at is None:
            return [Finding(
                self.name, _README, 1,
                "README.md has no 'Metric catalog (prefix "
                "`presto_tpu_`)' section — restore it (or update this "
                "rule's header pattern)")]
        documented: Dict[str, int] = {}
        for mname, line in entries:
            documented.setdefault(mname, line)
        out: List[Finding] = []
        for mname in sorted(set(registered) - set(documented)):
            f, line = registered[mname]
            out.append(self.finding(
                f, line,
                f"metric {mname!r} is registered here but absent from "
                f"the README metric catalog — document it"))
        for mname in sorted(set(documented) - set(registered)):
            out.append(Finding(
                self.name, _README, documented[mname],
                f"README catalog documents {mname!r} but nothing "
                f"registers it — stale docs entry, delete or fix it"))
        return out


register(MetricDocsSyncRule())

# =====================================================================
# 14. mv-cache-chokepoint — mv/manager.py is the only caller of the
#     fragment cache's pin/unpin API
# =====================================================================

#: a pin/unpin call site — pinning exempts an entry from LRU
#: eviction, so a stray pin anywhere else is a silent budget leak and a
#: stray unpin can evict live materialized-view state from under a read
_CACHE_PIN = re.compile(r"\.\s*pin\s*\(")
_CACHE_UNPIN = re.compile(r"\.\s*unpin\s*\(")

_MV_MANAGER = "presto_tpu/mv/manager.py"


class MvCacheChokepointRule(Rule):
    name = "mv-cache-chokepoint"
    description = (
        "only presto_tpu/mv/ may pin/unpin fragment-cache entries — "
        "materialized-view state is the sole legitimate pinned "
        "resident, and routing every pin through the mv manager keeps "
        "the pinned-bytes accounting, journalled lifecycle and "
        "refresh-then-release ordering in one place; a pin elsewhere "
        "leaks budget past eviction forever, an unpin elsewhere can "
        "drop live view state mid-read")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_CACHE_PIN, _CACHE_UNPIN),
            "fragment-cache pin/unpin outside presto_tpu/mv/ — route "
            "materialized state through mv.MaterializedViewManager",
            allowed=(_MV_MANAGER,))
        out.extend(honesty_finding(
            self, pkg, _MV_MANAGER, (_CACHE_PIN, _CACHE_UNPIN),
            "mv state pinning"))
        return out


register(MvCacheChokepointRule())

# =====================================================================
# 15. spill-chokepoint — exec/spill.py is the only spill-file writer
#     in the execution layers (exec/, ops/)
# =====================================================================

_SPILL = "presto_tpu/exec/spill.py"


class SpillChokepointRule(Rule):
    name = "spill-chokepoint"
    description = (
        "exec/ and ops/ open spill files for write only through "
        "exec/spill.FileSpiller — one spill write path means one "
        "partial-file cleanup story under ENOSPC, one SpillError "
        "classification, one stray-dir GC prefix and one "
        "spilled-bytes metric; a bare file write inside an operator "
        "would leak torn run files past every one of them")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, _WRITE_PATTERNS,
            "file-writing call site in the execution layer — spill "
            "through exec/spill.FileSpiller",
            allowed=(_SPILL,),
            prefixes=("presto_tpu/exec/", "presto_tpu/ops/"))
        # honesty: the spiller itself must still match the write
        # idioms this rule polices
        out.extend(honesty_finding(
            self, pkg, _SPILL, _WRITE_PATTERNS,
            "the spill-file writer"))
        return out


register(SpillChokepointRule())

# =====================================================================
# 16. alert-rule-metric-exists — every metric a declarative alert rule
#     references is a registered metric, and obs/tsdb.py is the only
#     telemetry-history writer
# =====================================================================

#: a `metric="..."` literal inside an AlertRule construction — the
#: name an alert evaluates against the telemetry history
_ALERT_METRIC_REF = re.compile(r"\bmetric\s*=\s*[\"']([^\"']+)[\"']")
#: the TimeSeriesStore write chokepoint: the scraper is the ONLY
#: legitimate history writer — a second writer could plant points the
#: alert engine fires on without any scrape having observed them
_TSDB_WRITE = re.compile(r"\.\s*write_points\s*\(")

_ALERTS_FILE = "presto_tpu/obs/alerts.py"
_TSDB_FILE = "presto_tpu/obs/tsdb.py"


class AlertRuleMetricExistsRule(Rule):
    name = "alert-rule-metric-exists"
    description = (
        "every metric name referenced by an alert rule in "
        "obs/alerts.py must be registered somewhere in the package — "
        "a rule over a metric nobody registers silently never fires, "
        "which is worse than no rule at all; and obs/tsdb.py is the "
        "only caller of the TSDB write chokepoint, so alert "
        "evaluations can only ever see history the scraper wrote")

    def run(self, pkg: Package) -> Iterable[Finding]:
        registered = set()
        for f in pkg.walk("presto_tpu/"):
            if f.relpath in _METRIC_EXCLUDED:
                continue
            for m in _METRIC_CALL.finditer(f.text):
                registered.add(m.group(1))
        out: List[Finding] = []
        alerts = pkg.get(_ALERTS_FILE)
        if alerts is None:
            out.append(Finding(
                self.name, _ALERTS_FILE, 1,
                "the alert-rule module is missing — the catalog "
                "moved? update the rule"))
        else:
            refs = list(_ALERT_METRIC_REF.finditer(alerts.text))
            for m in refs:
                if m.group(1) not in registered:
                    out.append(Finding(
                        self.name, _ALERTS_FILE,
                        alerts.line_at(m.start()),
                        f"alert rule references metric "
                        f"{m.group(1)!r}, which no call site "
                        f"registers — the rule can never fire"))
            # honesty: the catalog must still spell rule metrics with
            # the metric="..." idiom this rule scans for
            if not refs:
                out.append(Finding(
                    self.name, _ALERTS_FILE, 1,
                    "no metric=\"...\" references found in the alert "
                    "catalog — the rule idiom changed? update the "
                    "rule's pattern"))
        out.extend(regex_findings(
            self, pkg, (_TSDB_WRITE,),
            "telemetry-history write outside obs/tsdb.py — all "
            "history enters through the scraper's write chokepoint",
            allowed=(_TSDB_FILE,)))
        out.extend(honesty_finding(
            self, pkg, _TSDB_FILE, (_TSDB_WRITE,),
            "the telemetry-history write chokepoint"))
        return out


register(AlertRuleMetricExistsRule())

# =====================================================================
# 19. ici-exchange-chokepoint — server/mesh_tier.py is the only place
#     that decides ICI-vs-HTTP exchange routing
# =====================================================================

#: the ICI exchange descriptor rides the task session properties under
#: this key; reading or writing it anywhere else in the control plane
#: is a routing decision made outside the sanctioned policy
_ICI_DESCRIPTOR = re.compile(r"[\"']x_ici_exchange[\"']")

_MESH_TIER = "presto_tpu/server/mesh_tier.py"


class IciExchangeChokepointRule(Rule):
    name = "ici-exchange-chokepoint"
    description = (
        "only server/mesh_tier.py may decide whether an exchange "
        "rides ICI collectives or HTTP page pulls — a bare mesh-"
        "descriptor check elsewhere in server/ or protocol/ forks the "
        "routing policy, and a fork that disagrees with the "
        "chokepoint silently double-accounts or drops the fallback "
        "contract (non-co-located/degraded stages must keep HTTP "
        "byte-for-byte)")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_ICI_DESCRIPTOR,),
            "bare ICI exchange-descriptor access outside "
            "server/mesh_tier.py — route the decision through "
            "stamp_ici_descriptor/ici_descriptor",
            allowed=(_MESH_TIER,),
            prefixes=("presto_tpu/server/", "presto_tpu/protocol/"))
        out.extend(honesty_finding(
            self, pkg, _MESH_TIER, (_ICI_DESCRIPTOR,),
            "the ICI exchange routing chokepoint"))
        return out


register(IciExchangeChokepointRule())

# =====================================================================
# 20. no-page-copy-in-data-plane — page bytes cross protocol/ and
#     spool/ as views; copies live only at serde.py's sanctioned sites
# =====================================================================

#: flattening an array lane into an owned bytes object — the idiom the
#: PageBuffer scatter-gather writer exists to remove
_TOBYTES = re.compile(r"\.tobytes\(")
#: materializing a decoded lane that frombuffer already aliased
_FROMBUFFER_COPY = re.compile(r"frombuffer\([^)]*\)\s*\.copy\(")

_SERDE = "presto_tpu/protocol/serde.py"


class NoPageCopyInDataPlaneRule(Rule):
    name = "no-page-copy-in-data-plane"
    description = (
        "the columnar data plane (protocol/, spool/) moves page bytes "
        "as buffer views: encode scatter-gathers lanes into one "
        "pre-sized frame, decode returns read-only frombuffer aliases, "
        "spool reads slice one contiguous read — a stray .tobytes() "
        "or frombuffer(...).copy() reintroduces a per-lane copy that "
        "the zero-copy contract (and its GB/s bench lane) exists to "
        "keep out; sanctioned copies live in protocol/serde.py only, "
        "counted by page_copy_fallback_total")

    def run(self, pkg: Package) -> Iterable[Finding]:
        out = regex_findings(
            self, pkg, (_TOBYTES, _FROMBUFFER_COPY),
            "page-lane copy in the data plane — emit through the "
            "PageBuffer writer / return a frombuffer view (sanctioned "
            "copy sites live in protocol/serde.py and count "
            "page_copy_fallback_total)",
            allowed=(_SERDE,),
            prefixes=("presto_tpu/protocol/", "presto_tpu/spool/"))
        # honesty: serde.py must still contain a policed idiom (the
        # small-piece coalesce in _PageWriter.put_array); if the last
        # sanctioned copy disappears, the allowlist is vacuous
        out.extend(honesty_finding(
            self, pkg, _SERDE, (_TOBYTES,),
            "the sanctioned data-plane copy sites"))
        return out


register(NoPageCopyInDataPlaneRule())

"""dpxlint — AST lint pass enforcing this repo's distributed-runtime
invariants.

PRs 2-4 accumulated repo-wide rules that were only enforced at runtime
(or by review): collectives stay on the control thread, env reads go
through the typed registry, blocking calls carry deadlines, typed errors
carry attribution, threads are named. Each is now a machine-checked rule
(catalog in docs/analysis.md):

* **DPX001** — a collective / ``_barrier`` call is statically reachable
  from a function handed to ``threading.Thread(target=...)``. The
  ckpt/serve control-thread invariant: an IO/engine thread that issues a
  collective deadlocks the world (the PR-4 bug class that
  ``CheckpointManager._barrier`` now guards at runtime — this rule
  catches it before it runs).
* **DPX002** — raw ``os.environ`` / ``os.getenv`` access outside the
  typed registry (``runtime/env.py``). ``tests/`` are exempt (tests
  legitimately stage raw environments).
* **DPX003** — a blocking call (``.join()``, ``.wait()``, ``.get()``,
  ``.accept()``, ``.recv()``, ``.communicate()``, ``subprocess.run``)
  without a timeout/deadline argument, inside the package. The
  PR-2 invariant: nothing in the runtime may block unboundedly.
  Scoped to ``distributed_pytorch_tpu/`` (the native deadline layer
  ``runtime/native.py`` is the enforcement point itself and is exempt).
* **DPX004** — ``raise`` of a typed comm/ckpt/serve error with zero
  attribution kwargs. The typed hierarchies exist so supervisors act on
  structure (which rank, which op, which step); an unattributed raise
  is a plain RuntimeError wearing a type.
* **DPX005** — ``threading.Thread(...)`` without ``name=``. Every
  thread must carry a named owner: the ckpt phase trace, the watchdog,
  and crash dumps all attribute by thread name.
* **DPX006** — ``jax.jit`` of a step/decode builder (innermost
  enclosing function name contains ``step`` or ``decode``) inside the
  package without ``donate_argnums``. The front-door invariant
  (docs/front_door.md): train-step and decode hot loops donate their
  state buffers — a copying build silently doubles peak memory every
  step. Inline-waivable like the others (eval steps and grad-only
  jits legitimately don't own their inputs).
* **DPX007** — ``time.time()`` used for DURATION measurement (the
  ``t1 - t0`` pattern) inside the package. Wall clock steps under NTP,
  so a wall-clock difference is not a duration — ``time.perf_counter``
  / ``perf_counter_ns`` (or ``time.monotonic`` for deadlines) is.
  Flags a subtraction whose operand is a direct ``time.time()`` call,
  a local name assigned from one, or an attribute assigned from one
  anywhere in the file. Legitimate WALL-CLOCK sites (cross-process
  staleness against a timestamp another process wrote) are
  inline-waived with a reason; ``obs/trace.py``'s single anchor read
  is not a subtraction and does not trigger.
* **DPX008** — ``append_event`` called with a literal event name
  outside the registered ``KNOWN_EVENTS`` vocabulary
  (``obs/export.py``). The strict validators (``dpxtrace check`` /
  ``dpxmon check``) flag unknown names in the LOG; this rule catches
  the typo at the write site, before a soak run ships a week of
  invisible events. ``tests/`` are exempt (they stage unknown names to
  test the validators). Register the name in ``KNOWN_EVENTS`` or waive
  a deliberately-foreign stream with a reason.

Suppression: append ``# dpxlint: disable=DPXnnn <reason>`` to the
offending line (or the line above); ``# dpxlint: disable-file=DPXnnn
<reason>`` within the first 10 lines exempts the whole file. A
committed baseline (``analysis/dpxlint_baseline.json``) holds the
accepted pre-existing findings — CI fails only on NEW ones. Baselines
match on (rule, path, normalized line text), not line numbers, so
unrelated edits don't churn them.
"""

from __future__ import annotations

import ast
import collections
import json
import os
import re
from dataclasses import dataclass
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from ..obs.export import KNOWN_EVENTS
from .schedule import FRONT_DOOR_SURFACE, NATIVE_OPS

RULES = ("DPX001", "DPX002", "DPX003", "DPX004", "DPX005", "DPX006",
         "DPX007", "DPX008")

#: DPX006: a jit call inside a function whose name matches this is a
#: step/decode-builder site and must carry ``donate_argnums``.
_STEP_BUILDER_RE = re.compile(r"step|decode", re.IGNORECASE)

#: Call names counted as collectives for DPX001 (the static half shares
#: its vocabulary with the schedule verifier).
COLLECTIVE_NAMES: Set[str] = (set(FRONT_DOOR_SURFACE) | set(NATIVE_OPS)
                              | {"all_gather", "wait_for_everyone",
                                 "_barrier"})

#: DPX003: attribute calls that block forever when called with no
#: timeout-ish argument.
BLOCKING_ATTRS = ("join", "wait", "get", "accept", "recv", "recvfrom",
                  "communicate")
_TIMEOUT_KWARGS = ("timeout", "deadline", "deadline_ms", "timeout_ms",
                   "block")

#: DPX004: typed error class → attribution kwargs, at least one required.
TYPED_ERRORS: Dict[str, Tuple[str, ...]] = {
    "CommError": ("op", "rank", "peer"),
    "CommPeerDied": ("op", "rank", "peer"),
    "CommTimeout": ("op", "rank", "peer", "deadline_ms"),
    "CommCorrupt": ("op", "rank", "peer"),
    "CommRetryExhausted": ("op", "rank", "peer", "attempts"),
    "CollectiveMismatch": ("op", "rank", "peer", "seq"),
    "CkptError": ("step", "rank", "shard"),
    "CkptCorrupt": ("step", "rank", "shard"),
    "CkptIncomplete": ("step", "rank", "shard"),
    "CkptShapeMismatch": ("step", "rank", "shard"),
    "ServeError": ("request_id", "iteration"),
    "AdmissionRejected": ("request_id", "iteration", "reason"),
    "RequestDeadlineExceeded": ("request_id", "iteration", "deadline_ms",
                                "stage"),
    "EngineStopped": ("request_id", "iteration"),
    "PagePoolExhausted": ("request_id", "iteration", "needed",
                          "free_pages"),
    "HandoffError": ("request_id", "iteration", "engine"),
    "PrefillEngineDied": ("request_id", "iteration", "engine"),
    "HandoffTimeout": ("request_id", "iteration", "engine",
                       "deadline_ms"),
    "HandoffCorrupt": ("request_id", "iteration", "engine", "page"),
    "ReplicaFailed": ("request_id", "iteration", "replica"),
    "SpecDecodeError": ("request_id", "iteration", "stage"),
    "WorkerFailure": ("rank", "exitcode", "op", "kind"),
}

# build/ and chiprun_out/ are git-ignored scratch: an unpacked copy of
# the tree there (the chip proof run) is not a second repo to lint
_EXCLUDED_DIRS = {".git", ".github", ".pytest_cache", "__pycache__",
                  ".claude", ".venv", "node_modules", "build",
                  "chiprun_out"}
_EXCLUDED_FILES = {"__graft_entry__.py"}  # harness shim, not repo code
_ENV_REGISTRY_FILE = os.path.join("distributed_pytorch_tpu", "runtime",
                                  "env.py")
_DEADLINE_LAYER_FILES = {
    os.path.join("distributed_pytorch_tpu", "runtime", "native.py"),
}
_PACKAGE_DIR = "distributed_pytorch_tpu"

# the rule list is the comma-separated DPXnnn prefix; everything after
# it is the (required-by-convention) free-text reason
_DISABLE_RE = re.compile(
    r"#\s*dpxlint:\s*disable=((?:DPX\d+)(?:\s*,\s*DPX\d+)*)")
_DISABLE_FILE_RE = re.compile(
    r"#\s*dpxlint:\s*disable-file=((?:DPX\d+)(?:\s*,\s*DPX\d+)*)")


@dataclass(frozen=True)
class Finding:
    rule: str
    path: str          # repo-relative, forward slashes
    line: int          # 1-based
    message: str
    line_text: str     # stripped source of the offending line

    def fingerprint(self) -> Tuple[str, str, str]:
        # line numbers churn with unrelated edits; (rule, file, text)
        # survives them
        return (self.rule, self.path, self.line_text)

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: {self.rule} {self.message}"


# ---------------------------------------------------------------------------
# per-file checker
# ---------------------------------------------------------------------------

def _rules_in(match: Optional[re.Match]) -> Set[str]:
    if not match:
        return set()
    return {tok.strip() for tok in match.group(1).split(",") if tok.strip()}


class _FileChecker:
    def __init__(self, path: str, rel: str, source: str):
        self.path = path
        self.rel = rel.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.findings: List[Finding] = []
        self.file_disabled: Set[str] = set()
        # disable-file markers may sit below a long module docstring, so
        # the whole file is scanned (the marker is explicit + greppable)
        for line in self.lines:
            self.file_disabled |= _rules_in(_DISABLE_FILE_RE.search(line))

    # -- helpers -----------------------------------------------------------

    def _suppressed(self, rule: str, line: int) -> bool:
        if rule in self.file_disabled:
            return True
        for n in (line, line - 1):
            if 1 <= n <= len(self.lines):
                if rule in _rules_in(_DISABLE_RE.search(self.lines[n - 1])):
                    return True
        return False

    def _emit(self, rule: str, node: ast.AST, message: str) -> None:
        line = getattr(node, "lineno", 1)
        if self._suppressed(rule, line):
            return
        text = (self.lines[line - 1].strip()
                if 1 <= line <= len(self.lines) else "")
        self.findings.append(Finding(rule=rule, path=self.rel, line=line,
                                     message=message, line_text=text))

    def _in_package(self) -> bool:
        return self.rel.startswith(_PACKAGE_DIR + "/")

    # -- run ---------------------------------------------------------------

    def run(self) -> List[Finding]:
        try:
            tree = ast.parse(self.source, filename=self.path)
        except SyntaxError as e:
            self.findings.append(Finding(
                rule="DPX000", path=self.rel, line=e.lineno or 1,
                message=f"syntax error: {e.msg}", line_text=""))
            return self.findings
        self._check_thread_collectives(tree)   # DPX001
        self._check_env_access(tree)           # DPX002
        self._check_blocking_calls(tree)       # DPX003
        self._check_typed_raises(tree)         # DPX004
        self._check_thread_names(tree)         # DPX005
        self._check_jit_donation(tree)         # DPX006
        self._check_wall_clock_durations(tree)  # DPX007
        self._check_event_vocabulary(tree)     # DPX008
        return self.findings

    # -- DPX001 ------------------------------------------------------------

    def _check_thread_collectives(self, tree: ast.Module) -> None:
        # every function/method defined anywhere in the module, by bare
        # name (collisions merged — a lint over one module can't do
        # better, and merged resolution only ever ADDS coverage)
        defs: Dict[str, List[ast.AST]] = collections.defaultdict(list)
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs[node.name].append(node)

        entries: List[Tuple[str, ast.AST]] = []
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) == "Thread"):
                continue
            for kw in node.keywords:
                if kw.arg != "target":
                    continue
                tgt = kw.value
                name = None
                if isinstance(tgt, ast.Name):
                    name = tgt.id
                elif (isinstance(tgt, ast.Attribute)
                        and isinstance(tgt.value, ast.Name)
                        and tgt.value.id == "self"):
                    name = tgt.attr
                if name and name in defs:
                    entries.append((name, node))

        for entry_name, thread_call in entries:
            seen: Set[str] = set()
            queue = [entry_name]
            while queue:
                fn = queue.pop()
                if fn in seen:
                    continue
                seen.add(fn)
                for fn_node in defs.get(fn, ()):
                    for sub in ast.walk(fn_node):
                        if not isinstance(sub, ast.Call):
                            continue
                        callee = _call_name(sub)
                        if callee in COLLECTIVE_NAMES:
                            self._emit(
                                "DPX001", sub,
                                f"collective {callee!r} reachable from "
                                f"thread target {entry_name!r} (line "
                                f"{thread_call.lineno}) — collectives "
                                "must stay on the control thread")
                        elif callee and callee in defs and callee != fn:
                            # nested defs of the callee are walked too —
                            # only recurse into same-module definitions
                            queue.append(callee)

    # -- DPX002 ------------------------------------------------------------

    def _check_env_access(self, tree: ast.Module) -> None:
        if self.rel == _ENV_REGISTRY_FILE.replace(os.sep, "/"):
            return
        if self.rel.startswith("tests/"):
            return  # tests stage raw environments deliberately
        # aliases matter: `import os as _os` and `from os import environ
        # [as e]` are the same raw access with a different spelling —
        # the registry's closedness holds only if every spelling is seen
        os_aliases: Set[str] = set()
        environ_aliases: Set[str] = set()
        getenv_aliases: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "os":
                        os_aliases.add(alias.asname or "os")
            elif isinstance(node, ast.ImportFrom) and node.module == "os":
                for alias in node.names:
                    if alias.name == "environ":
                        environ_aliases.add(alias.asname or "environ")
                    elif alias.name == "getenv":
                        getenv_aliases.add(alias.asname or "getenv")
        for node in ast.walk(tree):
            if (isinstance(node, ast.Attribute) and node.attr == "environ"
                    and isinstance(node.value, ast.Name)
                    and node.value.id in os_aliases):
                self._emit(
                    "DPX002", node,
                    "raw os.environ access — declare the variable in "
                    "runtime/env.py and use env.get/raw/set")
            elif (isinstance(node, ast.Name)
                    and node.id in environ_aliases):
                self._emit(
                    "DPX002", node,
                    "raw environ access (from os import environ) — use "
                    "the runtime/env.py registry")
            elif (isinstance(node, ast.Call)
                    and (_call_name(node) == "getenv"
                         or (isinstance(node.func, ast.Name)
                             and node.func.id in getenv_aliases))):
                self._emit(
                    "DPX002", node,
                    "raw os.getenv — use the runtime/env.py registry")

    # -- DPX003 ------------------------------------------------------------

    def _check_blocking_calls(self, tree: ast.Module) -> None:
        if not self._in_package():
            return  # the deadline invariant governs the runtime package
        if self.rel in {p.replace(os.sep, "/")
                        for p in _DEADLINE_LAYER_FILES}:
            return  # the deadline layer itself
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            fn = node.func
            if (isinstance(fn, ast.Attribute)
                    and fn.attr in BLOCKING_ATTRS
                    and not (isinstance(fn.value, ast.Name)
                             and fn.value.id == "self")
                    and not node.args
                    and not any(kw.arg in _TIMEOUT_KWARGS
                                for kw in node.keywords)):
                # zero-arg .get()/.wait()/.join()/... is the
                # block-forever form (dict.get(k) etc. carry args;
                # self.X() is an app-level method, not a primitive)
                self._emit(
                    "DPX003", node,
                    f".{fn.attr}() with no timeout — blocking calls in "
                    "the runtime must carry a deadline "
                    "(docs/failures.md)")
            elif (isinstance(fn, ast.Attribute) and fn.attr == "run"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id == "subprocess"
                    and not any(kw.arg == "timeout"
                                for kw in node.keywords)):
                self._emit(
                    "DPX003", node,
                    "subprocess.run without timeout= — a wedged child "
                    "must become an error, not a hang")

    # -- DPX004 ------------------------------------------------------------

    def _check_typed_raises(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Raise)
                    and isinstance(node.exc, ast.Call)):
                continue
            name = _call_name(node.exc)
            required = TYPED_ERRORS.get(name or "")
            if not required:
                continue
            kwargs = {kw.arg for kw in node.exc.keywords if kw.arg}
            if not kwargs & set(required):
                self._emit(
                    "DPX004", node,
                    f"raise {name} without attribution — pass at least "
                    f"one of {required} so supervisors can attribute "
                    "the failure")

    # -- DPX005 ------------------------------------------------------------

    def _check_thread_names(self, tree: ast.Module) -> None:
        for node in ast.walk(tree):
            if (isinstance(node, ast.Call)
                    and _call_name(node) == "Thread"
                    and not any(kw.arg == "name" for kw in node.keywords)):
                self._emit(
                    "DPX005", node,
                    "threading.Thread without name= — every thread "
                    "carries a named owner (phase traces, watchdog, "
                    "crash dumps attribute by thread name)")


    # -- DPX006 ------------------------------------------------------------

    def _check_jit_donation(self, tree: ast.Module) -> None:
        """``jit(...)`` without ``donate_argnums`` inside a step/decode
        builder — in any spelling: a direct call, a ``@jax.jit``
        decorator on a step/decode-named def, or ``partial(jax.jit,
        ...)``. Attribution is to the INNERMOST enclosing function def:
        helper closures named outside the step/decode vocabulary
        (samplers, admit buckets) are not builder sites."""
        if not self._in_package():
            return

        def is_jit_ref(node: ast.AST) -> bool:
            return ((isinstance(node, ast.Name) and node.id == "jit")
                    or (isinstance(node, ast.Attribute)
                        and node.attr == "jit"))

        def msg(owner: str, spelling: str) -> str:
            return (f"{spelling} in step/decode builder {owner!r} "
                    "without donate_argnums — the front door donates "
                    "step buffers (docs/front_door.md); pass "
                    "donate_argnums or waive with a reason")

        def check_decorators(fn: ast.AST) -> None:
            for dec in fn.decorator_list:
                if is_jit_ref(dec):
                    # bare @jax.jit can never donate
                    self._emit("DPX006", dec, msg(fn.name, "@jit"))
                elif (isinstance(dec, ast.Call)
                        and _call_name(dec) == "jit"
                        and not any(kw.arg == "donate_argnums"
                                    for kw in dec.keywords)):
                    self._emit("DPX006", dec, msg(fn.name, "@jit(...)"))

        # decorator expressions are judged ONCE, by check_decorators
        # (against the decorated def's own name) — never re-judged by
        # the generic call walk against the enclosing owner
        decorator_nodes = {
            id(d)
            for fn in ast.walk(tree)
            if isinstance(fn, (ast.FunctionDef, ast.AsyncFunctionDef))
            for dec in fn.decorator_list
            for d in ast.walk(dec)}

        def walk(node: ast.AST, owner: Optional[str]) -> None:
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    if _STEP_BUILDER_RE.search(child.name):
                        check_decorators(child)
                    walk(child, child.name)
                    continue
                if id(child) in decorator_nodes:
                    continue
                in_builder = (owner is not None
                              and _STEP_BUILDER_RE.search(owner))
                if (isinstance(child, ast.Call) and in_builder
                        and _call_name(child) == "jit"
                        and not any(kw.arg == "donate_argnums"
                                    for kw in child.keywords)):
                    self._emit("DPX006", child, msg(owner, "jax.jit"))
                elif (isinstance(child, ast.Call) and in_builder
                        and _call_name(child) == "partial"
                        and child.args and is_jit_ref(child.args[0])
                        and not any(kw.arg == "donate_argnums"
                                    for kw in child.keywords)):
                    self._emit("DPX006", child,
                               msg(owner, "partial(jax.jit, ...)"))
                walk(child, owner)

        walk(tree, None)


    # -- DPX007 ------------------------------------------------------------

    def _check_wall_clock_durations(self, tree: ast.Module) -> None:
        """``time.time()`` in a subtraction — duration math on the wall
        clock. Wall time steps (NTP) and a stepped clock turns a
        "duration" negative or wildly wrong; ``perf_counter`` exists
        for exactly this. Tracked taint: direct ``time.time()`` calls
        (any alias spelling), local names assigned from one (per
        function scope), and attributes assigned from one (module-wide
        — ``self.start_time = time.time()`` subtracted in another
        method is the classic offender)."""
        if not self._in_package():
            return

        # alias spellings: `import time as t` → t.time(); `from time
        # import time [as now]` → now()
        time_mod_aliases: Set[str] = set()
        time_fn_aliases: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name == "time":
                        time_mod_aliases.add(alias.asname or "time")
            elif (isinstance(node, ast.ImportFrom)
                    and node.module == "time"):
                for alias in node.names:
                    if alias.name == "time":
                        time_fn_aliases.add(alias.asname or "time")

        def is_wall_call(node: ast.AST) -> bool:
            if not isinstance(node, ast.Call):
                return False
            fn = node.func
            if (isinstance(fn, ast.Attribute) and fn.attr == "time"
                    and isinstance(fn.value, ast.Name)
                    and fn.value.id in time_mod_aliases):
                return True
            return (isinstance(fn, ast.Name)
                    and fn.id in time_fn_aliases)

        # module-wide attribute taint: self.X = time.time() anywhere
        tainted_attrs: Set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Assign) and is_wall_call(node.value):
                for tgt in node.targets:
                    if isinstance(tgt, ast.Attribute):
                        tainted_attrs.add(tgt.attr)

        def scope_walk(root: ast.AST, skip_defs: bool):
            """ast.walk, optionally not descending into nested function
            defs — the MODULE scope must not inherit a sibling
            function's local taint (a `start = time.time()` in one def
            must never flag another def's perf_counter `end - start`).
            Function scopes keep nested defs (closure taint only ADDS
            coverage; duplicates dedupe via `flagged`)."""
            stack = [root]
            while stack:
                node = stack.pop()
                if node is not root and skip_defs and isinstance(
                        node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    continue
                yield node
                stack.extend(ast.iter_child_nodes(node))

        def scope_names(fn_node: ast.AST, skip_defs: bool) -> Set[str]:
            names: Set[str] = set()
            for node in scope_walk(fn_node, skip_defs):
                if (isinstance(node, ast.Assign)
                        and is_wall_call(node.value)):
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            names.add(tgt.id)
                elif (isinstance(node, (ast.AnnAssign, ast.NamedExpr))
                        and node.value is not None
                        and is_wall_call(node.value)
                        and isinstance(node.target, ast.Name)):
                    names.add(node.target.id)
            return names

        flagged: Set[int] = set()   # node ids — scopes overlap (a def
        # is walked by its own scope AND enclosing ones); emit once

        def check_scope(fn_node: ast.AST, skip_defs: bool = False) -> None:
            tainted = scope_names(fn_node, skip_defs)

            def is_wall(node: ast.AST) -> bool:
                if is_wall_call(node):
                    return True
                if isinstance(node, ast.Name) and node.id in tainted:
                    return True
                return (isinstance(node, ast.Attribute)
                        and node.attr in tainted_attrs)

            for node in scope_walk(fn_node, skip_defs):
                if (isinstance(node, ast.BinOp)
                        and isinstance(node.op, ast.Sub)
                        and id(node) not in flagged
                        and (is_wall(node.left) or is_wall(node.right))):
                    flagged.add(id(node))
                    self._emit(
                        "DPX007", node,
                        "time.time() used for duration measurement "
                        "(t1 - t0) — wall clock steps under NTP; use "
                        "time.perf_counter/perf_counter_ns (or the "
                        "obs.trace wall anchor for monotone wall "
                        "stamps), or waive a legitimate cross-process "
                        "wall-clock comparison with a reason")

        # one scope per function def + the module top level; the module
        # pass skips function bodies entirely so one function's local
        # wall-clock name can never taint a sibling's duration math
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                check_scope(node)
        check_scope(tree, skip_defs=True)


    # -- DPX008 ------------------------------------------------------------

    def _check_event_vocabulary(self, tree: ast.Module) -> None:
        """``append_event("name", ...)`` with a literal name outside
        the ``KNOWN_EVENTS`` vocabulary (obs/export.py). Variable names
        are out of scope (``MetricsLogger.event`` forwards its caller's
        name — the caller's own literal is the checked site)."""
        if self.rel.startswith("tests/"):
            return  # tests stage unknown names to test the validators
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _call_name(node) == "append_event"
                    and node.args
                    and isinstance(node.args[0], ast.Constant)
                    and isinstance(node.args[0].value, str)):
                continue
            name = node.args[0].value
            if name not in KNOWN_EVENTS:
                self._emit(
                    "DPX008", node,
                    f"append_event({name!r}) is outside the registered "
                    f"KNOWN_EVENTS vocabulary (obs/export.py) — the "
                    f"strict log validators would flag every line it "
                    f"writes; register the name or waive with a reason")


def _call_name(call: ast.Call) -> Optional[str]:
    fn = call.func
    if isinstance(fn, ast.Name):
        return fn.id
    if isinstance(fn, ast.Attribute):
        return fn.attr
    return None


# ---------------------------------------------------------------------------
# output formats (shared by tools/dpxlint.py and tools/dpxverify.py)
# ---------------------------------------------------------------------------

FORMATS = ("text", "json", "github")


def _gh_escape(s: str) -> str:
    # the workflow-command property/message escaping GitHub documents
    return (s.replace("%", "%25").replace("\r", "%0D").replace("\n", "%0A"))


def format_findings(findings: Sequence["Finding"], fmt: str = "text") -> str:
    """Render findings as ``text`` (the classic path:line lines),
    ``json`` (machine-readable list of finding dicts), or ``github``
    (``::error`` workflow annotations that surface inline on PRs)."""
    if fmt == "json":
        return json.dumps(
            [{"rule": f.rule, "path": f.path, "line": f.line,
              "message": f.message, "line_text": f.line_text}
             for f in findings], indent=1, sort_keys=True)
    if fmt == "github":
        return "\n".join(
            f"::error file={f.path},line={f.line},"
            f"title={f.rule}::{_gh_escape(f.message)}" for f in findings)
    return "\n".join(str(f) for f in findings)


# ---------------------------------------------------------------------------
# repo walk + baseline
# ---------------------------------------------------------------------------

def repo_root() -> str:
    return os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))


def iter_py_files(root: str) -> Iterable[str]:
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames[:] = sorted(d for d in dirnames
                             if d not in _EXCLUDED_DIRS)
        for fname in sorted(filenames):
            if fname.endswith(".py") and fname not in _EXCLUDED_FILES:
                yield os.path.join(dirpath, fname)


def lint_paths(paths: Optional[Sequence[str]] = None,
               root: Optional[str] = None) -> List[Finding]:
    root = root or repo_root()
    files: List[str] = []
    if not paths:
        files = list(iter_py_files(root))
    else:
        for p in paths:
            p = p if os.path.isabs(p) else os.path.join(root, p)
            if os.path.isdir(p):
                files.extend(iter_py_files(p))
            else:
                files.append(p)
    out: List[Finding] = []
    for path in files:
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except (OSError, UnicodeDecodeError):
            continue
        rel = os.path.relpath(path, root)
        out.extend(_FileChecker(path, rel, source).run())
    out.sort(key=lambda f: (f.path, f.line, f.rule))
    return out


DEFAULT_BASELINE = os.path.join("distributed_pytorch_tpu", "analysis",
                                "dpxlint_baseline.json")


def load_baseline(path: str) -> collections.Counter:
    with open(path, encoding="utf-8") as f:
        entries = json.load(f)
    return collections.Counter(
        (e["rule"], e["path"], e["line_text"]) for e in entries)


def save_baseline(path: str, findings: Sequence[Finding]) -> None:
    entries = [{"rule": f.rule, "path": f.path, "line_text": f.line_text,
                "message": f.message} for f in findings]
    with open(path, "w", encoding="utf-8") as f:
        json.dump(entries, f, indent=1, sort_keys=True)
        f.write("\n")


def apply_baseline(findings: Sequence[Finding], baseline: collections.Counter
                   ) -> List[Finding]:
    """Findings not covered by the baseline (multiset subtraction: N
    accepted copies of a fingerprint absorb at most N occurrences)."""
    budget = collections.Counter(baseline)
    fresh: List[Finding] = []
    for f in findings:
        fp = f.fingerprint()
        if budget[fp] > 0:
            budget[fp] -= 1
        else:
            fresh.append(f)
    return fresh

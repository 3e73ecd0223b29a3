#!/usr/bin/env python
"""hvdlint — AST-based repo-invariant linter (docs/static-analysis.md).

Turns the conventions every PR used to re-verify by hand into standing
static analysis.  Rules are named and individually testable
(tests/test_hvdlint.py gives each a positive and negative fixture); the
default run checks the whole repo and exits nonzero on any violation:

  knob-registry          every HOROVOD_* env var referenced anywhere in
                         horovod_tpu/, scripts/, csrc/ or bench.py is in
                         the common/knobs.py registry (so hvd.init
                         parses/validates it) AND has a docs/knobs.md
                         row; `NAME_*` glob prose matches by prefix.
  metrics-documented     every REGISTRY-registered hvd_* metric family
                         has a docs/metrics.md row, and the Prometheus
                         exposition renders lint-clean (subsumes and
                         extends scripts/check_metrics_format.py).
  serve-determinism      no `random` usage, no time-dependent control
                         flow, no set-iteration in the serve scheduler /
                         engine / plan-stream lockstep path — the
                         determinism contract the journal redrive and
                         the fleet plan stream depend on.
  kvshard-determinism    the scope->shard map (runner/kvshard.py) is a
                         pure function of (scope, shard count): no RNG,
                         no wall-clock control flow, no set iteration,
                         no builtin hash() (PYTHONHASHSEED-dependent),
                         no environment reads — every rank, the router
                         and the driver must derive the SAME partition
                         (docs/control-plane.md).
  scenario-determinism   the scenario generators/replay
                         (horovod_tpu/scenario) are pure functions of
                         (spec, seed): the kvshard discipline applied
                         module-wide — no RNG, no builtin hash(), no
                         env reads, no wall-clock control flow, no set
                         iteration, no random/time/uuid imports — so
                         one spec yields byte-identical event streams
                         and SLO rows everywhere (docs/scenarios.md).
  serve-kv-retry         serve-worker KV legs go through the _kv_op
                         bounded-backoff wrapper, never raw
                         get_kv/put_kv/delete_kv/wait_kv (a transient
                         rendezvous outage must stall serving, not kill
                         it).
  unique-test-basenames  test and worker module basenames are unique
                         across tests/ and tests/integration/ (no
                         __init__.py there, so a duplicate basename
                         breaks pytest collection with an import-file
                         mismatch).
  signal-safety          csrc/postmortem.cc (fatal-signal handler
                         territory) calls only an async-signal-safe
                         allowlist — write/itoa-style output, atomics,
                         and the file's own helpers.

Usage:
  python scripts/hvdlint.py                 # all rules, whole repo
  python scripts/hvdlint.py --rule NAME     # one rule
  python scripts/hvdlint.py --list          # rule catalog

Escape hatch: a line whose trailing comment contains
`hvdlint: allow[<rule>]` is exempt from that rule — use it with a
justification comment, the suppression-file policy.
"""

from __future__ import annotations

import argparse
import ast
import importlib.util
import os
import re
import sys
from typing import Dict, List, NamedTuple, Optional, Sequence

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class Violation(NamedTuple):
    rule: str
    path: str      # repo-relative
    line: int
    message: str

    def render(self) -> str:
        return f"{self.rule}: {self.path}:{self.line}: {self.message}"


def _load_by_path(name: str, path: str):
    """File-path module load (the check_metrics_format probe pattern);
    registers in sys.modules so dataclasses etc. resolve."""
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _read(root: str, rel: str) -> str:
    with open(os.path.join(root, rel), encoding="utf-8") as f:
        return f.read()


def _allowed(line_text: str, rule: str) -> bool:
    return f"hvdlint: allow[{rule}]" in line_text


# ------------------------------------------------------------ knob-registry
# Strings that look like knobs but are not env vars; each entry needs a
# justification, the suppression-file policy (docs/static-analysis.md).
KNOWN_NON_KNOBS = {
    # xprof/timeline SPAN NAMES mimicking the reference's trace naming
    # (utils/profiler.py, ops/negotiated.py) — never read from env.
    "HOROVOD_EXEC", "HOROVOD_ALLREDUCE",
    # The REFERENCE repo's knob, cited in docstrings as provenance for
    # HOROVOD_NUM_STREAMS; this repo never reads it.
    "HOROVOD_NUM_NCCL_STREAMS",
}
_KNOB_SCAN = ["horovod_tpu", "scripts", "csrc", "bench.py",
              "__graft_entry__.py"]
_KNOB_RE = re.compile(r"HOROVOD_[A-Z0-9_]*[A-Z0-9]")


def _scan_files(root: str, entries: Sequence[str],
                exts: Sequence[str]) -> List[str]:
    out = []
    for entry in entries:
        full = os.path.join(root, entry)
        if os.path.isfile(full):
            out.append(entry)
        elif os.path.isdir(full):
            for dirpath, _dirs, files in os.walk(full):
                for f in sorted(files):
                    if f.endswith(tuple(exts)):
                        out.append(os.path.relpath(
                            os.path.join(dirpath, f), root))
    return sorted(set(out))


def check_knob_registry(root: str = REPO,
                        scan: Optional[Sequence[str]] = None,
                        knobs_rel: str = "horovod_tpu/common/knobs.py",
                        docs_rel: str = "docs/knobs.md") -> List[Violation]:
    """Every HOROVOD_* referenced in code is registered (=> parsed at
    hvd.init) and documented in docs/knobs.md."""
    rule = "knob-registry"
    knobs = _load_by_path("_hvdlint_knobs", os.path.join(root, knobs_rel))
    registry = set(knobs.KNOBS)
    doc = _read(root, docs_rel)
    out = []
    seen_missing = set()
    for rel in _scan_files(root, scan or _KNOB_SCAN,
                           (".py", ".cc", ".h", ".sh")):
        text = _read(root, rel)
        for i, line in enumerate(text.splitlines(), 1):
            if _allowed(line, rule):
                continue
            for m in _KNOB_RE.finditer(line):
                name = m.group(0)
                rest = line[m.end():]
                if rest.startswith(("_*", "*")):
                    # glob prose ("HOROVOD_CHAOS_TCP_*"): a prefix
                    # reference — fine iff some registered knob matches.
                    if not any(k.startswith(name + "_") for k in registry):
                        out.append(Violation(
                            rule, rel, i,
                            f"{name}_* matches no registered knob"))
                    continue
                if name in registry or name in KNOWN_NON_KNOBS:
                    continue
                if (rel, name) in seen_missing:
                    continue  # one report per (file, name)
                seen_missing.add((rel, name))
                out.append(Violation(
                    rule, rel, i,
                    f"{name} is not in the common/knobs.py registry "
                    "(register it so hvd.init parses/validates it, or "
                    "add to KNOWN_NON_KNOBS with a justification)"))
    for name in sorted(registry):
        if f"`{name}`" not in doc:
            out.append(Violation(
                rule, docs_rel, 1,
                f"registered knob {name} has no docs/knobs.md row"))
    return out


# -------------------------------------------------------- metrics-documented
def _doc_metric_names(doc: str) -> set:
    """Names documented in metrics.md: verbatim `hvd_*` code spans,
    `{a,b}` alternations expanded, label annotations (`{op=...}`)
    stripped, and `_suffix` shorthand fragments expanded against every
    split point of the full names on the same line (the
    "`hvd_x_hits_total` / `_misses_total`" convention)."""
    def expand(span: str) -> List[str]:
        m = re.search(r"\{([^{}=]+)\}", span)
        if m and "," in m.group(1):
            return [x for alt in m.group(1).split(",")
                    for x in expand(span[:m.start()] + alt + span[m.end():])]
        return [re.sub(r"\{.*$", "", span).strip()]

    names = set()
    for line in doc.splitlines():
        fulls = []
        for span in re.findall(r"`([^`]+)`", line):
            for e in expand(span):
                if e.startswith("hvd_"):
                    names.add(e)
                    fulls.append(e)
                elif e.startswith("_"):
                    for f in fulls:
                        for i in range(len(f)):
                            names.add(f[:i] + e)
    return names


def check_metrics_documented(
        root: str = REPO,
        metrics_rel: str = "horovod_tpu/utils/metrics.py",
        docs_rel: str = "docs/metrics.md",
        lint_exposition: bool = True) -> List[Violation]:
    rule = "metrics-documented"
    src = _read(root, metrics_rel)
    fams: Dict[str, int] = {}
    for node in ast.walk(ast.parse(src)):
        if (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and node.func.attr in ("counter", "gauge", "histogram")
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == "REGISTRY"
                and node.args and isinstance(node.args[0], ast.Constant)):
            fams.setdefault(str(node.args[0].value), node.lineno)
    documented = _doc_metric_names(_read(root, docs_rel))
    out = [Violation(rule, metrics_rel, line,
                     f"metric family {name} has no {docs_rel} row")
           for name, line in sorted(fams.items())
           if name not in documented]
    if lint_exposition:
        # Subsumes scripts/check_metrics_format.py: a populated fleet
        # snapshot rendered through the server's own code path must
        # lint clean in Prometheus exposition format.
        m = _load_by_path("_hvdlint_metrics",
                          os.path.join(root, metrics_rel))
        text = m.render_prometheus([({"rank": "0"}, m.REGISTRY.snapshot())])
        for err in m.lint_exposition(text):
            out.append(Violation(rule, metrics_rel, 1,
                                 f"exposition lint: {err}"))
    return out


# --------------------------------------------------------- serve-determinism
# The lockstep-critical scopes: scheduling/plan decisions replicated
# across ranks (and replayed by the journal redrive).  Wall-clock METERING
# (TTFT stamps) is allowed; wall-clock or RNG CONTROL FLOW is not, and
# neither is iteration over unordered sets.
_DETERMINISM_SCOPES = {
    "horovod_tpu/serve/engine.py": ["Scheduler", "PrefixCache",
                                    "BlockAllocator", "HostSpillPool",
                                    "draft_lookup", "draft_rows",
                                    "tick_program", "block_tick_program",
                                    "block_admit_program",
                                    "_dispatch", "_fold_sched"],
    # the rule of a denoising pass, which the block tick applies on the
    # device and every rank must apply alike
    "horovod_tpu/models/blockdiff_moe.py": ["fix_positions", "candidates"],
    "horovod_tpu/serve/worker.py": ["plan_key", "_publish_plan",
                                    "_fetch_plan", "_apply_resume"],
    # The whole replicated tier is lockstep-grade: routing decisions
    # must replay identically (callers pass `now` explicitly).
    "horovod_tpu/serve/replica.py": ["ReplicaRouter",
                                     "prompt_fingerprints",
                                     "prefix_fingerprints",
                                     "fold_digest", "scoped",
                                     "_fold_block", "_bisect_contains"],
}
_TIME_FNS = {"time", "monotonic", "perf_counter", "process_time",
             "thread_time", "clock_gettime"}


class _DeterminismVisitor(ast.NodeVisitor):
    def __init__(self, rel, src_lines, rule):
        self.rel = rel
        self.lines = src_lines
        self.rule = rule
        self.out: List[Violation] = []
        self._test_depth = 0

    def _flag(self, node, msg):
        line = self.lines[node.lineno - 1] if node.lineno <= len(
            self.lines) else ""
        if not _allowed(line, self.rule):
            self.out.append(Violation(self.rule, self.rel, node.lineno,
                                      msg))

    def _is_module_call(self, node, module, fns=None):
        return (isinstance(node, ast.Call)
                and isinstance(node.func, ast.Attribute)
                and isinstance(node.func.value, ast.Name)
                and node.func.value.id == module
                and (fns is None or node.func.attr in fns))

    def visit_Call(self, node):
        if self._is_module_call(node, "random") or \
                self._is_module_call(node, "uuid"):
            self._flag(node, "RNG call in the lockstep path "
                             "(nondeterministic across ranks/replays)")
        if self._test_depth and self._is_module_call(node, "time",
                                                     _TIME_FNS):
            self._flag(node, "wall-clock value drives control flow in "
                             "the lockstep path (rank-local timing "
                             "would fork the fleet's schedule)")
        self.generic_visit(node)

    def _visit_test(self, test):
        self._test_depth += 1
        self.visit(test)
        self._test_depth -= 1

    def visit_If(self, node):
        self._visit_test(node.test)
        for n in node.body + node.orelse:
            self.visit(n)

    def visit_While(self, node):
        self._visit_test(node.test)
        for n in node.body + node.orelse:
            self.visit(n)

    def visit_IfExp(self, node):
        self._visit_test(node.test)
        self.visit(node.body)
        self.visit(node.orelse)

    def visit_For(self, node):
        it = node.iter
        if isinstance(it, (ast.Set, ast.SetComp)) or (
                isinstance(it, ast.Call) and isinstance(it.func, ast.Name)
                and it.func.id in ("set", "frozenset")):
            self._flag(node, "iteration over an unordered set in the "
                             "lockstep path (order varies per process; "
                             "sorted(...) it)")
        self.generic_visit(node)


def check_serve_determinism(
        root: str = REPO,
        scopes: Optional[Dict[str, List[str]]] = None) -> List[Violation]:
    """No RNG, time-driven control flow, or set iteration in the serve
    lockstep scopes."""
    rule = "serve-determinism"
    out = []
    for rel, names in sorted((scopes or _DETERMINISM_SCOPES).items()):
        src = _read(root, rel)
        tree = ast.parse(src)
        lines = src.splitlines()
        # also flag `import random` at module scope of these files
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mods = [a.name for a in node.names] if isinstance(
                    node, ast.Import) else [node.module or ""]
                if any(m1 == "random" or m1.startswith("random.")
                       for m1 in mods):
                    line = lines[node.lineno - 1]
                    if not _allowed(line, rule):
                        out.append(Violation(
                            rule, rel, node.lineno,
                            "`random` imported in a lockstep-path "
                            "module"))

        def walk_scope(node):
            v = _DeterminismVisitor(rel, lines, rule)
            for child in ast.iter_child_nodes(node):
                v.visit(child)
            out.extend(v.out)

        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)) and node.name in names:
                walk_scope(node)
    return out


# ------------------------------------------------------ kvshard-determinism
class _KVShardVisitor(_DeterminismVisitor):
    """The serve-determinism checks plus two map-specific hazards:
    builtin ``hash()`` (varies per process under PYTHONHASHSEED) and
    environment reads (two ranks with different env would partition the
    KV differently)."""

    def visit_Call(self, node):
        if isinstance(node.func, ast.Name) and node.func.id == "hash":
            self._flag(node, "builtin hash() in the scope->shard map "
                             "(PYTHONHASHSEED-dependent: ranks would "
                             "disagree on the partition; use the FNV "
                             "helper)")
        if self._is_module_call(node, "os", {"getenv"}):
            self._flag(node, "environment read in the scope->shard map "
                             "(the map must be a pure function of "
                             "(scope, count))")
        super().visit_Call(node)

    def visit_Attribute(self, node):
        if (isinstance(node.value, ast.Name) and node.value.id == "os"
                and node.attr == "environ"):
            self._flag(node, "os.environ access in the scope->shard map "
                             "(the map must be a pure function of "
                             "(scope, count))")
        self.generic_visit(node)


def check_kvshard_determinism(
        root: str = REPO,
        rel: str = "horovod_tpu/runner/kvshard.py") -> List[Violation]:
    """The scope->shard map is a pure function of (scope, count)."""
    rule = "kvshard-determinism"
    src = _read(root, rel)
    tree = ast.parse(src)
    lines = src.splitlines()
    out: List[Violation] = []
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names] if isinstance(
                node, ast.Import) else [node.module or ""]
            bad = [m1 for m1 in mods
                   if m1 == "random" or m1.startswith("random.")
                   or m1 == "time" or m1.startswith("time.")]
            if bad and not _allowed(lines[node.lineno - 1], rule):
                out.append(Violation(
                    rule, rel, node.lineno,
                    f"{'/'.join(bad)} imported in the scope->shard map "
                    "module (determinism contract; "
                    "docs/control-plane.md)"))
    v = _KVShardVisitor(rel, lines, rule)
    v.visit(tree)
    out.extend(v.out)
    return out


# ----------------------------------------------------------- serve-kv-retry
# ``wait_kv`` is the arrivals reader's held GET (http_client.KeyWaiter)
_KV_OPS = {"get_kv", "put_kv", "delete_kv", "wait_kv"}
_KV_WRAPPERS = {"_kv_op", "_kv_get", "_kv_put", "_kv_delete"}


# --------------------------------------------------- scenario-determinism
# The scenario generators/replay (horovod_tpu/scenario): the whole
# module surface is determinism-critical — same spec, same seed must
# yield byte-identical event streams and SLO rows across processes,
# interpreter sessions and PYTHONHASHSEED values (docs/scenarios.md).
# The kvshard discipline applies module-wide: no RNG, no builtin
# hash(), no env reads, no wall-clock control flow, no set iteration,
# and neither `random` nor `time` may even be imported.
_SCENARIO_FILES = (
    "horovod_tpu/scenario/trace.py",
    "horovod_tpu/scenario/spec.py",
    "horovod_tpu/scenario/storm.py",
    "horovod_tpu/scenario/harness.py",
)


def check_scenario_determinism(
        root: str = REPO,
        files: Sequence[str] = _SCENARIO_FILES) -> List[Violation]:
    """Scenario generators/replay are pure functions of (spec, seed):
    no RNG, no hash(), no env/wall-clock, no set iteration."""
    rule = "scenario-determinism"
    out: List[Violation] = []
    for rel in files:
        src = _read(root, rel)
        tree = ast.parse(src)
        lines = src.splitlines()
        for node in ast.walk(tree):
            if isinstance(node, (ast.Import, ast.ImportFrom)):
                mods = [a.name for a in node.names] if isinstance(
                    node, ast.Import) else [node.module or ""]
                bad = [m1 for m1 in mods
                       if m1 == "random" or m1.startswith("random.")
                       or m1 == "time" or m1.startswith("time.")
                       or m1 == "uuid" or m1.startswith("uuid.")]
                if bad and not _allowed(lines[node.lineno - 1], rule):
                    out.append(Violation(
                        rule, rel, node.lineno,
                        f"{'/'.join(bad)} imported in a scenario module "
                        "(every draw must come from scenario/trace.py "
                        "Stream; docs/scenarios.md)"))
        v = _KVShardVisitor(rel, lines, rule)
        v.visit(tree)
        out.extend(v.out)
    return out


def check_serve_kv_retry(
        root: str = REPO,
        files: Sequence[str] = ("horovod_tpu/serve/worker.py",
                                "horovod_tpu/serve/arrivals.py",
                                "horovod_tpu/serve/journal.py"),
) -> List[Violation]:
    """Serve-worker KV legs must ride the _kv_op backoff wrapper."""
    rule = "serve-kv-retry"
    out = []
    for rel in files:
        src = _read(root, rel)
        lines = src.splitlines()
        tree = ast.parse(src)
        # annotate parents so we can look up enclosing function/lambda
        parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _KV_OPS):
                continue
            ok = False
            cur = node
            while cur in parents:
                cur = parents[cur]
                if isinstance(cur, ast.Lambda):
                    # a thunk handed to *._kv_op(...) is the sanctioned
                    # shape; any other lambda is still a raw call
                    call = parents.get(cur)
                    if (isinstance(call, ast.Call)
                            and isinstance(call.func, ast.Attribute)
                            and call.func.attr == "_kv_op"):
                        ok = True
                        break
                if isinstance(cur, (ast.FunctionDef,
                                    ast.AsyncFunctionDef)):
                    ok = cur.name in _KV_WRAPPERS
                    break
            line = lines[node.lineno - 1]
            if not ok and not _allowed(line, rule):
                out.append(Violation(
                    rule, rel, node.lineno,
                    f"raw {node.func.attr} outside the _kv_op backoff "
                    "wrapper — a transient rendezvous outage would kill "
                    "the serve loop instead of stalling it"))
    return out


# ----------------------------------------------------- unique-test-basenames
def check_unique_test_basenames(root: str = REPO,
                                tests_rel: str = "tests") -> List[Violation]:
    """Test/worker module basenames unique across the tests/ tree."""
    rule = "unique-test-basenames"
    seen: Dict[str, str] = {}
    out = []
    for dirpath, _dirs, files in sorted(os.walk(os.path.join(root,
                                                             tests_rel))):
        for f in sorted(files):
            if not f.endswith(".py") or f in ("__init__.py",
                                              "conftest.py"):
                continue
            rel = os.path.relpath(os.path.join(dirpath, f), root)
            if f in seen:
                out.append(Violation(
                    rule, rel, 1,
                    f"basename {f} collides with {seen[f]} — tests/ "
                    "packages have no __init__.py, so pytest collection "
                    "fails with an import-file mismatch; rename one "
                    "(e.g. *_integration.py)"))
            else:
                seen[f] = rel
    return out


# ------------------------------------------------------------- signal-safety
# Allowlist for csrc/postmortem.cc: async-signal-safe libc, lock-free
# accessors, and the file's own handler helpers.  Anything else called
# from this file is a finding — the whole file is handler-reachable
# except the Arm/Disarm installers, and keeping ONE allowlist for the
# file is what makes the rule reviewable.
SIGNAL_SAFE_CALLS = {
    # async-signal-safe libc (POSIX) + string helpers on local buffers
    "write", "open", "close", "raise", "signal", "sigaction",
    "sigemptyset", "abort", "_exit", "memcpy", "memset", "strlen",
    "strcpy", "strcat", "strncpy", "strncat",
    # lock-free atomics / installers
    "load", "store", "exchange", "compare_exchange_strong",
    "set_terminate",
    # chaining the PREVIOUS std::terminate handler is the documented
    # contract of TerminateHandler (restore-and-chain); its safety is
    # whoever installed it, which is outside this file's control.
    "g_prev_terminate",
    # project accessors that are lock-free by design (atomic snapshots,
    # bounded-spin ring copy — csrc/core.h, csrc/trace.h)
    "stats", "transport_stats", "health_snapshot", "rank", "size",
    "trace", "NowUs", "SnapshotTail", "Snapshot", "EnableTrace",
    # this file's own helpers
    "PutStr", "PutChar", "PutU64", "PutI64", "PutKV", "SigName",
    "DumpNow", "WriteFlightRecord", "FatalSignalHandler",
    "TerminateHandler", "InstallHandlers", "FlightRecorderArm",
    "FlightRecorderDisarm", "FlightDump",
}
_CPP_KEYWORDS = {"if", "while", "for", "switch", "return", "sizeof",
                 "catch", "do", "else", "case", "defined", "alignof",
                 "decltype", "noexcept"}


def _strip_cpp_comments_strings(src: str) -> str:
    """Blank out comments and string/char literals, preserving line
    structure so reported line numbers stay meaningful."""
    out = []
    i, n = 0, len(src)
    mode = None  # None | '//' | '/*' | '"' | "'"
    while i < n:
        c = src[i]
        nxt = src[i + 1] if i + 1 < n else ""
        if mode is None:
            if c == "/" and nxt == "/":
                mode = "//"
                out.append("  ")
                i += 2
                continue
            if c == "/" and nxt == "*":
                mode = "/*"
                out.append("  ")
                i += 2
                continue
            if c in "\"'":
                mode = c
                out.append(" ")
                i += 1
                continue
            out.append(c)
        else:
            if c == "\n":
                out.append("\n")
                if mode == "//":
                    mode = None
                i += 1
                continue
            if mode == "/*" and c == "*" and nxt == "/":
                mode = None
                out.append("  ")
                i += 2
                continue
            if mode in "\"'" and c == "\\":
                out.append("  ")
                i += 2
                continue
            if mode in "\"'" and c == mode:
                mode = None
            out.append(" ")
        i += 1
    return "".join(out)


def check_signal_safety(root: str = REPO,
                        rel: str = "csrc/postmortem.cc",
                        allow: Optional[set] = None) -> List[Violation]:
    """postmortem.cc calls only the async-signal-safe allowlist."""
    rule = "signal-safety"
    src = _read(root, rel)
    raw_lines = src.splitlines()
    stripped = _strip_cpp_comments_strings(src)
    allow = allow if allow is not None else SIGNAL_SAFE_CALLS
    out = []
    for i, line in enumerate(stripped.splitlines(), 1):
        raw = raw_lines[i - 1] if i <= len(raw_lines) else ""
        if _allowed(raw, rule):
            continue
        for m in re.finditer(r"\b([A-Za-z_][A-Za-z0-9_]*)\s*\(", line):
            name = m.group(1)
            if name in _CPP_KEYWORDS or name in allow:
                continue
            out.append(Violation(
                rule, rel, i,
                f"call to {name}() is not on the async-signal-safe "
                "allowlist (scripts/hvdlint.py SIGNAL_SAFE_CALLS) — "
                "fatal-signal handlers may run on a corrupt heap/stack"))
    return out


# --------------------------------------------------------------- trace-context
# The request-tracing determinism contract (docs/serving.md#request-
# lifecycle): span ids are a pure function of (rid, hop) — the trace-id
# module must stay clock/RNG-free so redrives, re-dispatches and
# scenario replays re-mint IDENTICAL ids — and every serve-path span
# emission carries the rid in its args so the merged timeline stays
# causally linked across replica fleets.
_TRACE_MODULE = "horovod_tpu/serve/trace.py"
_TRACE_SPAN_FILES = (
    "horovod_tpu/serve/engine.py",
    "horovod_tpu/serve/router.py",
    "horovod_tpu/serve/stream.py",
    "horovod_tpu/serve/worker.py",
    "horovod_tpu/scenario/harness.py",
)
_SPAN_EMITTERS = {"record_span", "trace_span"}


def _call_name(func) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _is_ctx_args(node, ctx_names) -> bool:
    """args passes the contract when it is a ``span_args(...)`` call, a
    dict literal with a ``rid``/``req`` key, or a name bound to one of
    those in the enclosing function."""
    if isinstance(node, ast.Call) and _call_name(node.func) == "span_args":
        return True
    if isinstance(node, ast.Dict):
        return any(isinstance(k, ast.Constant)
                   and k.value in ("rid", "req") for k in node.keys)
    if isinstance(node, ast.Name):
        return node.id in ctx_names
    return False


def check_trace_context(
        root: str = REPO,
        files: Sequence[str] = _TRACE_SPAN_FILES,
        trace_rel: str = _TRACE_MODULE) -> List[Violation]:
    """Span ids stay pure (rid, hop) functions; serve-path span
    emissions carry the rid."""
    rule = "trace-context"
    out: List[Violation] = []
    # (A) the trace-id module itself: clock/RNG-free, no builtin hash().
    src = _read(root, trace_rel)
    tree = ast.parse(src)
    lines = src.splitlines()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            mods = [a.name for a in node.names] if isinstance(
                node, ast.Import) else [node.module or ""]
            bad = [m1 for m1 in mods
                   if m1.split(".")[0] in ("time", "random", "uuid")]
            if bad and not _allowed(lines[node.lineno - 1], rule):
                out.append(Violation(
                    rule, trace_rel, node.lineno,
                    f"{'/'.join(bad)} imported in the trace-id module — "
                    "span ids must be a pure function of (rid, hop) so "
                    "redrives and replays re-mint identical ids"))
        if (isinstance(node, ast.Call) and isinstance(node.func, ast.Name)
                and node.func.id == "hash"
                and not _allowed(lines[node.lineno - 1], rule)):
            out.append(Violation(
                rule, trace_rel, node.lineno,
                "builtin hash() in the trace-id module "
                "(PYTHONHASHSEED-dependent: two processes would mint "
                "different ids for the same hop; use the FNV helper)"))
    # (B) span emission sites carry the context; (C) no id minted from
    # clock/RNG at the call site.
    for rel in files:
        src = _read(root, rel)
        tree = ast.parse(src)
        lines = src.splitlines()
        parents = {}
        for node in ast.walk(tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node

        def _scope_ctx_names(call):
            cur = call
            while cur in parents and not isinstance(
                    cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                cur = parents[cur]
            names = set()
            if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for n in ast.walk(cur):
                    if isinstance(n, ast.Assign) \
                            and _is_ctx_args(n.value, ()):
                        names.update(t.id for t in n.targets
                                     if isinstance(t, ast.Name))
            return names

        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            name = _call_name(node.func)
            if name == "span_id":
                for sub in ast.walk(node):
                    if (isinstance(sub, ast.Call)
                            and isinstance(sub.func, ast.Attribute)
                            and isinstance(sub.func.value, ast.Name)
                            and sub.func.value.id in ("time", "random",
                                                      "uuid")
                            and not _allowed(lines[node.lineno - 1],
                                             rule)):
                        out.append(Violation(
                            rule, rel, node.lineno,
                            f"span_id minted from {sub.func.value.id}."
                            f"{sub.func.attr}() — ids must derive from "
                            "(rid, hop) only, never RNG or clock"))
                continue
            if name in _SPAN_EMITTERS:
                args_node = None
                for kw in node.keywords:
                    if kw.arg == "args":
                        args_node = kw.value
                if args_node is None and name == "trace_span" \
                        and len(node.args) >= 6:
                    args_node = node.args[5]
                if (args_node is None
                        or not _is_ctx_args(args_node,
                                            _scope_ctx_names(node))) \
                        and not _allowed(lines[node.lineno - 1], rule):
                    out.append(Violation(
                        rule, rel, node.lineno,
                        f"{name}() on the serve path without "
                        "trace-context args — pass trace.span_args(...) "
                        "(or a dict carrying 'rid'/'req') so the merged "
                        "timeline stays causally linked"))
    return out


# ------------------------------------------------------------------- driver
RULES = {
    "knob-registry": check_knob_registry,
    "metrics-documented": check_metrics_documented,
    "serve-determinism": check_serve_determinism,
    "kvshard-determinism": check_kvshard_determinism,
    "scenario-determinism": check_scenario_determinism,
    "serve-kv-retry": check_serve_kv_retry,
    "trace-context": check_trace_context,
    "unique-test-basenames": check_unique_test_basenames,
    "signal-safety": check_signal_safety,
}


def run(rules: Optional[Sequence[str]] = None,
        root: str = REPO) -> List[Violation]:
    out = []
    for name in (rules or sorted(RULES)):
        out.extend(RULES[name](root))
    return out


def main() -> int:
    ap = argparse.ArgumentParser(
        description="repo-invariant linter (docs/static-analysis.md)")
    ap.add_argument("--rule", action="append", choices=sorted(RULES),
                    help="run only this rule (repeatable)")
    ap.add_argument("--list", action="store_true",
                    help="print the rule catalog and exit")
    ap.add_argument("--root", default=REPO, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.list:
        for name in sorted(RULES):
            doc = (RULES[name].__doc__ or "").strip().split("\n")[0]
            print(f"{name:24s} {doc}")
        return 0
    violations = run(args.rule, root=args.root)
    for v in violations:
        print(v.render(), file=sys.stderr)
    if violations:
        print(f"hvdlint: {len(violations)} violation(s) across "
              f"{len({v.rule for v in violations})} rule(s)",
              file=sys.stderr)
        return 1
    names = args.rule or sorted(RULES)
    print(f"hvdlint OK: {len(names)} rule(s) clean "
          f"({', '.join(names)})")
    return 0


if __name__ == "__main__":
    sys.exit(main())

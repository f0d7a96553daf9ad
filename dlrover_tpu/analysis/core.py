"""graftlint core: rule framework, suppression, config, runner, output.

The analyzer is purely AST + line based (stdlib ``ast``), so it runs in
milliseconds over the whole tree and never imports the code it checks —
with one deliberate exception: the env-registry rule imports
``dlrover_tpu.common.envs`` (a leaf module with no heavy deps) to learn
the set of registered knobs.

Vocabulary:

* A **rule** is a class with a stable ``id`` (``GL1xx`` collective
  divergence, ``GL2xx`` lock discipline, ``GL3xx`` env knobs, ``GL4xx``
  thread hygiene), a default severity, and a ``check(module)`` generator
  yielding :class:`Finding`.
* A **finding** pins (rule, path, line, col, message).
* A finding is **suppressed** by a same-line comment
  ``# graftlint: disable=GL201`` (comma-separated ids, ``all`` wildcard).
  Suppressions should carry a reason after the id list, e.g.
  ``# graftlint: disable=GL202 (pacing sleep is the point of the stager)``.
  ``--show-suppressed`` lists them; they never affect the exit code.

Config comes from ``[tool.graftlint]`` in ``pyproject.toml`` (found by
walking up from the first scanned path), parsed with the stdlib
``tomllib``; without a file the built-in defaults apply.
"""

import ast
import dataclasses
import json
import os
import re
import sys
import time
from typing import (
    Any, Dict, Iterable, Iterator, List, Optional, Set, Tuple,
)

SEVERITIES = ("info", "warning", "error")

_SUPPRESS_RE = re.compile(
    r"#\s*graftlint:\s*disable=([A-Za-z0-9_,\- ]+?)(?:\s*\((?P<reason>[^)]*)\))?\s*$"
)


@dataclasses.dataclass(frozen=True)
class Finding:
    rule_id: str
    severity: str
    path: str
    line: int
    col: int
    message: str
    suppressed: bool = False
    suppress_reason: str = ""

    def render(self) -> str:
        tag = " (suppressed)" if self.suppressed else ""
        return (
            f"{self.path}:{self.line}:{self.col}: "
            f"{self.rule_id} {self.severity}: {self.message}{tag}"
        )

    def to_dict(self) -> Dict[str, Any]:
        return dataclasses.asdict(self)


class SourceFile:
    """One parsed file: text, AST, per-line suppression directives."""

    def __init__(self, path: str, text: str):
        self.path = path
        self.text = text
        self.lines = text.splitlines()
        self.tree: Optional[ast.Module] = None
        self.parse_error: Optional[SyntaxError] = None
        try:
            self.tree = ast.parse(text, filename=path)
        except SyntaxError as e:
            self.parse_error = e
        # line -> (set of rule ids or {"all"}, reason)
        self.suppressions: Dict[int, Tuple[set, str]] = {}
        for i, line in enumerate(self.lines, start=1):
            m = _SUPPRESS_RE.search(line)
            if m:
                ids = {
                    s.strip().upper()
                    for s in m.group(1).split(",")
                    if s.strip()
                }
                self.suppressions[i] = (ids, (m.group("reason") or "").strip())
        # scratch space rules share so the same file is never re-walked
        # per rule (the GL2xx lock scans, the flat node list, ...)
        self.cache: Dict[str, Any] = {}

    def nodes(self) -> List[ast.AST]:
        """Flat ``ast.walk`` order, computed once and shared by every
        rule that does a whole-tree sweep."""
        cached = self.cache.get("nodes")
        if cached is None:
            cached = [] if self.tree is None else list(ast.walk(self.tree))
            self.cache["nodes"] = cached
        return cached

    def suppression_for(self, line: int, rule_id: str) -> Optional[str]:
        """Reason string when ``rule_id`` is disabled on ``line`` else None."""
        entry = self.suppressions.get(line)
        if not entry:
            return None
        ids, reason = entry
        if rule_id.upper() in ids or "ALL" in ids:
            return reason or "(no reason given)"
        return None


class Rule:
    """Base class.  Subclasses set ``id``/``name``/``severity``/``doc``
    and implement :meth:`check`."""

    id: str = ""
    name: str = ""
    severity: str = "error"
    doc: str = ""

    def __init__(self, config: "Config"):
        self.config = config

    def check(self, src: SourceFile) -> Iterator[Finding]:
        """Per-file pass; whole-program rules may leave this empty."""
        return iter(())

    def check_program(self, program) -> Iterator[Finding]:
        """Whole-program pass, called once per run with the
        :class:`~dlrover_tpu.analysis.program.Program` index built over
        every scanned file.  Default: no interprocedural findings."""
        return iter(())

    # shared helper: make a finding at a node
    def finding(self, src: SourceFile, node: ast.AST, message: str) -> Finding:
        line = getattr(node, "lineno", 1)
        col = getattr(node, "col_offset", 0)
        severity = self.config.severity_overrides.get(self.id, self.severity)
        return Finding(
            rule_id=self.id,
            severity=severity,
            path=src.path,
            line=line,
            col=col,
            message=message,
        )


@dataclasses.dataclass
class Config:
    enable: Optional[List[str]] = None  # None = all registered rules
    disable: List[str] = dataclasses.field(default_factory=list)
    knob_prefix: str = "DLROVER_TPU_"
    # classes whose attributes name env vars (constants.py style)
    env_const_classes: List[str] = dataclasses.field(
        default_factory=lambda: ["NodeEnv", "RendezvousEnv", "ConfigPath"]
    )
    # legacy helper fns that read env by name; calls with knob literals
    # count as raw reads too (otherwise wrappers launder the access)
    env_wrapper_funcs: List[str] = dataclasses.field(
        default_factory=lambda: ["get_env_int", "get_env_float", "get_env_bool"]
    )
    # path suffixes allowed to touch os.environ for registered knobs
    # (the registry implementation itself)
    allow_raw_env_files: List[str] = dataclasses.field(
        default_factory=lambda: ["dlrover_tpu/common/envs.py"]
    )
    # extra knob names (non-prefixed legacy) the registry also owns
    extra_knobs: List[str] = dataclasses.field(default_factory=list)
    # control-plane modules whose RPC boundaries must open/propagate a
    # trace span (GL601): path suffixes, checked with endswith
    traced_rpc_files: List[str] = dataclasses.field(
        default_factory=lambda: [
            "dlrover_tpu/master/servicer.py",
            "dlrover_tpu/master/kv_store.py",
            "dlrover_tpu/unified/rpc.py",
            "dlrover_tpu/agent/master_client.py",
        ]
    )
    # path fragments where arming chaos injection is legitimate (GL501):
    # the chaos package itself, tests, and the drill modules
    chaos_allowed_paths: List[str] = dataclasses.field(
        default_factory=lambda: [
            "dlrover_tpu/chaos/",
            "tests/",
            "tests_tpu/",
            "chaos_drill.py",
            "goodput_drill.py",
            "reshard_drill.py",
            "multi_controller_drill.py",
            "trace_smoke.py",
            "incident_smoke.py",
            "goodput_smoke.py",
            "data_smoke.py",
            "comm_smoke.py",
            "mem_smoke.py",
            "hierarchy_smoke.py",
            "tuner_smoke.py",
            "conftest.py",
        ]
    )
    # wire-protocol drift (GL9xx): where the message dataclasses and the
    # demux/servicer routes live (path suffixes), and the human-facing
    # catalogs the registries must stay in sync with (relative to
    # ``root`` when loaded from pyproject.toml)
    wire_comm_files: List[str] = dataclasses.field(
        default_factory=lambda: ["dlrover_tpu/common/comm.py"]
    )
    wire_servicer_files: List[str] = dataclasses.field(
        default_factory=lambda: ["dlrover_tpu/master/servicer.py"]
    )
    chaos_doc_file: str = "docs/chaos.md"
    env_doc_file: str = "docs/envs.md"
    severity_overrides: Dict[str, str] = dataclasses.field(
        default_factory=dict
    )
    fail_on: str = "warning"  # minimum severity that flips the exit code
    # repo root (directory holding pyproject.toml) — lets rules resolve
    # doc files that sit outside the scanned paths; None for ad-hoc
    # configs (unit tests)
    root: Optional[str] = None

    @staticmethod
    def load(start_path: str) -> "Config":
        """Find pyproject.toml upward from ``start_path``; read
        ``[tool.graftlint]``.  Missing file/section => defaults."""
        cfg = Config()
        pyproject = _find_pyproject(start_path)
        if not pyproject:
            return cfg
        cfg.root = os.path.dirname(pyproject)
        import tomllib

        try:
            with open(pyproject, "rb") as f:
                data = tomllib.load(f)
        except (OSError, ValueError):
            return cfg
        section = data.get("tool", {}).get("graftlint", {})
        if not isinstance(section, dict):
            return cfg
        for key in (
            "enable",
            "disable",
            "knob_prefix",
            "env_const_classes",
            "env_wrapper_funcs",
            "allow_raw_env_files",
            "extra_knobs",
            "chaos_allowed_paths",
            "traced_rpc_files",
            "wire_comm_files",
            "wire_servicer_files",
            "chaos_doc_file",
            "env_doc_file",
            "fail_on",
        ):
            if key in section:
                setattr(cfg, key, section[key])
        sev = section.get("severity", {})
        if isinstance(sev, dict):
            cfg.severity_overrides = {
                str(k).upper(): str(v) for k, v in sev.items()
            }
        return cfg


def _find_pyproject(start: str) -> Optional[str]:
    cur = os.path.abspath(start)
    if os.path.isfile(cur):
        cur = os.path.dirname(cur)
    while True:
        cand = os.path.join(cur, "pyproject.toml")
        if os.path.isfile(cand):
            return cand
        parent = os.path.dirname(cur)
        if parent == cur:
            return None
        cur = parent


# -- AST helpers shared by rule modules -------------------------------------


def dotted_name(node: ast.AST) -> Optional[str]:
    """'a.b.c' for Name/Attribute chains, else None."""
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


def call_name(node: ast.Call) -> Optional[str]:
    return dotted_name(node.func)


def iter_child_statements(node: ast.AST) -> Iterator[ast.stmt]:
    for field in ("body", "orelse", "finalbody", "handlers"):
        for child in getattr(node, field, []) or []:
            if isinstance(child, ast.ExceptHandler):
                yield from child.body
            elif isinstance(child, ast.stmt):
                yield child


def walk_functions(tree: ast.Module) -> Iterator[ast.AST]:
    """Every function/async-function/lambda-free scope, outermost first."""
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


# -- registry ----------------------------------------------------------------

_RULE_CLASSES: List[type] = []


def register_rule(cls: type) -> type:
    assert cls.id, f"rule {cls.__name__} missing id"
    assert all(c.id != cls.id for c in _RULE_CLASSES), f"dup rule id {cls.id}"
    _RULE_CLASSES.append(cls)
    return cls


def all_rule_classes() -> List[type]:
    # import side-effect registration
    from dlrover_tpu.analysis import rules as _rules  # noqa: F401

    return list(_RULE_CLASSES)


def active_rules(config: Config) -> List[Rule]:
    enabled = []
    for cls in all_rule_classes():
        if config.enable is not None and cls.id not in config.enable:
            continue
        if cls.id in config.disable:
            continue
        enabled.append(cls(config))
    return sorted(enabled, key=lambda r: r.id)


@register_rule
class UnusedSuppressionRule(Rule):
    """GL001 is synthesized by the runner, not by a ``check`` pass: a
    ``# graftlint: disable=GLxxx`` directive whose rule (active in this
    run) produced no finding on that line is dead weight — usually a fix
    landed and the comment rotted, or interprocedural precision now sees
    the guard the old rule couldn't.  Unknown rule ids are flagged too
    (a typo'd id silently suppresses nothing)."""

    id = "GL001"
    name = "unused-suppression"
    severity = "warning"
    doc = (
        "suppression directive whose rule produced no finding on that "
        "line (stale after a fix or a precision upgrade), or an unknown "
        "rule id"
    )


# -- runner ------------------------------------------------------------------


def collect_py_files(paths: Iterable[str]) -> List[str]:
    out: List[str] = []
    for p in paths:
        if os.path.isfile(p):
            out.append(p)
        elif os.path.isdir(p):
            for root, dirs, files in os.walk(p):
                dirs[:] = sorted(
                    d for d in dirs
                    if d != "__pycache__" and not d.startswith(".")
                )
                for f in sorted(files):
                    if f.endswith(".py"):
                        out.append(os.path.join(root, f))
    return out


def run_paths(
    paths: Iterable[str],
    config: Optional[Config] = None,
    timings: Optional[Dict[str, float]] = None,
    changed_only: Optional[Iterable[str]] = None,
) -> List[Finding]:
    """Lint ``paths`` (files or dirs).  Returns ALL findings; suppressed
    ones carry ``suppressed=True`` so callers can decide what to show.
    A file that fails to parse yields a single GL000 error finding.

    ``timings`` (when a dict is passed) is filled with wall seconds per
    rule id plus the ``(parse)`` and ``(program)`` pseudo-phases.

    ``changed_only``: a list of changed file paths.  The whole-program
    index is still built over every ``paths`` file (call resolution and
    summaries need it), but findings are restricted to the changed files
    plus their reverse interprocedural dependents — the ``--since``
    pre-commit fast path."""
    from dlrover_tpu.analysis.program import Program

    t0 = time.perf_counter()
    files = collect_py_files(paths)
    if config is None:
        config = Config.load(files[0] if files else os.getcwd())
    rules = active_rules(config)
    active_ids = {r.id for r in rules}
    known_ids = {cls.id for cls in all_rule_classes()} | {"GL000"}

    findings: List[Finding] = []
    srcs: List[SourceFile] = []
    for path in files:
        try:
            with open(path, "r", encoding="utf-8") as f:
                text = f.read()
        except OSError as e:
            findings.append(
                Finding("GL000", "error", path, 1, 0, f"unreadable: {e}")
            )
            continue
        src = SourceFile(_display_path(path), text)
        if src.parse_error is not None:
            findings.append(
                Finding(
                    "GL000",
                    "error",
                    src.path,
                    src.parse_error.lineno or 1,
                    src.parse_error.offset or 0,
                    f"syntax error: {src.parse_error.msg}",
                )
            )
            continue
        srcs.append(src)
    if timings is not None:
        timings["(parse)"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    program = Program(srcs)
    if timings is not None:
        timings["(program)"] = time.perf_counter() - t0

    select: Optional[Set[str]] = None
    if changed_only is not None:
        changed = [os.path.abspath(p) for p in changed_only]
        select = {
            _display_path(p)
            for p in program.dependents_of(changed)
        }
        # changed files outside the program (parse errors, non-modules)
        # are still in scope
        known = {os.path.abspath(s.path) for s in srcs}
        select |= {_display_path(p) for p in changed if p not in known}
        findings = [f for f in findings if f.path in select]

    by_path = {s.path: s for s in srcs}

    def _apply_suppression(src: Optional[SourceFile],
                           finding: Finding) -> Finding:
        if src is None:
            return finding
        reason = src.suppression_for(finding.line, finding.rule_id)
        if reason is not None:
            finding = dataclasses.replace(
                finding, suppressed=True, suppress_reason=reason
            )
        return finding

    for src in srcs:
        if select is not None and src.path not in select:
            continue
        for rule in rules:
            t0 = time.perf_counter()
            for finding in rule.check(src):
                findings.append(_apply_suppression(src, finding))
            if timings is not None:
                timings[rule.id] = (
                    timings.get(rule.id, 0.0) + time.perf_counter() - t0
                )

    for rule in rules:
        t0 = time.perf_counter()
        for finding in rule.check_program(program):
            if select is not None and finding.path not in select:
                continue
            findings.append(
                _apply_suppression(by_path.get(finding.path), finding)
            )
        if timings is not None:
            timings[rule.id] = (
                timings.get(rule.id, 0.0) + time.perf_counter() - t0
            )

    if "GL001" in active_ids:
        gl001 = next(r for r in rules if r.id == "GL001")
        sev = config.severity_overrides.get("GL001", gl001.severity)
        used = {
            (f.path, f.line, f.rule_id) for f in findings if f.suppressed
        }
        for src in srcs:
            if select is not None and src.path not in select:
                continue
            for line, (ids, _reason) in sorted(src.suppressions.items()):
                for rid in sorted(ids):
                    if rid in ("ALL", "GL001"):
                        continue
                    if rid not in known_ids:
                        msg = (
                            f"suppression names unknown rule id `{rid}` "
                            "— typo? it disables nothing"
                        )
                    elif rid in active_ids and (
                        src.path, line, rid
                    ) not in used:
                        msg = (
                            f"suppression for {rid} matches no finding "
                            "on this line — stale after a fix or a "
                            "precision upgrade; delete it"
                        )
                    else:
                        continue
                    findings.append(_apply_suppression(src, Finding(
                        "GL001", sev, src.path, line, 0, msg
                    )))

    findings.sort(key=lambda f: (f.path, f.line, f.col, f.rule_id))
    return findings


def _display_path(path: str) -> str:
    rel = os.path.relpath(path, os.getcwd())
    return path if rel.startswith("..") else rel


def severity_rank(sev: str) -> int:
    try:
        return SEVERITIES.index(sev)
    except ValueError:
        return len(SEVERITIES)


def exit_code(findings: List[Finding], config: Config) -> int:
    threshold = severity_rank(config.fail_on)
    live = [
        f
        for f in findings
        if not f.suppressed and severity_rank(f.severity) >= threshold
    ]
    return 1 if live else 0


def render_text(
    findings: List[Finding], show_suppressed: bool = False
) -> str:
    lines = []
    shown = 0
    n_sup = 0
    for f in findings:
        if f.suppressed:
            n_sup += 1
            if not show_suppressed:
                continue
        shown += 1 if not f.suppressed else 0
        lines.append(f.render())
    lines.append(
        f"graftlint: {shown} finding(s), {n_sup} suppressed"
    )
    return "\n".join(lines)


def render_json(findings: List[Finding]) -> str:
    return json.dumps([f.to_dict() for f in findings], indent=2)

"""Single-pass repo index shared by every lint rule.

One walk of the tree reads every ``.py`` source exactly once; ASTs parse
lazily and cache per file. On top of that sit:

- a class/method table (``classes()``, ``classes_by_name``,
  ``methods_by_name``) — the raw material for call-graph walks;
- the shared catalogs that used to live scattered across the one-off
  ``tools/check_*.py`` scripts and the scenario engine:
  fault-injection sites (``fault_sites()``), metric definitions parsed
  statically out of ``tmtpu/libs/metrics.py`` (``metric_defs()``),
  timeline event names (``timeline_events()``), trace span names
  (``span_names()``), and config knobs (``config_knobs()``).

The scenario engine's contract checks (tools/scenario_run.py
``--validate`` and the ``scenarios`` rule) and the lint rules all read
these catalogs, so a metric/fault-site/event rename is caught by one
source of truth instead of three regexes drifting apart.

An index is rooted anywhere: ``RepoIndex(tmp_path)`` over a synthetic
tree is how tests/test_lint.py proves each rule detects its failure
mode. ``default_index()`` memoizes the real repo's index per process so
the CLI, the tier-1 test, and the seven shim CLIs share one parse.
"""

from __future__ import annotations

import ast
import os
import re
from collections import defaultdict
from typing import Dict, Iterator, List, Optional, Set, Tuple

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

DEFAULT_SCAN = ("tmtpu", "tools", "tests", "bench.py", "chip_smoke.py")

# ---------------------------------------------------------------- catalogs
# (regexes ported verbatim from tools/check_failpoints.py /
#  check_scenarios.py / check_metrics.py so catalog semantics are
#  unchanged by the move)

# unique-name fault registrations (duplicates are findings)
FAULT_REGISTER_RE = re.compile(r"faultinject\.register\(\s*[\"']([^\"']+)[\"']")
# idempotent fault names: repeats fine, coverage still required
FAULT_ENSURE_RE = re.compile(
    r"(?:faultinject\.ensure|fail\.fail_point|(?<![.\w])fail_point)"
    r"\(\s*[\"']([^\"']+)[\"']")
_METRIC_DEF_RE = re.compile(
    r"DEFAULT\.(?:counter|gauge|histogram|summary_metric)"
    r"\(\s*[\"'](\w+)[\"'],"
    r"\s*[\"'](\w+)[\"']", re.S)
_TIMELINE_CONST_RE = re.compile(r"EVENT_\w+\s*=\s*[\"']([\w.]+)[\"']")
_TIMELINE_RECORD_RE = re.compile(
    r"record\(\s*[^,()]+,\s*[\"']([\w.]+)[\"']", re.S)
_SPAN_RE = re.compile(
    r"""\btrace\.(?:traced|span)\(\s*["']([a-z0-9_.]+)["']""")
METRIC_WRITE_RE = r"\.(?:inc|set|add|observe|bound)\("
# a metric handed on as a value (``queued_gauge=_m.sidecar_server_
# queue_lanes,``) to the code that writes it — the framed-daemon core
# takes each daemon's metric objects this way — counts as written
METRIC_BIND_RE = r"\s*(?=[,)\n])"


class FileInfo:
    """One source file: relpath (/-separated), raw source, lazy AST."""

    __slots__ = ("rel", "path", "source", "_tree", "_parse_error")

    def __init__(self, rel: str, path: str, source: str):
        self.rel = rel
        self.path = path
        self.source = source
        self._tree: Optional[ast.Module] = None
        self._parse_error: Optional[SyntaxError] = None

    @property
    def tree(self) -> Optional[ast.Module]:
        if self._tree is None and self._parse_error is None:
            try:
                self._tree = ast.parse(self.source)
            except SyntaxError as e:
                self._parse_error = e
        return self._tree

    @property
    def parse_error(self) -> Optional[SyntaxError]:
        self.tree  # force the parse attempt
        return self._parse_error

    def line_of(self, pos: int) -> int:
        return self.source.count("\n", 0, pos) + 1


class ClassInfo:
    """One class definition with its method table and simple attr facts."""

    __slots__ = ("rel", "node", "name", "base_names", "methods",
                 "_attr_ctors")

    def __init__(self, rel: str, node: ast.ClassDef):
        self.rel = rel
        self.node = node
        self.name = node.name
        self.base_names: Set[str] = set()
        for base in node.bases:
            if isinstance(base, ast.Name):
                self.base_names.add(base.id)
            elif isinstance(base, ast.Attribute):
                self.base_names.add(base.attr)
        self.methods: Dict[str, ast.FunctionDef] = {
            n.name: n for n in node.body
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        self._attr_ctors: Optional[Dict[str, str]] = None

    @property
    def attr_ctors(self) -> Dict[str, str]:
        """{attr: CtorName} for every ``self.attr = Name(...)``
        assignment anywhere in the class — the type hints the deep
        analyzers use to follow ``self.attr.method()`` calls."""
        if self._attr_ctors is None:
            out: Dict[str, str] = {}
            for fn in self.methods.values():
                for node in ast.walk(fn):
                    if not isinstance(node, ast.Assign):
                        continue
                    if not isinstance(node.value, ast.Call):
                        continue
                    ctor = node.value.func
                    ctor_name = ctor.id if isinstance(ctor, ast.Name) \
                        else (ctor.attr if isinstance(ctor, ast.Attribute)
                              else "")
                    if not ctor_name:
                        continue
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Attribute) and \
                                isinstance(tgt.value, ast.Name) and \
                                tgt.value.id == "self":
                            out[tgt.attr] = ctor_name
            self._attr_ctors = out
        return self._attr_ctors

    def is_subclass_of(self, name: str, index: "RepoIndex") -> bool:
        """Transitive subclass check by simple name (``name`` may also be
        a suffix match like ``Reactor`` matching ``PexReactor`` bases —
        the same contract tools/check_recv_sync.py used)."""
        seen: Set[str] = set()
        frontier = list(self.base_names)
        while frontier:
            base = frontier.pop()
            if base in seen:
                continue
            seen.add(base)
            if base == name or base.endswith(name):
                return True
            for cls in index.classes_by_name.get(base, []):
                frontier.extend(cls.base_names)
        return False


class RepoIndex:
    def __init__(self, root: str = REPO_ROOT,
                 scan: Tuple[str, ...] = DEFAULT_SCAN):
        self.root = os.path.abspath(root)
        self.scan = tuple(scan)
        self._files: Dict[str, FileInfo] = {}
        self._cache: dict = {}
        for entry in self.scan:
            path = os.path.join(self.root, entry)
            if os.path.isfile(path):
                self._load(path)
                continue
            for dirpath, _dirs, files in os.walk(path):
                for f in sorted(files):
                    if f.endswith(".py"):
                        self._load(os.path.join(dirpath, f))

    def _load(self, path: str) -> None:
        rel = os.path.relpath(path, self.root).replace(os.sep, "/")
        try:
            with open(path, encoding="utf-8") as fh:
                self._files[rel] = FileInfo(rel, path, fh.read())
        except OSError:
            pass

    # ------------------------------------------------------------- files

    def files(self, *prefixes: str) -> Iterator[FileInfo]:
        """Iterate files, optionally filtered to top-level entries or
        path prefixes ("tmtpu", "tmtpu/consensus", "bench.py")."""
        for rel in sorted(self._files):
            fi = self._files[rel]
            if not prefixes:
                yield fi
            elif any(rel == p or rel.startswith(p.rstrip("/") + "/")
                     for p in prefixes):
                yield fi

    def get(self, rel: str) -> Optional[FileInfo]:
        return self._files.get(rel.replace(os.sep, "/"))

    @property
    def importable(self) -> bool:
        """True when this index covers the real repo (rules that must
        import runtime registries — scenario library, sidecar protocol —
        only run then)."""
        try:
            return os.path.samefile(self.root, REPO_ROOT)
        except OSError:
            return False

    # ----------------------------------------------------------- classes

    def classes(self, *prefixes: str) -> List[ClassInfo]:
        key = ("classes", prefixes)
        if key not in self._cache:
            out = []
            for fi in self.files(*prefixes):
                if fi.tree is None:
                    continue
                for node in ast.walk(fi.tree):
                    if isinstance(node, ast.ClassDef):
                        out.append(ClassInfo(fi.rel, node))
            self._cache[key] = out
        return self._cache[key]

    @property
    def classes_by_name(self) -> Dict[str, List[ClassInfo]]:
        if "classes_by_name" not in self._cache:
            out: Dict[str, List[ClassInfo]] = defaultdict(list)
            for cls in self.classes("tmtpu"):
                out[cls.name].append(cls)
            self._cache["classes_by_name"] = dict(out)
        return self._cache["classes_by_name"]

    @property
    def methods_by_name(self) -> Dict[str, List[ClassInfo]]:
        """{method name: [classes defining it]} over tmtpu/ — the
        name-unique call-resolution table the deep analyzers use when a
        receiver's type is unknown."""
        if "methods_by_name" not in self._cache:
            out: Dict[str, List[ClassInfo]] = defaultdict(list)
            for cls in self.classes("tmtpu"):
                for m in cls.methods:
                    out[m].append(cls)
            self._cache["methods_by_name"] = dict(out)
        return self._cache["methods_by_name"]

    # ---------------------------------------------------------- catalogs

    def fault_sites(self) -> Tuple[Dict[str, List[str]],
                                   Dict[str, List[str]]]:
        """(registered, ensured): {site name: ["rel:line", ...]} over
        tmtpu/ — the catalog check_failpoints and the scenario rule
        share. ``register()`` names must be unique; ``ensure``/
        ``fail_point`` names are idempotent but still count toward (and
        are held to) test coverage."""
        if "fault_sites" not in self._cache:
            registered: Dict[str, List[str]] = defaultdict(list)
            ensured: Dict[str, List[str]] = defaultdict(list)
            for fi in self.files("tmtpu"):
                for m in FAULT_REGISTER_RE.finditer(fi.source):
                    registered[m.group(1)].append(
                        f"{fi.rel}:{fi.line_of(m.start())}")
                for m in FAULT_ENSURE_RE.finditer(fi.source):
                    ensured[m.group(1)].append(
                        f"{fi.rel}:{fi.line_of(m.start())}")
            self._cache["fault_sites"] = (dict(registered), dict(ensured))
        return self._cache["fault_sites"]

    def fault_site_names(self) -> Set[str]:
        registered, ensured = self.fault_sites()
        return set(registered) | set(ensured)

    def metric_defs(self) -> Dict[str, str]:
        """{module attr: prometheus name} for every metric bound to a
        module-level name through the DEFAULT registry factories in
        tmtpu/libs/metrics.py — parsed statically (no import), so the
        catalog also works on synthetic trees."""
        if "metric_defs" not in self._cache:
            out: Dict[str, str] = {}
            fi = self.get("tmtpu/libs/metrics.py")
            if fi is not None and fi.tree is not None:
                for node in fi.tree.body:
                    if not (isinstance(node, ast.Assign) and
                            isinstance(node.value, ast.Call)):
                        continue
                    fn = node.value.func
                    if not (isinstance(fn, ast.Attribute) and
                            fn.attr in ("counter", "gauge", "histogram",
                                        "summary_metric")):
                        continue
                    args = node.value.args
                    if len(args) < 2 or not all(
                            isinstance(a, ast.Constant) and
                            isinstance(a.value, str) for a in args[:2]):
                        continue
                    prom = f"tendermint_{args[0].value}_{args[1].value}"
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            out[tgt.id] = prom
            self._cache["metric_defs"] = out
        return self._cache["metric_defs"]

    def metric_names(self) -> Set[str]:
        """The prometheus-name catalog (``tendermint_<sub>_<name>``) the
        scenario metric oracles must resolve against."""
        if "metric_names" not in self._cache:
            fi = self.get("tmtpu/libs/metrics.py")
            src = fi.source if fi is not None else ""
            self._cache["metric_names"] = {
                f"tendermint_{sub}_{name}"
                for sub, name in _METRIC_DEF_RE.findall(src)}
        return self._cache["metric_names"]

    def timeline_events(self) -> Set[str]:
        """Every timeline event name some code path records (EVENT_*
        constants in libs/timeline.py plus dotted literals at record()
        call sites) — what ``timeline_saw`` oracles may wait for."""
        if "timeline_events" not in self._cache:
            events: Set[str] = set()
            for fi in self.files("tmtpu"):
                if fi.rel.endswith("libs/timeline.py"):
                    events.update(_TIMELINE_CONST_RE.findall(fi.source))
                if "timeline" in fi.source:
                    events.update(
                        e for e in _TIMELINE_RECORD_RE.findall(fi.source)
                        if "." in e)
            self._cache["timeline_events"] = events
        return self._cache["timeline_events"]

    def consensus_step_events(self) -> List[str]:
        """The declared timeline.CONSENSUS_STEP_EVENTS tuple, statically."""
        if "step_events" not in self._cache:
            out: List[str] = []
            fi = self.get("tmtpu/libs/timeline.py")
            if fi is not None and fi.tree is not None:
                for node in fi.tree.body:
                    if isinstance(node, ast.Assign) and any(
                            isinstance(t, ast.Name) and
                            t.id == "CONSENSUS_STEP_EVENTS"
                            for t in node.targets):
                        if isinstance(node.value, (ast.Tuple, ast.List)):
                            out = [e.value for e in node.value.elts
                                   if isinstance(e, ast.Constant) and
                                   isinstance(e.value, str)]
            self._cache["step_events"] = out
        return self._cache["step_events"]

    def span_names(self) -> Set[str]:
        """trace.traced("...") / trace.span("...") literals under tmtpu/."""
        if "span_names" not in self._cache:
            names: Set[str] = set()
            for fi in self.files("tmtpu"):
                names.update(_SPAN_RE.findall(fi.source))
            self._cache["span_names"] = names
        return self._cache["span_names"]

    def timeline_record_sites(self) -> Dict[str, str]:
        """{event name: first rel recording it} at record() call sites."""
        if "timeline_record_sites" not in self._cache:
            out: Dict[str, str] = {}
            for fi in self.files("tmtpu"):
                for ev in re.findall(
                        r"""\b(?:timeline|_tl)\.record\(\s*[^,]+,"""
                        r"""\s*["']([a-z0-9_.]+)["']""", fi.source):
                    out.setdefault(ev, fi.rel)
            self._cache["timeline_record_sites"] = out
        return self._cache["timeline_record_sites"]

    def config_knobs(self) -> Dict[str, Set[str]]:
        """{ConfigClass: {attr, ...}} — every ``self.x = ...`` knob in
        tmtpu/config/config.py's *Config classes. Rules (and docs
        tooling) resolve config-key references against this instead of
        re-parsing the file."""
        if "config_knobs" not in self._cache:
            out: Dict[str, Set[str]] = {}
            fi = self.get("tmtpu/config/config.py")
            if fi is not None and fi.tree is not None:
                for node in fi.tree.body:
                    if not (isinstance(node, ast.ClassDef) and
                            node.name.endswith("Config")):
                        continue
                    attrs: Set[str] = set()
                    for sub in ast.walk(node):
                        if isinstance(sub, ast.Attribute) and \
                                isinstance(sub.value, ast.Name) and \
                                sub.value.id == "self" and \
                                isinstance(sub.ctx, ast.Store):
                            attrs.add(sub.attr)
                    out[node.name] = attrs
            self._cache["config_knobs"] = out
        return self._cache["config_knobs"]

    def test_corpus(self) -> str:
        """Concatenated tests/ source — coverage checks grep this."""
        if "test_corpus" not in self._cache:
            self._cache["test_corpus"] = "\n".join(
                fi.source for fi in self.files("tests"))
        return self._cache["test_corpus"]


_default: Optional[RepoIndex] = None


def default_index() -> RepoIndex:
    """The memoized real-repo index every entry point shares."""
    global _default
    if _default is None:
        _default = RepoIndex(REPO_ROOT)
    return _default


def reset_default_index() -> None:
    """Drop the memoized index (tests that mutate the tree call this)."""
    global _default
    _default = None

"""Registry-drift pass: every name-like string literal must resolve to
a declared registry entry and (for knobs/metrics) appear in docs
(ISSUE 11 tentpole pass 3).

The repo grew five string namespaces with no single source of truth:
``bigdl.*`` conf keys, ``bigdl_*`` metric series, fault-injection
sites, trace span names and pytest markers. Each now has a declared
registry in :mod:`bigdl_tpu.analysis.registries`; this pass verifies,
without importing any of the analyzed code:

- ``conf-unregistered`` / ``metric-unregistered`` / ``span-unregistered``
  / ``site-unregistered`` / ``marker-unregistered`` — a literal used in
  code that no registry entry covers (typo, or an undeclared knob);
- ``conf-undocumented`` / ``metric-undocumented`` — a registered,
  in-use conf key or metric series whose name appears in none of the
  user-facing docs (README.md, docs/*.md);
- ``conf-dead`` / ``metric-dead`` / ``span-dead`` / ``marker-dead`` —
  a registered entry no code uses any more;
- ``registry-source-drift`` — the registries must mirror their
  in-tree sources exactly: ``conf._DEFAULTS`` keys ⊆ CONF_KEYS,
  ``faults.SITES`` == FAULT_SITES, and the markers conftest declares ==
  PYTEST_MARKERS.

Scopes: literals are collected from ``bigdl_tpu/`` and ``tools/``
(docstrings excluded); usage for dead-entry checks additionally counts
``tests/`` and ``examples/``; doc presence is a plain substring scan
over README.md + docs/*.md.
"""

from __future__ import annotations

import ast
import fnmatch
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from . import registries
from .core import Finding, ProjectIndex

_CONF_RE = re.compile(r"^bigdl(\.[a-z0-9_]+)+$")
_SITE_RE = re.compile(r"^[a-z0-9_]+(\.[a-z0-9_*?]+)+$")
_METRIC_DECL_FUNCS = ("counter", "gauge", "histogram", "sketch",
                      "_count")
_METRIC_USE_FUNCS = _METRIC_DECL_FUNCS + ("sample_value", "get")
# ``_phase`` is LLMServer's wrapper around ``span`` for the pass phases
_SPAN_FUNCS = ("span", "add_complete", "_phase")

#: pytest's own marks plus plugin marks in use — never registry entries
_BUILTIN_MARKS = frozenset({
    "parametrize", "skip", "skipif", "xfail", "usefixtures",
    "filterwarnings", "timeout", "tryfirst", "trylast"})

#: files whose literals ARE the source tables (excluded from usage
#: scans so the mirror itself never counts as a consumer)
_SOURCE_FILES = ("bigdl_tpu/utils/conf.py",
                 "bigdl_tpu/reliability/faults.py",
                 "bigdl_tpu/analysis/registries.py")


class _Literals:
    """Name-like literals harvested from one tree scan."""

    def __init__(self):
        # name -> (file, line) of first sighting
        self.conf: Dict[str, Tuple[str, int]] = {}
        self.metric_decl: Dict[str, Tuple[str, int]] = {}
        self.metric_use: Dict[str, Tuple[str, int]] = {}
        self.span: Dict[str, Tuple[str, int]] = {}
        self.span_prefix: Dict[str, Tuple[str, int]] = {}
        self.site_inject: Dict[str, Tuple[str, int]] = {}
        self.site_inject_prefix: Dict[str, Tuple[str, int]] = {}
        self.site_arm: Dict[str, Tuple[str, int]] = {}
        self.marks: Dict[str, Tuple[str, int]] = {}


def _first(d: Dict[str, Tuple[str, int]], key: str, file: str, line: int):
    d.setdefault(key, (file, line))


def _callee(func: ast.AST) -> str:
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Attribute):
        return func.attr
    return ""


def _docstring_nodes(mod) -> Set[int]:
    """id()s of Constant nodes that are docstrings — excluded from the
    literal scan (prose mentioning a key is not a use of it)."""
    out: Set[int] = set()
    for node in mod.walk(mod.tree):
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef)):
            body = getattr(node, "body", [])
            if body and isinstance(body[0], ast.Expr) and \
                    isinstance(body[0].value, ast.Constant) and \
                    isinstance(body[0].value.value, str):
                out.add(id(body[0].value))
    return out


#: every first-sighting dict on _Literals, for the per-module merge
_LIT_FIELDS = ("conf", "metric_decl", "metric_use", "span",
               "span_prefix", "site_inject", "site_inject_prefix",
               "site_arm", "marks")


def _module_literals(mod, rel: str) -> _Literals:
    """One module's literal harvest, cached on the ModuleInfo — the
    enforce and usage indexes are filtered views over the SAME parsed
    modules, so without the cache every shared module is scanned
    twice per run."""
    cached = getattr(mod, "_registry_literals", None)
    if cached is not None:
        return cached
    lits = _Literals()
    docstrings = _docstring_nodes(mod)
    is_source = rel in _SOURCE_FILES
    for node in mod.walk(mod.tree):
        if isinstance(node, ast.Constant) and \
                isinstance(node.value, str) and \
                id(node) not in docstrings and not is_source:
            if _CONF_RE.match(node.value):
                _first(lits.conf, node.value, rel, node.lineno)
        if isinstance(node, ast.Call):
            _scan_call(node, rel, lits)
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Attribute) and \
                isinstance(node.value.value, ast.Name) and \
                node.value.value.id == "pytest" and \
                node.value.attr == "mark":
            _first(lits.marks, node.attr, rel, node.lineno)
    mod._registry_literals = lits
    return lits


def collect_literals(index: ProjectIndex) -> _Literals:
    lits = _Literals()
    for rel, mod in index.modules.items():
        mlits = _module_literals(mod, rel)
        for fname in _LIT_FIELDS:
            dst = getattr(lits, fname)
            for key, where in getattr(mlits, fname).items():
                _first(dst, key, *where)
    return lits


def _scan_call(node: ast.Call, rel: str, lits: _Literals):
    callee = _callee(node.func)
    arg0 = node.args[0] if node.args else None
    # pytest.mark via pytestmark lists / config.addinivalue_line
    if callee == "addinivalue_line" and len(node.args) == 2 and \
            isinstance(arg0, ast.Constant) and arg0.value == "markers" \
            and isinstance(node.args[1], ast.Constant):
        name = str(node.args[1].value).split(":", 1)[0].strip()
        _first(lits.marks, name, rel, node.lineno)
        return
    if arg0 is None:
        return
    if isinstance(arg0, ast.Constant) and isinstance(arg0.value, str):
        val = arg0.value
        if callee in _METRIC_DECL_FUNCS and (
                val.startswith("bigdl_") or
                val in registries.METRIC_EXTRA_NAMES):
            _first(lits.metric_decl, val, rel, node.lineno)
            _first(lits.metric_use, val, rel, node.lineno)
        elif callee in _METRIC_USE_FUNCS and val.startswith("bigdl_"):
            _first(lits.metric_use, val, rel, node.lineno)
        if callee in _SPAN_FUNCS and "/" in val:
            _first(lits.span, val, rel, node.lineno)
        if callee == "inject" and _SITE_RE.match(val):
            _first(lits.site_inject, val, rel, node.lineno)
        if callee == "add" and _SITE_RE.match(val):
            _first(lits.site_arm, val, rel, node.lineno)
    elif isinstance(arg0, ast.JoinedStr) and arg0.values and \
            isinstance(arg0.values[0], ast.Constant):
        prefix = str(arg0.values[0].value)
        if callee == "inject":
            _first(lits.site_inject_prefix, prefix, rel, node.lineno)
        elif callee in _SPAN_FUNCS:
            _first(lits.span_prefix, prefix, rel, node.lineno)


# ---------------------------------------------------------------------------
# source tables (AST-parsed, never imported)
# ---------------------------------------------------------------------------

def parse_conf_defaults(root: str) -> Optional[Set[str]]:
    """``None`` when conf.py is absent (fixture trees): a missing
    source file skips the mirror check instead of faking drift."""
    path = os.path.join(root, "bigdl_tpu/utils/conf.py")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.AnnAssign) and \
                isinstance(node.target, ast.Name) and \
                node.target.id == "_DEFAULTS" and \
                isinstance(node.value, ast.Dict):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)}
        if isinstance(node, ast.Assign) and \
                isinstance(node.targets[0], ast.Name) and \
                node.targets[0].id == "_DEFAULTS" and \
                isinstance(node.value, ast.Dict):
            return {k.value for k in node.value.keys
                    if isinstance(k, ast.Constant)}
    return set()


def parse_fault_sites(root: str) -> Optional[Set[str]]:
    path = os.path.join(root, "bigdl_tpu/reliability/faults.py")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, (ast.Assign, ast.AnnAssign)):
            tgt = node.targets[0] if isinstance(node, ast.Assign) \
                else node.target
            if isinstance(tgt, ast.Name) and tgt.id == "SITES" and \
                    isinstance(node.value, (ast.Tuple, ast.List)):
                return {e.value for e in node.value.elts
                        if isinstance(e, ast.Constant)}
    return set()


def parse_conftest_markers(root: str) -> Optional[Set[str]]:
    path = os.path.join(root, "tests/conftest.py")
    if not os.path.exists(path):
        return None
    with open(path) as f:
        tree = ast.parse(f.read())
    out: Set[str] = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and \
                _callee(node.func) == "addinivalue_line" and \
                len(node.args) == 2 and \
                isinstance(node.args[0], ast.Constant) and \
                node.args[0].value == "markers" and \
                isinstance(node.args[1], ast.Constant):
            out.add(str(node.args[1].value).split(":", 1)[0].strip())
    return out


class DocIndex:
    """User-facing doc text + the names it covers. The docs use brace
    shorthand (``bigdl_kvcache_{hits,misses}_total``,
    ``bigdl.llm.retry_after.{base,max}``) — ``covers`` expands those
    groups so shorthand counts as documentation."""

    def __init__(self, text: str):
        self.text = text
        self.expanded: Set[str] = set()
        # brace groups may wrap across doc line breaks ([^{}] spans \n)
        for token in re.findall(r"[\w.]*(?:\{[^{}]*\}[\w.]*)+", text):
            self.expanded.update(_expand_braces(token))

    def covers(self, name: str) -> bool:
        return name in self.text or name in self.expanded


def _expand_braces(token: str, limit: int = 256) -> List[str]:
    out = [token]
    for _ in range(8):              # nested/multiple groups
        nxt: List[str] = []
        changed = False
        for t in out:
            m = re.search(r"\{([^{}]*)\}", t)
            if m is None:
                nxt.append(t)
                continue
            changed = True
            for alt in m.group(1).split(","):
                nxt.append(t[:m.start()] + alt.strip() + t[m.end():])
            if len(nxt) > limit:
                return nxt[:limit]
        out = nxt
        if not changed:
            break
    return out


def load_docs(root: str) -> DocIndex:
    """The user-facing docs the drift pass checks names against."""
    chunks: List[str] = []
    for rel in ["README.md"] + sorted(
            os.path.join("docs", f)
            for f in (os.listdir(os.path.join(root, "docs"))
                      if os.path.isdir(os.path.join(root, "docs"))
                      else [])
            if f.endswith(".md")):
        path = os.path.join(root, rel)
        if os.path.exists(path):
            with open(path) as f:
                chunks.append(f.read())
    return DocIndex("\n".join(chunks))


# ---------------------------------------------------------------------------
# the pass
# ---------------------------------------------------------------------------

def run_registry_pass(index: ProjectIndex,
                      usage_index: Optional[ProjectIndex] = None,
                      root: Optional[str] = None) -> List[Finding]:
    """``index`` scopes *enforcement* (unregistered literals);
    ``usage_index`` (a superset scan incl. tests/examples) scopes
    *dead-entry* checks so a knob exercised only by tests is not
    reported dead. ``root`` locates conf.py/faults.py/conftest/docs."""
    root = root or index.root
    lits = collect_literals(index)
    use = collect_literals(usage_index) if usage_index is not None \
        else lits
    docs = load_docs(root)
    findings: List[Finding] = []

    # -- conf keys -----------------------------------------------------------
    for key, (file, line) in sorted(lits.conf.items()):
        if key not in registries.CONF_KEYS:
            findings.append(Finding(
                rule="conf-unregistered", file=file, line=line, key=key,
                message=f"conf key {key!r} is not in "
                        f"analysis/registries.py CONF_KEYS (typo, or an "
                        f"undeclared knob)"))
        elif not docs.covers(key):
            findings.append(Finding(
                rule="conf-undocumented", file=file, line=line, key=key,
                message=f"conf key {key!r} appears in no user-facing "
                        f"doc (README.md, docs/*.md)"))
    for key in sorted(registries.CONF_KEYS):
        if key not in use.conf:
            src_file = "bigdl_tpu/analysis/registries.py"
            findings.append(Finding(
                rule="conf-dead", file=src_file, line=0, key=key,
                message=f"registered conf key {key!r} is used nowhere "
                        f"in bigdl_tpu/tools/tests/examples — delete "
                        f"the registration or the knob is vestigial"))

    # -- metrics -------------------------------------------------------------
    for name, (file, line) in sorted(lits.metric_decl.items()):
        if name not in registries.METRICS:
            findings.append(Finding(
                rule="metric-unregistered", file=file, line=line,
                key=name,
                message=f"metric series {name!r} is declared in code "
                        f"but not in analysis/registries.py METRICS"))
        elif not docs.covers(name):
            findings.append(Finding(
                rule="metric-undocumented", file=file, line=line,
                key=name,
                message=f"metric series {name!r} appears in no "
                        f"user-facing doc (README.md, docs/*.md)"))
    for name in sorted(registries.METRICS):
        if name not in use.metric_decl and name not in use.metric_use:
            findings.append(Finding(
                rule="metric-dead", file="bigdl_tpu/analysis/registries.py",
                line=0, key=name,
                message=f"registered metric {name!r} is declared "
                        f"nowhere in code — misspelled or removed"))

    # -- spans ---------------------------------------------------------------
    for name, (file, line) in sorted(lits.span.items()):
        if name not in registries.SPAN_NAMES:
            findings.append(Finding(
                rule="span-unregistered", file=file, line=line, key=name,
                message=f"trace span {name!r} is not in "
                        f"analysis/registries.py SPAN_NAMES"))
    for name in sorted(registries.SPAN_NAMES):
        if name not in use.span and not any(
                name.startswith(p) for p in use.span_prefix):
            findings.append(Finding(
                rule="span-dead", file="bigdl_tpu/analysis/registries.py",
                line=0, key=name,
                message=f"registered span {name!r} is emitted nowhere"))

    # -- fault sites ---------------------------------------------------------
    for name, (file, line) in sorted(lits.site_inject.items()):
        if name not in registries.FAULT_SITES:
            findings.append(Finding(
                rule="site-unregistered", file=file, line=line, key=name,
                message=f"fault site {name!r} injected in code but not "
                        f"in analysis/registries.py FAULT_SITES"))
    for prefix, (file, line) in sorted(lits.site_inject_prefix.items()):
        if not any(s.startswith(prefix) for s in registries.FAULT_SITES):
            findings.append(Finding(
                rule="site-unregistered", file=file, line=line,
                key=f"{prefix}*",
                message=f"dynamic fault site prefix {prefix!r} matches "
                        f"no registered FAULT_SITES entry"))
    for pat, (file, line) in sorted(use.site_arm.items()):
        if not any(fnmatch.fnmatch(s, pat)
                   for s in registries.FAULT_SITES):
            findings.append(Finding(
                rule="site-unregistered", file=file, line=line, key=pat,
                message=f"fault plan arms {pat!r} which matches no "
                        f"registered site — the rule can never fire"))

    # -- markers -------------------------------------------------------------
    for name, (file, line) in sorted(use.marks.items()):
        if name not in registries.PYTEST_MARKERS and \
                name not in _BUILTIN_MARKS:
            findings.append(Finding(
                rule="marker-unregistered", file=file, line=line,
                key=name,
                message=f"pytest marker {name!r} used but not in "
                        f"analysis/registries.py PYTEST_MARKERS"))

    # -- registry <-> source mirrors -----------------------------------------
    defaults = parse_conf_defaults(root)
    for key in sorted((defaults or set()) - set(registries.CONF_KEYS)):
        findings.append(Finding(
            rule="registry-source-drift", file="bigdl_tpu/utils/conf.py",
            line=0, key=f"conf:{key}",
            message=f"conf._DEFAULTS key {key!r} missing from "
                    f"CONF_KEYS registry"))
    sites = parse_fault_sites(root)
    for s in sorted(sites ^ set(registries.FAULT_SITES)
                    if sites is not None else ()):
        where = "faults.SITES" if s in sites else "FAULT_SITES registry"
        findings.append(Finding(
            rule="registry-source-drift",
            file="bigdl_tpu/reliability/faults.py", line=0,
            key=f"site:{s}",
            message=f"fault site {s!r} present only in {where} — the "
                    f"two must mirror exactly"))
    markers = parse_conftest_markers(root)
    for m in sorted(markers ^ set(registries.PYTEST_MARKERS)
                    if markers is not None else ()):
        where = "tests/conftest.py" if m in markers \
            else "PYTEST_MARKERS registry"
        findings.append(Finding(
            rule="registry-source-drift", file="tests/conftest.py",
            line=0, key=f"marker:{m}",
            message=f"pytest marker {m!r} present only in {where} — "
                    f"the two must mirror exactly"))
    return findings

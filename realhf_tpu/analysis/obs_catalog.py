"""obs-catalog-drift checker: the metric catalog matches the code.

``docs/observability.md`` carries the catalog every dashboard,
recording rule, and alert is written against. Because the metrics
registry creates metrics lazily, catalog drift never fails fast -- a
renamed counter silently splits a series, an undocumented one is
invisible to operators. This project checker diffs BOTH directions:

- a **literal** metric name at an instrumentation call site
  (``inc`` / ``set_gauge`` / ``observe`` / ``observe_hist`` /
  ``event`` or a registry constructor), or handed to a table as a
  ``token_counter="..."`` keyword (a record that names the counter its
  caller feeds, ``models/operators.py``), that does not appear in
  the catalog -> finding at the call site;
- a catalog row naming a metric that no call site emits -> finding
  at the doc line.

Catalog rows may use brace alternation (``serving_{a,b}_total``
expands to both names) and label sets (a trailing ``{label,...}``
group is dropped). Dynamic names in code are handled two ways:
f-strings with literal head/tail (``f"serving_{key}_total"``) become
patterns that EXCUSE matching doc rows (the doc side can document
what the code spells dynamically), and entirely dynamic names are
out of scope -- the checker never guesses.
"""

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from realhf_tpu.analysis.core import (
    ProjectChecker,
    iter_python_files,
)
from realhf_tpu.analysis.finding import Finding

#: instrumentation entry points taking a literal metric name first
METRIC_CALLS = ("inc", "set_gauge", "observe", "observe_hist",
                "counter", "gauge", "summary", "histogram", "event")
#: keywords of ANY call whose literal value is a metric's name
METRIC_KEYWORDS = ("token_counter",)

_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
_HEADING_RE = re.compile(r"^#{2,}\s")


def expand_doc_token(token: str) -> Set[str]:
    """Expand one backticked catalog token into metric names: a
    trailing ``{...}`` group is a label set (dropped); an interior
    one is brace alternation (each alternative recursively
    expanded)."""
    i = token.find("{")
    if i < 0:
        return {token} if _NAME_RE.match(token) else set()
    depth, j = 0, i
    for j in range(i, len(token)):
        if token[j] == "{":
            depth += 1
        elif token[j] == "}":
            depth -= 1
            if depth == 0:
                break
    if depth != 0:
        return set()
    head, group, tail = token[:i], token[i + 1:j], token[j + 1:]
    if not tail:  # trailing group = label set
        return expand_doc_token(head)
    alts, buf, depth = [], "", 0
    for ch in group:
        if ch == "," and depth == 0:
            alts.append(buf)
            buf = ""
            continue
        if ch == "{":
            depth += 1
        elif ch == "}":
            depth -= 1
        buf += ch
    alts.append(buf)
    out: Set[str] = set()
    for alt in alts:
        # expand the alternative itself first (it may carry its own
        # label group), then splice into head/tail and re-expand
        for mid in expand_doc_token(alt.strip()):
            out |= expand_doc_token(head + mid + tail)
    return out


def parse_catalog(doc_text: str) -> Dict[str, int]:
    """metric name -> first line number, from the '### Catalog'
    section's table rows."""
    out: Dict[str, int] = {}
    in_catalog = False
    for lineno, line in enumerate(doc_text.splitlines(), start=1):
        if line.strip().startswith("### Catalog"):
            in_catalog = True
            continue
        if in_catalog and _HEADING_RE.match(line):
            break
        if not in_catalog or not line.lstrip().startswith("|"):
            continue
        first_cell = line.split("|")[1] if line.count("|") >= 2 else ""
        for token in re.findall(r"`([^`]+)`", first_cell):
            for name in expand_doc_token(token.strip()):
                out.setdefault(name, lineno)
    return out


def _literal_or_pattern(call: ast.Call
                        ) -> Tuple[Optional[str], Optional[str]]:
    """(literal name, regex pattern) of the call's first arg: a
    constant yields a literal, an f-string with constant fragments a
    pattern, anything else (None, None)."""
    if not call.args:
        return None, None
    arg = call.args[0]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value, None
    if isinstance(arg, ast.JoinedStr):
        parts = []
        for v in arg.values:
            if isinstance(v, ast.Constant):
                parts.append(re.escape(str(v.value)))
            else:
                parts.append(r"[a-z0-9_]+")
        return None, "".join(parts)
    return None, None


class ObsCatalogChecker(ProjectChecker):
    name = "obs-catalog"
    cacheable = True

    def __init__(self, package: str = "realhf_tpu",
                 doc_path: str = os.path.join("docs",
                                              "observability.md")):
        self.package = package
        self.doc_path = doc_path

    def stamp_extra(self, root: str) -> str:
        try:
            with open(os.path.join(root, self.doc_path),
                      encoding="utf-8") as f:
                import hashlib
                return hashlib.sha1(f.read().encode()).hexdigest()
        except OSError:
            return "missing"

    # ------------------------------------------------------------------
    def check_project(self, root: str) -> List[Finding]:
        doc_abs = os.path.join(root, self.doc_path)
        pkg_abs = os.path.join(root, self.package)
        if not os.path.exists(doc_abs) or not os.path.isdir(pkg_abs):
            return []  # fixture trees without the doc: nothing to pin
        with open(doc_abs, encoding="utf-8") as f:
            doc_text = f.read()
        doc_names = parse_catalog(doc_text)
        doc_rel = self.doc_path.replace(os.sep, "/")

        #: literal name -> first (relpath, line, col, symbol)
        code_names: Dict[str, Tuple] = {}
        patterns: List[str] = []
        for path in iter_python_files([pkg_abs], root):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            try:
                with open(path, encoding="utf-8") as f:
                    tree = ast.parse(f.read())
            except (OSError, SyntaxError, ValueError):
                continue
            from realhf_tpu.analysis.core import enclosing_symbols
            symbols = enclosing_symbols(tree)
            for node in ast.walk(tree):
                if not isinstance(node, ast.Call):
                    continue
                for kw in node.keywords:
                    if kw.arg in METRIC_KEYWORDS and isinstance(
                            kw.value, ast.Constant) and isinstance(
                            kw.value.value, str):
                        code_names.setdefault(
                            kw.value.value,
                            (rel, kw.value.lineno, kw.value.col_offset,
                             symbols.get(node, "")))
                func = node.func
                attr = func.attr if isinstance(func, ast.Attribute) \
                    else (func.id if isinstance(func, ast.Name)
                          else "")
                if attr not in METRIC_CALLS:
                    continue
                literal, pattern = _literal_or_pattern(node)
                if pattern is not None:
                    patterns.append(pattern)
                if literal is None or not _NAME_RE.match(literal):
                    continue
                code_names.setdefault(
                    literal, (rel, node.lineno, node.col_offset,
                              symbols.get(node, "")))

        findings: List[Finding] = []
        for name in sorted(code_names):
            if name in doc_names:
                continue
            rel, line, col, symbol = code_names[name]
            findings.append(Finding(
                checker=self.name, code="obs-catalog-drift",
                path=rel, line=line, col=col,
                message=(f"metric `{name}` is emitted here but "
                         f"missing from the {doc_rel} catalog -- "
                         "add a row (operators only see documented "
                         "series)"),
                symbol=symbol))
        compiled = [re.compile(p + r"$") for p in patterns]
        for name in sorted(doc_names):
            if name in code_names:
                continue
            if any(p.match(name) for p in compiled):
                continue  # spelled dynamically in code
            findings.append(Finding(
                checker=self.name, code="obs-catalog-drift",
                path=doc_rel, line=doc_names[name], col=0,
                message=(f"catalog row names metric `{name}` but no "
                         "call site emits it -- stale doc or renamed "
                         "metric (dashboards built on it see no "
                         "data)"),
                symbol=name))
        return findings

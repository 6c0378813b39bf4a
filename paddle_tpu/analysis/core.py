"""graftlint core: the rule-based static-analysis framework.

CLAUDE.md's hard-won architecture invariants (the single-chokepoint
autograd rule, the round-11 thread-local grad-mode incident, the Mosaic
compile hazards, the HTTP-413 jit-constant-capture class, the round-3
dist_spec passthrough, the serving engine lock discipline, and the env-knob registry) exist as prose that a
future builder may not read.  This package turns each of them into an
enforced AST check — the Paddle-reference idea of framework
self-policing (op-registry checks, static-graph pass validators) applied
to this repo's own source tree.

Deliberately jax-free: `tools/lint.py` loads this package without
executing `paddle_tpu/__init__` (a lint run must not claim the chip or
pay a backend start-up), so nothing here may import jax or any sibling
paddle_tpu subpackage.

Concepts
--------
- :class:`Rule` — one invariant; ``applies(ctx)`` scopes it by path,
  ``check(ctx)`` yields :class:`Finding`\\ s from the file's AST.
- :class:`FileContext` — parsed file handed to rules: source, lines,
  AST annotated with parent links and decorator markers, plus the
  :class:`Project` for repo-level lookups (the env-knob registry).
- Suppressions — ``# graftlint: disable=<rule>[,<rule>]  (reason)``
  trailing a flagged line (or a standalone comment on the line above).
  ``disable-file=`` in the file head suppresses for the whole file.
  An EMPTY reason is itself a finding (``bad-suppression``): every
  suppression must say why (ISSUE-6 acceptance rule).
- Baseline — a checked-in JSON file of grandfathered findings, matched
  by (rule, path, stripped source line) so plain line-number churn does
  not resurrect them.  Baseline entries also require a non-empty
  ``reason``.
"""
from __future__ import annotations

import ast
import dataclasses
import io
import json
import os
import re
import tokenize

__all__ = [
    "Finding", "Rule", "FileContext", "Project", "run_paths",
    "run_source", "load_baseline", "save_baseline", "apply_baseline",
    "iter_py_files", "dotted_name", "BAD_SUPPRESSION", "BAD_BASELINE",
]

BAD_SUPPRESSION = "bad-suppression"
BAD_BASELINE = "bad-baseline"

_DISABLE_RE = re.compile(
    r"#\s*graftlint:\s*disable(?P<whole>-file)?="
    r"(?P<rules>[A-Za-z0-9_,-]+)\s*(?:\((?P<reason>[^)]*)\))?")


@dataclasses.dataclass
class Finding:
    """One rule violation at file:line.  ``snippet`` (the stripped
    source line) is the baseline fingerprint — stable across pure
    line-number churn."""
    rule: str
    path: str
    line: int
    message: str
    snippet: str = ""

    def key(self):
        return (self.rule, self.path, self.snippet)

    def to_json(self):
        return dataclasses.asdict(self)

    def __str__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


class Project:
    """Repo-level context shared across files (lazy, cached)."""

    def __init__(self, root):
        self.root = os.path.abspath(root) if root else None
        self._knobs = None

    def knob_registry(self):
        """Set of PADDLE_TPU_* knob names listed in docs/ENV_KNOBS.md
        (first table column).  Empty set when the doc is missing — the
        env-knob rule then flags every knob, which is the honest signal
        to run ``tools/lint.py --gen-knobs``."""
        if self._knobs is None:
            self._knobs = set()
            if self.root:
                doc = os.path.join(self.root, "docs", "ENV_KNOBS.md")
                if os.path.exists(doc):
                    with open(doc, encoding="utf-8") as f:
                        text = f.read()
                    self._knobs = set(
                        re.findall(r"^\|\s*`(PADDLE_TPU_[A-Z0-9_]+)`",
                                   text, re.M))
        return self._knobs


class FileContext:
    """A parsed source file as rules see it."""

    def __init__(self, relpath, source, project=None):
        self.relpath = relpath.replace(os.sep, "/")
        self.source = source
        self.lines = source.splitlines()
        self.project = project if project is not None else Project(None)
        self.tree = ast.parse(source)
        self._annotate()

    def _annotate(self):
        """Parent links + decorator-subtree markers, once per file."""
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                for dec in node.decorator_list:
                    for sub in ast.walk(dec):
                        sub._gl_in_decorator = True
            for child in ast.iter_child_nodes(node):
                child._gl_parent = node

    def snippet(self, line):
        if 1 <= line <= len(self.lines):
            return self.lines[line - 1].strip()
        return ""

    def finding(self, rule, node_or_line, message):
        line = node_or_line if isinstance(node_or_line, int) \
            else getattr(node_or_line, "lineno", 1)
        return Finding(rule=rule, path=self.relpath, line=line,
                       message=message, snippet=self.snippet(line))

    # -- AST helpers shared by the rules -----------------------------------
    def parent(self, node):
        return getattr(node, "_gl_parent", None)

    def ancestors(self, node):
        p = self.parent(node)
        while p is not None:
            yield p
            p = self.parent(p)

    def enclosing_function(self, node):
        for a in self.ancestors(node):
            if isinstance(a, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return a
        return None

    def in_decorator(self, node):
        return getattr(node, "_gl_in_decorator", False)

    def functions_by_name(self):
        """Every FunctionDef in the module keyed by name (methods
        included; later defs win — good enough for target resolution)."""
        out = {}
        for node in ast.walk(self.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                out[node.name] = node
        return out


def dotted_name(node):
    """'jax.lax.fori_loop' for an Attribute/Name chain, else None."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class Rule:
    """Base class: subclass with ``id``, ``description`` and ``check``."""

    id = ""
    description = ""

    def applies(self, ctx: FileContext) -> bool:
        return True

    def check(self, ctx: FileContext):
        raise NotImplementedError
        yield  # pragma: no cover


# ---------------------------------------------------------------------------
# Suppressions

def _parse_suppressions(ctx, known_ids):
    """Returns (line -> set(rule_ids), file_wide set, bad findings).

    A trailing comment suppresses its own line; a standalone comment
    line suppresses the NEXT line (so multi-line calls annotate the
    ``pl.BlockSpec(`` line or the line above it).  Real COMMENT tokens
    only — directive-looking text inside string literals (test
    fixtures, docs) is ignored.
    """
    per_line: dict[int, set] = {}
    file_wide: set = set()
    bad = []
    comments = []
    try:
        for tok in tokenize.generate_tokens(
                io.StringIO(ctx.source).readline):
            if tok.type == tokenize.COMMENT:
                comments.append((tok.start[0], tok.start[1], tok.string))
    except (tokenize.TokenError, IndentationError):
        pass  # ast.parse succeeded, so this is practically unreachable
    for i, col, comment in comments:
        m = _DISABLE_RE.search(comment)
        if not m:
            continue
        raw = ctx.lines[i - 1] if i <= len(ctx.lines) else ""
        rules = {r.strip() for r in m.group("rules").split(",") if r.strip()}
        reason = (m.group("reason") or "").strip()
        if not reason:
            bad.append(ctx.finding(
                BAD_SUPPRESSION, i,
                "graftlint disable without a reason — write "
                "`# graftlint: disable=<rule>  (why this is intended)`"))
        unknown = rules - set(known_ids)
        if unknown:
            bad.append(ctx.finding(
                BAD_SUPPRESSION, i,
                f"graftlint disable names unknown rule(s) "
                f"{sorted(unknown)} — typo? known: {sorted(known_ids)}"))
        if m.group("whole"):
            file_wide |= rules
            continue
        standalone = raw[:col].strip() == ""
        target = i + 1 if standalone else i
        per_line.setdefault(target, set()).update(rules)
        # a standalone disable also covers its own line so a finding
        # anchored to the comment itself (rare) stays suppressible
        if standalone:
            per_line.setdefault(i, set()).update(rules)
    return per_line, file_wide, bad


# ---------------------------------------------------------------------------
# Runner

def check_context(ctx, rules):
    """Run rules over one FileContext, honoring suppressions.  Returns
    (kept findings, suppressed count); bad-suppression findings are
    included in the kept list."""
    known = [r.id for r in rules]
    per_line, file_wide, bad = _parse_suppressions(ctx, known)
    kept, suppressed = list(bad), 0
    for rule in rules:
        if not rule.applies(ctx):
            continue
        for f in rule.check(ctx):
            if f.rule in file_wide or f.rule in per_line.get(f.line, ()):
                suppressed += 1
                continue
            kept.append(f)
    return kept, suppressed


def run_source(source, relpath, rules, project=None):
    """Test/driver helper: lint one in-memory source blob."""
    ctx = FileContext(relpath, source, project)
    return check_context(ctx, rules)[0]


def iter_py_files(paths, root):
    """Yield repo-relative posix paths of .py files under ``paths``
    (files or directories, resolved against ``root``)."""
    seen = set()
    for p in paths:
        full = p if os.path.isabs(p) else os.path.join(root, p)
        if os.path.isfile(full):
            cands = [full]
        else:
            cands = []
            for dirpath, dirnames, filenames in os.walk(full):
                dirnames[:] = [d for d in dirnames
                               if d not in ("__pycache__", ".git",
                                            ".bench_r4", "node_modules")]
                cands.extend(os.path.join(dirpath, f)
                             for f in sorted(filenames)
                             if f.endswith(".py"))
        for c in cands:
            rel = os.path.relpath(os.path.abspath(c), root)
            rel = rel.replace(os.sep, "/")
            if rel not in seen:
                seen.add(rel)
                yield rel


def run_paths(paths, root, rules):
    """Lint every .py file under paths.  Returns (findings, stats)."""
    project = Project(root)
    findings, suppressed, files = [], 0, 0
    for rel in iter_py_files(paths, root):
        files += 1
        with open(os.path.join(root, rel), encoding="utf-8") as f:
            source = f.read()
        try:
            ctx = FileContext(rel, source, project)
        except SyntaxError as exc:
            findings.append(Finding(
                rule="syntax-error", path=rel,
                line=getattr(exc, "lineno", 1) or 1,
                message=f"file does not parse: {exc.msg}"))
            continue
        kept, sup = check_context(ctx, rules)
        findings.extend(kept)
        suppressed += sup
    return findings, {"files": files, "suppressed": suppressed}


# ---------------------------------------------------------------------------
# Baseline

def load_baseline(path):
    """Returns (key -> entry dict, bad findings).  Every entry must name
    a rule, a path, a snippet fingerprint, and a non-empty reason."""
    if not path or not os.path.exists(path):
        return {}, []
    with open(path, encoding="utf-8") as f:
        data = json.load(f)
    entries, bad = {}, []
    for e in data.get("entries", []):
        rule = e.get("rule", "")
        reason = (e.get("reason") or "").strip()
        if not rule or not reason:
            bad.append(Finding(
                rule=BAD_BASELINE, path=os.path.basename(path), line=1,
                message=f"baseline entry {e!r} needs both a rule id and "
                        "a non-empty reason"))
            continue
        entries[(rule, e.get("path", ""), e.get("snippet", ""))] = e
    return entries, bad


def save_baseline(path, findings, reason):
    entries = [{"rule": f.rule, "path": f.path, "snippet": f.snippet,
                "reason": reason} for f in findings]
    entries.sort(key=lambda e: (e["path"], e["rule"], e["snippet"]))
    with open(path, "w", encoding="utf-8") as f:
        json.dump({"entries": entries}, f, indent=1)
        f.write("\n")


def apply_baseline(findings, baseline):
    """Split findings into (new, grandfathered-by-baseline)."""
    new, old = [], []
    for f in findings:
        (old if f.key() in baseline else new).append(f)
    return new, old

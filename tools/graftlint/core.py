"""Rule engine: file walking, waiver parsing, finding plumbing.

Everything here is stdlib-only (``ast`` + ``tokenize``): the container
has no network and nothing may be pip-installed, so graftlint carries
zero dependencies by construction.

A rule is an object with:

- ``id``       — stable slug, shown in output and used by ``--rule``;
- ``waiver``   — the token accepted in ``# graftlint: token(reason)``;
- ``doc``      — one-line description for ``--list-rules``;
- ``check(ctx) -> list[Finding]``            (per-file rules), or
- ``check_repo(root, ctxs) -> list[Finding]`` (repo-wide rules);
- ``applies(rel) -> bool``                   (per-file rules only).

Waivers attach to the flagged line or the line directly above it, and
MUST carry a non-empty reason — an empty waiver is converted into its
own unwaived finding, so "silence it later" can never land.
"""

from __future__ import annotations

import ast
import dataclasses
import io
import json
import re
import tokenize
from pathlib import Path

WAIVER_RE = re.compile(r"#\s*graftlint:\s*([a-z_-]+)\(([^()]*)\)")


@dataclasses.dataclass
class Finding:
    rule: str
    path: str  # repo-relative posix path
    line: int
    message: str
    end_line: int = 0  # inclusive; 0 = same as ``line``
    waived: bool = False
    reason: str = ""

    def __post_init__(self):
        if not self.end_line:
            self.end_line = self.line

    def to_dict(self) -> dict:
        return {
            "rule": self.rule, "path": self.path, "line": self.line,
            "message": self.message, "waived": self.waived,
            "reason": self.reason,
        }

    def render(self) -> str:
        tag = f" [waived: {self.reason}]" if self.waived else ""
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}{tag}"


class Context:
    """One parsed source file plus the lookup structures rules share."""

    def __init__(self, root: Path, path: Path, source: str):
        self.root = root
        self.path = path
        self.rel = path.resolve().relative_to(root.resolve()).as_posix()
        self.source = source
        self.lines = source.splitlines()
        self.tree = ast.parse(source, filename=str(path))
        self.waivers = _parse_waivers(source)
        self._parents: dict[ast.AST, ast.AST] | None = None

    @property
    def parents(self) -> dict[ast.AST, ast.AST]:
        if self._parents is None:
            self._parents = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    self._parents[child] = node
        return self._parents

    def ancestors(self, node: ast.AST):
        cur = self.parents.get(node)
        while cur is not None:
            yield cur
            cur = self.parents.get(cur)

    def enclosing_function(self, node: ast.AST):
        for anc in self.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)):
                return anc
        return None


def _parse_waivers(source: str) -> dict[int, list[tuple[str, str]]]:
    """{line: [(token, reason), ...]} from ``# graftlint:`` comments."""
    out: dict[int, list[tuple[str, str]]] = {}
    try:
        toks = tokenize.generate_tokens(io.StringIO(source).readline)
        for tok in toks:
            if tok.type != tokenize.COMMENT:
                continue
            for m in WAIVER_RE.finditer(tok.string):
                out.setdefault(tok.start[0], []).append(
                    (m.group(1), m.group(2).strip())
                )
    except tokenize.TokenError:
        pass
    return out


def callee_name(node: ast.AST) -> str:
    """Best-effort name of a call's target: the attribute/identifier,
    or — for immediately-invoked accessors like
    ``self.programs.paged_chunk_fn()(…)`` (engine/programs.py)
    — the INNER accessor's name (what the repo's rules key on)."""
    func = node.func if isinstance(node, ast.Call) else node
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    if isinstance(func, ast.Call):
        return callee_name(func)
    return ""


def dotted_name(node: ast.AST) -> str:
    """``time.monotonic`` → "time.monotonic" (Attribute chains only)."""
    parts: list[str] = []
    cur = node
    while isinstance(cur, ast.Attribute):
        parts.append(cur.attr)
        cur = cur.value
    if isinstance(cur, ast.Name):
        parts.append(cur.id)
        return ".".join(reversed(parts))
    return ""


def _apply_waivers(findings: list[Finding], ctxs: dict[str, Context],
                   token_for_rule: dict[str, str]) -> list[Finding]:
    out: list[Finding] = []
    for f in findings:
        ctx = ctxs.get(f.path)
        token = token_for_rule.get(f.rule, f.rule)
        waiver = None
        if ctx is not None:
            for ln in range(f.line - 1, f.end_line + 1):
                for tok, reason in ctx.waivers.get(ln, ()):
                    if tok == token:
                        waiver = (ln, reason)
                        break
                if waiver:
                    break
        if waiver is None:
            out.append(f)
        elif not waiver[1]:
            out.append(Finding(
                f.rule, f.path, waiver[0],
                f"waiver `{token}(...)` has no reason — write why this "
                f"site is exempt (finding was: {f.message})",
            ))
        else:
            f.waived = True
            f.reason = waiver[1]
            out.append(f)
    return out


def find_repo_root(start: Path) -> Path:
    cur = start.resolve()
    if cur.is_file():
        cur = cur.parent
    for cand in (cur, *cur.parents):
        if (cand / "pyproject.toml").exists():
            return cand
    return cur


def rules() -> list:
    from .rules import ALL_RULES

    return [cls() for cls in ALL_RULES]


def _collect_files(paths: list[Path]) -> list[Path]:
    files: list[Path] = []
    for p in paths:
        if p.is_dir():
            files.extend(sorted(
                f for f in p.rglob("*.py") if "__pycache__" not in f.parts
            ))
        elif p.suffix == ".py":
            files.append(p)
    return files


def lint_paths(paths: list[str | Path], root: Path | None = None,
               only: str | None = None) -> list[Finding]:
    """Run every rule (or just ``only``) over ``paths``; returns the
    waiver-resolved finding list (waived findings included, marked)."""
    pl = [Path(p) for p in paths]
    if root is None:
        root = find_repo_root(pl[0] if pl else Path.cwd())
    ctxs: dict[str, Context] = {}
    for f in _collect_files(pl):
        try:
            source = f.read_text()
        except (OSError, UnicodeDecodeError):
            continue
        try:
            ctx = Context(root, f, source)
        except SyntaxError as e:
            ctxs_rel = f.resolve().relative_to(root.resolve()).as_posix()
            ctxs[ctxs_rel] = None  # type: ignore[assignment]
            return [Finding("parse", ctxs_rel, e.lineno or 1,
                            f"syntax error: {e.msg}")]
        ctxs[ctx.rel] = ctx

    active = [r for r in rules() if only is None or r.id == only]
    findings: list[Finding] = []
    token_for_rule: dict[str, str] = {}
    for rule in active:
        token_for_rule[rule.id] = getattr(rule, "waiver", rule.id)
        if hasattr(rule, "check_repo"):
            findings.extend(rule.check_repo(root, ctxs))
        else:
            for ctx in ctxs.values():
                if rule.applies(ctx.rel):
                    findings.extend(rule.check(ctx))
    findings = _apply_waivers(findings, ctxs, token_for_rule)
    findings.sort(key=lambda f: (f.path, f.line, f.rule))
    return findings


def lint_source(source: str, rel: str, rule_id: str,
                root: Path | None = None) -> list[Finding]:
    """Test helper: run ONE per-file rule over an in-memory snippet as
    if it lived at ``rel`` inside the repo."""
    root = root or Path.cwd()
    ctx = Context(root, root / rel, source)
    ctx.rel = rel  # honor the caller's virtual location exactly
    rule = next(r for r in rules() if r.id == rule_id)
    if not rule.applies(rel):
        return []
    findings = rule.check(ctx)
    return _apply_waivers(
        findings, {rel: ctx}, {rule.id: getattr(rule, "waiver", rule.id)}
    )


def render_report(findings: list[Finding], as_json: bool) -> tuple[str, int]:
    """(report text, exit code)."""
    unwaived = [f for f in findings if not f.waived]
    if as_json:
        body = json.dumps({
            "findings": [f.to_dict() for f in findings],
            "total": len(findings),
            "waived": len(findings) - len(unwaived),
            "unwaived": len(unwaived),
        }, indent=2)
        return body, (1 if unwaived else 0)
    out = [f.render() for f in findings]
    out.append(
        f"graftlint: {len(findings)} finding(s), "
        f"{len(findings) - len(unwaived)} waived, "
        f"{len(unwaived)} unwaived"
    )
    return "\n".join(out), (1 if unwaived else 0)

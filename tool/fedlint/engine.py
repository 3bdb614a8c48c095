"""fedlint engine: file loading, pragma handling, rule running, CLI.

Pure stdlib / pure AST — no runtime dependency, no imports of the code
under analysis (linting must not require a working jax install, and must
not execute repo code).

Suppression contract (enforced, not advisory): a finding is suppressed
ONLY by an inline pragma **carrying a written reason**::

    some_call()  # fedlint: disable=FED001 — safe: <why>

    # fedlint: disable=FED004,FED007 — <why>   (comment-only line:
    some_call()                                  applies to the NEXT line)

A pragma without a reason is itself an error (FED000) — every exception
to a contract must be visible and justified in the diff.
"""

from __future__ import annotations

import argparse
import ast
import dataclasses
import os
import re
import sys
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

REPO_ROOT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

# The surfaces whose contracts the rules encode (the runtime package
# and the test suite — fixture snippets in tests are plain strings,
# invisible to the AST walk).
DEFAULT_TARGETS = ("rayfed_tpu", "tests")

# Exit codes: distinct so CI logs are unambiguous (2 is argparse usage).
EXIT_OK = 0
EXIT_ERROR = 1
EXIT_FINDINGS = 3

# ``disable=`` then rule codes, then an optional reason after an em/en
# dash or ``--``/``:``.  The reason is REQUIRED for suppression; the
# regex makes it optional only so a reasonless pragma can be reported
# as FED000 instead of silently not matching.
_PRAGMA_RE = re.compile(
    r"#\s*fedlint:\s*disable=([A-Z]{3}\d{3}(?:\s*,\s*[A-Z]{3}\d{3})*)"
    r"(?:\s*(?:—|–|--|:)\s*(?P<reason>\S.*))?"
)
# Anything that *looks* like a fedlint pragma but doesn't parse (typo'd
# code list, wrong keyword) must fail loudly, not silently no-op.
_PRAGMA_LIKE_RE = re.compile(r"#\s*fedlint\b")


@dataclasses.dataclass(frozen=True)
class Finding:
    path: str
    line: int
    col: int
    code: str
    message: str

    def render(self) -> str:
        return f"{self.path}:{self.line}:{self.col} {self.code} {self.message}"

    def sort_key(self) -> Tuple:
        return (self.path, self.line, self.col, self.code)


class _Pragma:
    __slots__ = ("line", "target_line", "codes", "reason")

    def __init__(self, line: int, target_line: int, codes: Tuple[str, ...],
                 reason: Optional[str]) -> None:
        self.line = line
        self.target_line = target_line
        self.codes = codes
        self.reason = reason


class SourceFile:
    """One parsed source file plus its suppression pragmas."""

    def __init__(self, path: str, text: str) -> None:
        self.path = path  # repo-relative, forward slashes (display + scoping)
        self.text = text
        self.tree = ast.parse(text, filename=path)
        self.pragmas: List[_Pragma] = []
        self.pragma_errors: List[Finding] = []
        self._parents: Optional[Dict[ast.AST, ast.AST]] = None
        self._scan_pragmas()

    def _scan_pragmas(self) -> None:
        # Tokenize so only real COMMENT tokens can carry (or trip) a
        # pragma — pragma-shaped text inside string literals/docstrings
        # (e.g. documentation of the syntax itself, or the fixture
        # sources in tests/test_fedlint.py) is data, not a directive.
        if "fedlint" not in self.text:
            return
        import io
        import tokenize

        try:
            tokens = list(tokenize.generate_tokens(
                io.StringIO(self.text).readline
            ))
        except (tokenize.TokenError, IndentationError):  # pragma: no cover
            return  # the file already parsed via ast; defensive only
        for tok in tokens:
            if tok.type != tokenize.COMMENT or "fedlint" not in tok.string:
                continue
            lineno, col = tok.start
            m = _PRAGMA_RE.search(tok.string)
            if not m:
                if _PRAGMA_LIKE_RE.search(tok.string):
                    self.pragma_errors.append(Finding(
                        self.path, lineno, 1, "FED000",
                        "malformed fedlint pragma (expected "
                        "'# fedlint: disable=FED00x — <reason>')",
                    ))
                continue
            codes = tuple(c.strip() for c in m.group(1).split(","))
            reason = m.group("reason")
            comment_only = tok.line[:col].strip() == ""
            target = lineno + 1 if comment_only else lineno
            if not reason:
                self.pragma_errors.append(Finding(
                    self.path, lineno, 1, "FED000",
                    f"pragma disables {', '.join(codes)} without a written "
                    "reason — add one after an em dash: "
                    "'# fedlint: disable=FED00x — <reason>'",
                ))
                continue
            self.pragmas.append(_Pragma(lineno, target, codes, reason))

    def parents(self) -> Dict[ast.AST, ast.AST]:
        if self._parents is None:
            self._parents = {}
            for node in ast.walk(self.tree):
                for child in ast.iter_child_nodes(node):
                    self._parents[child] = node
        return self._parents

    def suppressed(self, finding: Finding) -> bool:
        return any(
            p.target_line == finding.line and finding.code in p.codes
            for p in self.pragmas
        )


class Project:
    """All files under analysis — rules see the whole project at once
    (FED007's lock graph and FED006's declared-key set are global)."""

    def __init__(self, files: Sequence[SourceFile]) -> None:
        self.files = list(files)
        self._by_path = {f.path: f for f in self.files}

    def get(self, path: str) -> Optional[SourceFile]:
        return self._by_path.get(path)


def _iter_py_files(target: str) -> Iterable[str]:
    if os.path.isfile(target):
        yield target
        return
    for dirpath, dirnames, filenames in os.walk(target):
        dirnames[:] = sorted(
            d for d in dirnames
            if d != "__pycache__" and not d.startswith(".")
        )
        for name in sorted(filenames):
            if name.endswith(".py"):
                yield os.path.join(dirpath, name)


def load_project(
    targets: Sequence[str], root: str = REPO_ROOT
) -> Tuple[Project, List[Finding]]:
    """Parse every ``.py`` under ``targets`` (relative to ``root``).

    Returns the project plus parse-failure findings (a file that does
    not parse cannot be checked — that is a finding, not a crash).
    """
    files: List[SourceFile] = []
    errors: List[Finding] = []
    for target in targets:
        abs_target = target if os.path.isabs(target) else os.path.join(root, target)
        if not os.path.exists(abs_target):
            errors.append(Finding(
                target, 1, 1, "FED000", f"target does not exist: {target}"
            ))
            continue
        for path in _iter_py_files(abs_target):
            rel = os.path.relpath(path, root).replace(os.sep, "/")
            with open(path, "r", encoding="utf-8") as fh:
                text = fh.read()
            try:
                files.append(SourceFile(rel, text))
            except SyntaxError as e:
                errors.append(Finding(
                    rel, e.lineno or 1, e.offset or 1, "FED000",
                    f"file does not parse: {e.msg}",
                ))
    return Project(files), errors


def run_rules(
    project: Project,
    rules: Optional[Sequence] = None,
) -> Tuple[List[Finding], List[Finding]]:
    """Run ``rules`` over ``project``.

    Returns ``(visible, suppressed)`` — pragma errors (FED000) are
    always visible; rule findings on a line covered by a well-formed
    pragma naming their code land in ``suppressed``.
    """
    from tool.fedlint.rules import ALL_RULES

    if rules is None:
        rules = ALL_RULES
    visible: List[Finding] = []
    suppressed: List[Finding] = []
    for f in project.files:
        visible.extend(f.pragma_errors)
    for rule in rules:
        for finding in rule.check(project):
            src = project.get(finding.path)
            if src is not None and src.suppressed(finding):
                suppressed.append(finding)
            else:
                visible.append(finding)
    visible.sort(key=Finding.sort_key)
    suppressed.sort(key=Finding.sort_key)
    return visible, suppressed


def lint_sources(
    sources: Dict[str, str], rules: Optional[Sequence] = None
) -> Tuple[List[Finding], List[Finding]]:
    """In-memory entry point (tests): ``{relative_path: source}``."""
    files = [SourceFile(path, text) for path, text in sources.items()]
    return run_rules(Project(files), rules)


def lint_paths(
    targets: Sequence[str] = DEFAULT_TARGETS,
    root: str = REPO_ROOT,
    rules: Optional[Sequence] = None,
) -> Tuple[List[Finding], List[Finding]]:
    project, errors = load_project(targets, root)
    visible, suppressed = run_rules(project, rules)
    visible = sorted(visible + errors, key=Finding.sort_key)
    return visible, suppressed


def _list_rules() -> str:
    from tool.fedlint.rules import ALL_RULES

    out = ["fedlint rule catalog:"]
    for rule in ALL_RULES:
        out.append(f"  {rule.code}  {rule.name}")
        out.append(f"         {rule.summary}")
        out.append(f"         origin: {rule.origin}")
    out.append(
        "  FED000  pragma-hygiene (always on): malformed or reasonless "
        "suppression pragmas."
    )
    return "\n".join(out)


def main(argv: Optional[Sequence[str]] = None) -> int:
    parser = argparse.ArgumentParser(
        prog="python -m tool.fedlint",
        description="Enforce the repo's concurrency/aggregation contracts "
        "as machine-checked AST rules.",
    )
    parser.add_argument(
        "targets", nargs="*", default=list(DEFAULT_TARGETS),
        help="files/directories to lint (default: %(default)s)",
    )
    parser.add_argument(
        "--list-rules", action="store_true",
        help="print the rule catalog and exit",
    )
    parser.add_argument(
        "--select", metavar="CODES", default=None,
        help="comma-separated rule codes to run (default: all)",
    )
    args = parser.parse_args(argv)

    if args.list_rules:
        try:
            print(_list_rules())
        except BrokenPipeError:  # `| head` closing the pipe is fine
            pass
        return EXIT_OK

    from tool.fedlint.rules import ALL_RULES

    rules = ALL_RULES
    if args.select:
        wanted = {c.strip() for c in args.select.split(",")}
        unknown = wanted - {r.code for r in ALL_RULES}
        if unknown:
            print(f"fedlint: unknown rule codes: {sorted(unknown)}",
                  file=sys.stderr)
            return EXIT_ERROR
        rules = [r for r in ALL_RULES if r.code in wanted]

    try:
        visible, suppressed = lint_paths(tuple(args.targets), rules=rules)
    except Exception as e:  # a crash must not read as "clean"
        print(f"fedlint: internal error: {e!r}", file=sys.stderr)
        return EXIT_ERROR

    for finding in visible:
        print(finding.render())
    n_files = len({f.path for f in visible})
    if visible:
        print(
            f"fedlint: {len(visible)} finding(s) in {n_files} file(s)"
            f" ({len(suppressed)} suppressed by pragma)",
            file=sys.stderr,
        )
        return EXIT_FINDINGS
    print(f"fedlint: clean ({len(suppressed)} finding(s) suppressed by pragma)")
    return EXIT_OK

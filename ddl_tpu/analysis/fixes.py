"""Deterministic autofixes for the mechanical lint finding classes.

``ddl_tpu lint --fix`` repairs exactly the findings whose fix is a
mechanical, behavior-preserving rewrite — the classes where the right
edit is implied by the finding itself:

* ``bare-except`` — ``except:`` → ``except Exception:`` (narrower is a
  human judgement; not swallowing SystemExit/KeyboardInterrupt is not);
* ``compat-bypass`` — legacy ``jax.experimental.shard_map`` imports
  rewritten to ``from jax import shard_map``,
  ``check_rep=`` → ``check_vma=``, ``TPUCompilerParams`` →
  ``CompilerParams`` (the ``pjit`` variants need call-site rewrites and
  stay manual);
* ``pspec-hand-rolled`` — a ``PartitionSpec`` literal in a step-factory
  module whose value equals one of the ``parallel/rules.py`` boundary-
  spec constants is replaced by that constant's name, and the import is
  added/extended;
* ``obs-event-unregistered`` — the emitted-but-unregistered kind is
  appended to ``EVENT_KINDS`` in ``<package>/obs/events.py``;
* ``donation-missing`` — ``donate_argnums=(0,)`` is inserted into the
  flagged ``jax.jit(train_step, ...)`` call (behavior-safe: donating
  the consumed train state is exactly what the finding demands).

The contract the tests pin: fixes are **deterministic** (same findings →
same bytes) and **idempotent** (fix → clean lint for these classes → a
second ``--fix`` run changes zero bytes).  ``--check`` renders the same
edits as a unified diff and writes nothing.

Everything here is span-edit based: per file, a list of
``(start_offset, end_offset, replacement)`` spans over the original
source, applied in one pass (descending, overlap-checked) — no
re-serialization of the AST, so untouched lines keep their bytes.
"""

from __future__ import annotations

import ast
import dataclasses
import difflib
import re
from pathlib import Path

from ddl_tpu.analysis.findings import Finding

__all__ = ["FIXABLE_RULES", "FixPlan", "plan_fixes"]

FIXABLE_RULES = frozenset({
    "bare-except",
    "compat-bypass",
    "pspec-hand-rolled",
    "obs-event-unregistered",
    "donation-missing",
})


@dataclasses.dataclass
class FixPlan:
    """The computed edits for one ``--fix`` run."""

    # abs path -> (old_source, new_source); only files that change
    edits: dict[Path, tuple[str, str]]
    fixed: list[Finding]
    unfixable: list[Finding]  # fixable-rule findings with no mechanical fix

    @property
    def changed(self) -> bool:
        return bool(self.edits)

    def unified_diff(self, repo_root: Path) -> str:
        chunks = []
        for path in sorted(self.edits):
            old, new = self.edits[path]
            try:
                rel = path.relative_to(repo_root).as_posix()
            except ValueError:
                rel = path.as_posix()
            chunks.append("".join(difflib.unified_diff(
                old.splitlines(keepends=True),
                new.splitlines(keepends=True),
                fromfile=f"a/{rel}", tofile=f"b/{rel}",
            )))
        return "".join(chunks)

    def apply(self) -> None:
        for path, (_old, new) in self.edits.items():
            path.write_text(new)


class _FileEditor:
    """Collects non-overlapping span edits over one source string."""

    def __init__(self, src: str) -> None:
        self.src = src
        self.spans: list[tuple[int, int, str]] = []
        self._line_offsets = [0]
        for line in src.splitlines(keepends=True):
            self._line_offsets.append(self._line_offsets[-1] + len(line))

    def offset(self, lineno: int, col: int) -> int:
        return self._line_offsets[lineno - 1] + col

    def line_span(self, lineno: int) -> tuple[int, int]:
        return self._line_offsets[lineno - 1], self._line_offsets[lineno]

    def line_text(self, lineno: int) -> str:
        a, b = self.line_span(lineno)
        return self.src[a:b]

    def node_span(self, node: ast.AST) -> tuple[int, int]:
        return (
            self.offset(node.lineno, node.col_offset),
            self.offset(node.end_lineno, node.end_col_offset),
        )

    def replace(self, start: int, end: int, text: str) -> None:
        self.spans.append((start, end, text))

    def replace_on_line(self, lineno: int, pattern: str, repl: str) -> bool:
        """Regex-replace the first match of ``pattern`` on ``lineno``."""
        a, _b = self.line_span(lineno)
        m = re.search(pattern, self.line_text(lineno))
        if m is None:
            return False
        self.replace(a + m.start(), a + m.end(), m.expand(repl))
        return True

    def render(self) -> str:
        spans = sorted(self.spans, key=lambda s: (s[0], s[1]))
        out = []
        pos = 0
        for start, end, text in spans:
            if start < pos:  # overlapping edits: keep the first, drop
                continue
            out.append(self.src[pos:start])
            out.append(text)
            pos = end
        out.append(self.src[pos:])
        return "".join(out)


# ---------------------------------------------------------------------------
# rule-table constants (for the pspec fixer), parsed without JAX
# ---------------------------------------------------------------------------


def _spec_value(call: ast.Call):
    """Structural value of a PartitionSpec(...) literal: a tuple whose
    entries are None, an axis string, or a tuple of axis strings — or
    None when any arg is not a literal."""
    out = []
    for arg in call.args:
        if isinstance(arg, ast.Constant) and (
            arg.value is None or isinstance(arg.value, str)
        ):
            out.append(arg.value)
        elif isinstance(arg, ast.Tuple) and all(
            isinstance(e, ast.Constant) and isinstance(e.value, str)
            for e in arg.elts
        ):
            out.append(tuple(e.value for e in arg.elts))
        else:
            return None
    return tuple(out)


def _rule_table_constants(package_root: Path) -> dict[tuple, str]:
    """value -> constant name for every module-level ``NAME = P(...)``
    literal in ``<package>/parallel/rules.py`` (first definition wins,
    so the mapping is deterministic)."""
    rules_py = package_root / "parallel" / "rules.py"
    try:
        tree = ast.parse(rules_py.read_text())
    except (OSError, SyntaxError):
        return {}
    out: dict[tuple, str] = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign) or len(node.targets) != 1:
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name) or not isinstance(
            node.value, ast.Call
        ):
            continue
        d = node.value.func
        name = d.id if isinstance(d, ast.Name) else getattr(d, "attr", "")
        if name not in ("P", "PartitionSpec"):
            continue
        value = _spec_value(node.value)
        if value is not None:
            out.setdefault(value, target.id)
    return out


# ---------------------------------------------------------------------------
# per-rule fixers
# ---------------------------------------------------------------------------


def _fix_bare_except(ed: _FileEditor, tree, finding: Finding) -> bool:
    return ed.replace_on_line(
        finding.line, r"\bexcept(\s*):", r"except Exception\1:"
    )


def _fix_compat(ed: _FileEditor, tree, finding: Finding) -> bool:
    msg = finding.message
    if "check_rep=" in msg:
        # the finding anchors at the Call; the kwarg may sit on a later
        # line of a multi-line call — use the keyword node's own span
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call) or node.lineno != finding.line:
                continue
            for kw in node.keywords:
                if kw.arg == "check_rep":
                    start = ed.offset(kw.lineno, kw.col_offset)
                    ed.replace(start, start + len("check_rep"), "check_vma")
                    return True
        return False
    if "TPUCompilerParams" in msg:
        return ed.replace_on_line(
            finding.line, r"\bTPUCompilerParams\b", "CompilerParams"
        )
    if "shard_map" in msg and "import" in msg:
        for node in ast.walk(tree):
            if (
                isinstance(node, ast.ImportFrom)
                and node.lineno == finding.line
                and (node.module or "").startswith(
                    "jax.experimental.shard_map"
                )
                and len(node.names) == 1
                and node.names[0].name == "shard_map"
            ):
                alias = node.names[0]
                as_clause = f" as {alias.asname}" if alias.asname else ""
                start, end = ed.node_span(node)
                ed.replace(start, end, f"from jax import shard_map{as_clause}")
                return True
    return False  # pjit variants and compound imports stay manual


def _fix_donation(ed: _FileEditor, tree, finding: Finding) -> bool:
    """Insert ``donate_argnums=(0,)`` into the flagged ``jax.jit(...)``
    step-factory call (the train state is argument 0 by the step-fns
    convention the astlint rule checks)."""
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or node.lineno != finding.line:
            continue
        func = node.func
        fname = (
            func.id if isinstance(func, ast.Name)
            else getattr(func, "attr", "")
        )
        if fname != "jit" or not node.args:
            continue
        first = node.args[0]
        if not (isinstance(first, ast.Name) and "train" in first.id):
            continue
        if any(
            kw.arg in ("donate_argnums", "donate_argnames")
            for kw in node.keywords
        ):
            continue
        # anchor on the last argument's end, same discipline as
        # _register_event_kinds (never scan backwards over comments)
        last = max(
            list(node.args) + list(node.keywords),
            key=lambda n: (n.end_lineno, n.end_col_offset),
        )
        last_end = ed.offset(last.end_lineno, last.end_col_offset)
        close = ed.offset(node.end_lineno, node.end_col_offset) - 1
        tail = ed.src[last_end:close]
        if tail.lstrip().startswith(","):
            ins = last_end + tail.index(",") + 1
            prefix = ""
        else:
            ins = last_end
            prefix = ","
        if node.lineno != node.end_lineno:
            indent = re.match(r"\s*", ed.line_text(last.lineno)).group(0)
            text = prefix + f"\n{indent}donate_argnums=(0,),"
        else:
            text = prefix + " donate_argnums=(0,)"
        ed.replace(ins, ins, text)
        return True
    return False


_KIND_RE = re.compile(r"obs event kind '([^']+)'")


def _fix_pspec(
    ed: _FileEditor, tree, finding: Finding, constants: dict[tuple, str],
    needed_imports: set[str], used: set[int],
) -> bool:
    if not constants:
        return False
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or node.lineno != finding.line:
            continue
        if id(node) in used:
            continue  # two findings on one line: one node each
        func = node.func
        fname = (
            func.id if isinstance(func, ast.Name)
            else getattr(func, "attr", "")
        )
        if fname not in ("P", "PartitionSpec"):
            continue
        value = _spec_value(node)
        if value is None:
            continue
        name = constants.get(value)
        if name is None:
            continue
        start, end = ed.node_span(node)
        ed.replace(start, end, name)
        needed_imports.add(name)
        used.add(id(node))
        return True
    return False


def _ensure_rules_import(
    ed: _FileEditor, tree, package: str, names: set[str]
) -> None:
    """Add/extend ``from <package>.parallel.rules import ...`` so the
    constants the pspec fixer substituted resolve."""
    rules_mod = f"{package}.parallel.rules"
    for node in tree.body:
        if isinstance(node, ast.ImportFrom) and node.module == rules_mod:
            have = {a.name for a in node.names}
            if names <= have:
                return
            # rebuild preserving existing `as` aliases — dropping one
            # would break every use of the alias name
            clauses = {
                a.name: (
                    f"{a.name} as {a.asname}" if a.asname else a.name
                )
                for a in node.names
            }
            for n in names:
                clauses.setdefault(n, n)
            start, end = ed.node_span(node)
            ed.replace(
                start, end,
                f"from {rules_mod} import "
                + ", ".join(clauses[k] for k in sorted(clauses)),
            )
            return
    # no existing import: insert after the last top-level import (or the
    # module docstring, or at the top)
    last_import = None
    for node in tree.body:
        if isinstance(node, (ast.Import, ast.ImportFrom)):
            last_import = node
    line = (
        f"from {rules_mod} import {', '.join(sorted(names))}\n"
    )
    if last_import is not None:
        _a, b = ed.line_span(last_import.end_lineno)
        ed.replace(b, b, line)
    elif (
        tree.body
        and isinstance(tree.body[0], ast.Expr)
        and isinstance(tree.body[0].value, ast.Constant)
    ):
        _a, b = ed.line_span(tree.body[0].end_lineno)
        ed.replace(b, b, "\n" + line)
    else:
        ed.replace(0, 0, line)


def _register_event_kinds(ed: _FileEditor, tree, kinds: set[str]) -> bool:
    """Add spans appending ``kinds`` to the EVENT_KINDS tuple of an
    already-parsed events.py; composes with other edits to the same
    file through the shared editor."""
    for node in tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == "EVENT_KINDS"
            and isinstance(node.value, ast.Tuple)
        ):
            src = ed.src
            existing = {
                e.value
                for e in ast.walk(node.value)
                if isinstance(e, ast.Constant) and isinstance(e.value, str)
            }
            missing = sorted(kinds - existing)
            if not missing:
                return True  # already registered: nothing to do
            paren_end = ed.offset(
                node.value.end_lineno, node.value.end_col_offset
            )
            elts = node.value.elts
            if not elts:
                # empty tuple `()` — insert directly before the paren
                text = ", ".join(f'"{k}"' for k in missing) + ","
                ed.replace(paren_end - 1, paren_end - 1, text)
                return True
            # anchor on the LAST ELEMENT's end (never a backwards text
            # scan — a trailing `# comment` on that line must stay a
            # comment, not swallow the inserted comma)
            last = elts[-1]
            last_end = ed.offset(last.end_lineno, last.end_col_offset)
            tail = src[last_end:paren_end - 1]
            if tail.lstrip().startswith(","):
                # existing trailing comma: insert just after it
                ins = last_end + tail.index(",") + 1
                prefix = ""
            else:
                ins = last_end
                prefix = ","
            multiline = node.value.lineno != node.value.end_lineno
            if multiline:
                text = prefix + "".join(
                    f'\n    "{k}",' for k in missing
                )
            else:
                text = prefix + " " + ", ".join(f'"{k}"' for k in missing)
            ed.replace(ins, ins, text)
            return True
    return False


# ---------------------------------------------------------------------------
# planner
# ---------------------------------------------------------------------------


def plan_fixes(
    findings: list[Finding],
    repo_root: str | Path,
    package_root: str | Path,
) -> FixPlan:
    """Compute the edits for every fixable finding.  ``findings`` may
    include non-fixable rules (ignored); the same finding list a lint
    run produced keeps line numbers valid."""
    repo_root = Path(repo_root)
    package_root = Path(package_root)
    constants = _rule_table_constants(package_root)
    events_py = (package_root / "obs" / "events.py").resolve()
    by_path: dict[str, list[Finding]] = {}
    kind_findings: list[Finding] = []
    event_kinds: set[str] = set()
    for f in findings:
        if f.rule not in FIXABLE_RULES:
            continue
        if f.rule == "obs-event-unregistered":
            # resolved by editing the registry, not the emitting line
            m = _KIND_RE.search(f.message)
            if m is not None:
                event_kinds.add(m.group(1))
                kind_findings.append(f)
            continue
        by_path.setdefault(f.path, []).append(f)
    if event_kinds:
        # route the registry edit through the normal per-file pass so it
        # composes with line fixes landing in events.py itself
        by_path.setdefault(
            events_py.relative_to(repo_root).as_posix()
            if events_py.is_relative_to(repo_root) else str(events_py),
            [],
        )

    edits: dict[Path, tuple[str, str]] = {}
    fixed: list[Finding] = []
    unfixable: list[Finding] = []
    kinds_handled = False

    for rel in sorted(by_path):
        path = Path(rel)
        if not path.is_absolute():
            path = repo_root / rel
        try:
            src = path.read_text()
            tree = ast.parse(src)
        except (OSError, SyntaxError):
            unfixable.extend(by_path[rel])
            continue
        ed = _FileEditor(src)
        needed_imports: set[str] = set()
        used_pspec_nodes: set[int] = set()
        for f in sorted(by_path[rel]):
            if f.rule == "bare-except":
                ok = _fix_bare_except(ed, tree, f)
            elif f.rule == "compat-bypass":
                ok = _fix_compat(ed, tree, f)
            elif f.rule == "donation-missing":
                ok = _fix_donation(ed, tree, f)
            else:  # pspec-hand-rolled
                ok = _fix_pspec(
                    ed, tree, f, constants, needed_imports,
                    used_pspec_nodes,
                )
            (fixed if ok else unfixable).append(f)
        if needed_imports:
            _ensure_rules_import(ed, tree, package_root.name, needed_imports)
        if event_kinds and path.resolve() == events_py:
            registered = _register_event_kinds(ed, tree, event_kinds)
            (fixed if registered else unfixable).extend(kind_findings)
            kinds_handled = True
        if ed.spans:
            new = ed.render()
            if new != src:
                edits[path] = (src, new)

    if event_kinds and not kinds_handled:
        # registry missing OR unreadable/unparseable: the kind findings
        # must still surface as not-auto-fixable, never silently vanish
        unfixable.extend(kind_findings)

    return FixPlan(edits=edits, fixed=sorted(fixed), unfixable=sorted(unfixable))

"""Core of the unified static-analysis framework.

One parse of the corpus (`paddle_tpu/`, `tools/`) into a
shared :class:`Index` — per-module AST with parent links and def/class
qualnames, raw source lines, and the inline-suppression table — then
every registered pass (tools/analyze/passes/) runs over the same index
and emits typed :class:`Finding`s.

Finding lifecycle:

  pass emits Finding
    -> suppressed?   `# lint: disable=<pass-id> -- justification` on
                     the finding's line removes it (a suppression with
                     NO justification is itself a finding)
    -> baselined?    an entry in tools/analyze/baseline.json keyed by
                     (pass, file, line) grandfathers it (green at
                     introduction; the baseline only ever shrinks)
    -> otherwise     it is NEW and the run exits non-zero.

Stale baseline entries and unused suppressions are reported as
warnings without failing, so the ratchet is visible but a mid-refactor
tree doesn't flap.
"""
from __future__ import annotations

import ast
import io
import json
import os
import re
import tokenize
from dataclasses import dataclass, field, replace

# directories that make up the analyzed corpus, relative to root
CORPUS_DIRS = ("paddle_tpu", "tools")
SKIP_DIRS = {"__pycache__", ".git"}

# `# lint: disable=<id>[,<id>...] -- justification`  (the justification
# is REQUIRED: a suppression that doesn't say why is itself a finding)
_SUPPRESS_RE = re.compile(
    r"#\s*lint:\s*disable=([A-Za-z0-9_\-]+(?:\s*,\s*[A-Za-z0-9_\-]+)*)"
    r"(?:\s*--\s*(.*))?")


@dataclass(frozen=True)
class Finding:
    """One typed diagnostic: {pass, severity, file, line, qualname,
    message}.  `qualname` (the enclosing def/class) is filled in
    centrally by the runner from the finding's line — passes never need
    to compute it."""
    pass_id: str
    file: str               # path relative to the analyzed root
    line: int
    message: str
    severity: str = "error"
    qualname: str = ""      # enclosing def/class ("" = module level)

    def key(self):
        return (self.pass_id, self.file, self.line)

    def to_json(self, suppressed=False):
        return {"pass": self.pass_id, "severity": self.severity,
                "file": self.file, "line": self.line,
                "qualname": self.qualname, "message": self.message,
                "suppressed": suppressed}

    def render(self):
        where = f" ({self.qualname})" if self.qualname else ""
        return (f"[{self.pass_id}] {self.file}:{self.line}{where}: "
                f"{self.message}")


@dataclass
class Module:
    """One parsed corpus file."""
    path: str                      # absolute
    rel: str                       # relative to Index.root
    source: str
    lines: list = field(default_factory=list)          # 1-based via [no-1]
    tree: ast.Module | None = None
    parse_error: str | None = None
    # line -> set of suppressed pass ids (only well-formed suppressions)
    suppressions: dict = field(default_factory=dict)
    # (line, raw_comment) for suppressions missing their justification
    bad_suppressions: list = field(default_factory=list)

    def qualname(self, node) -> str:
        """Dotted def/class qualname ("Trainer.step", "Engine._tick.run")
        computed from parent links at index time."""
        return getattr(node, "_pt_qualname", getattr(node, "name", "?"))

    def qualname_at(self, line: int) -> str:
        """Innermost def/class qualname containing `line` ("" when the
        line sits at module level)."""
        spans = getattr(self, "_qual_spans", None)
        if spans is None:
            spans = []
            if self.tree is not None:
                for node in ast.walk(self.tree):
                    if isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef,
                                         ast.ClassDef)):
                        end = getattr(node, "end_lineno", node.lineno)
                        spans.append((node.lineno, end,
                                      self.qualname(node)))
            self._qual_spans = spans
        best = ""
        best_start = -1
        for start, end, qn in spans:
            if start <= line <= end and start > best_start:
                best, best_start = qn, start
        return best


class Index:
    """The shared AST index every pass runs over."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        self.modules: list[Module] = []
        self.by_rel: dict[str, Module] = {}

    def add(self, mod: Module):
        self.modules.append(mod)
        self.by_rel[mod.rel] = mod

    def under(self, prefix: str):
        """Modules whose relpath sits under `prefix` (a corpus subdir)."""
        pre = prefix.rstrip(os.sep) + os.sep
        for m in self.modules:
            if m.rel.startswith(pre) or m.rel == prefix:
                yield m


def _iter_corpus(root, subdirs=CORPUS_DIRS):
    for sub in subdirs:
        top = os.path.join(root, sub)
        if not os.path.isdir(top):
            continue
        for dirpath, dirnames, filenames in os.walk(top):
            dirnames[:] = sorted(d for d in dirnames if d not in SKIP_DIRS)
            for fn in sorted(filenames):
                if fn.endswith(".py"):
                    yield os.path.join(dirpath, fn)


def _link_parents(tree):
    for node in ast.walk(tree):
        for child in ast.iter_child_nodes(node):
            child.parent = node


def _assign_qualnames(tree):
    """Set ._pt_qualname on every def/class: enclosing def/class names
    joined with '.' (no `<locals>` noise — this feeds config matching
    like "Trainer.step", not introspection)."""

    def visit(node, prefix):
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef)):
                qn = f"{prefix}.{child.name}" if prefix else child.name
                child._pt_qualname = qn
                visit(child, qn)
            else:
                visit(child, prefix)

    visit(tree, "")


def _iter_comments(mod: Module):
    """(lineno, comment_text) for every real COMMENT token — a
    suppression spelled inside a string literal or docstring is prose,
    not a directive, and must not count."""
    try:
        toks = tokenize.generate_tokens(io.StringIO(mod.source).readline)
        for tok in toks:
            if tok.type == tokenize.COMMENT:
                yield tok.start[0], tok.string
    except (tokenize.TokenError, IndentationError, SyntaxError):
        # untokenizable file: fall back to raw lines so suppressions
        # keep working on files the AST passes already skip
        for no, line in enumerate(mod.lines, 1):
            if "#" in line and "lint:" in line:
                yield no, line[line.index("#"):]


def _parse_suppressions(mod: Module):
    if "lint:" not in mod.source:      # cheap gate: most files have no
        return                         # directives; skip tokenization
    for no, comment in _iter_comments(mod):
        if "lint:" not in comment:
            continue
        m = _SUPPRESS_RE.search(comment)
        if not m:
            continue
        ids = {p.strip() for p in m.group(1).split(",") if p.strip()}
        just = (m.group(2) or "").strip()
        if not just:
            mod.bad_suppressions.append((no, comment.strip()))
            continue
        mod.suppressions.setdefault(no, set()).update(ids)


def build_index(root: str, subdirs=CORPUS_DIRS) -> Index:
    """Parse the corpus once. Files that fail to parse keep their raw
    lines (line-based passes still see them) with tree=None.
    `subdirs` narrows the corpus — the legacy `scan(root)` shims
    index only paddle_tpu/ instead of paying for the full tree."""
    index = Index(root)
    for path in _iter_corpus(index.root, subdirs):
        rel = os.path.relpath(path, index.root)
        try:
            with open(path, encoding="utf-8") as f:
                source = f.read()
        except OSError as e:
            index.add(Module(path=path, rel=rel, source="",
                             parse_error=f"unreadable: {e}"))
            continue
        mod = Module(path=path, rel=rel, source=source,
                     lines=source.splitlines())
        try:
            mod.tree = ast.parse(source, filename=rel)
            _link_parents(mod.tree)
            _assign_qualnames(mod.tree)
        except SyntaxError as e:
            mod.parse_error = f"syntax error: {e}"
        _parse_suppressions(mod)
        index.add(mod)
    return index


# -- baseline ----------------------------------------------------------------

class Baseline:
    """Checked-in grandfather list: findings present when their pass was
    introduced. Keyed (pass, file, line); every entry carries a
    justification so the file documents WHY each one is tolerated."""

    def __init__(self, entries=None, path=None):
        self.path = path
        self.entries = list(entries or [])
        self._keys = {(e["pass"], e["file"], int(e["line"]))
                      for e in self.entries}

    @classmethod
    def load(cls, path):
        if path is None or not os.path.isfile(path):
            return cls(path=path)
        with open(path, encoding="utf-8") as f:
            doc = json.load(f)
        return cls(doc.get("entries", []), path=path)

    def match(self, finding: Finding) -> bool:
        return finding.key() in self._keys

    def stale(self, findings, ran_pass_ids=None) -> list:
        """Entries whose finding no longer occurs. With `ran_pass_ids`
        (a `--pass`-filtered run), entries for passes that did not run
        are unknowable, not stale."""
        hit = {f.key() for f in findings}
        return [e for e in self.entries
                if (ran_pass_ids is None or e["pass"] in ran_pass_ids)
                and (e["pass"], e["file"], int(e["line"])) not in hit]

    @staticmethod
    def dump(findings, path, prior=None, ran_pass_ids=None):
        """Rewrite the baseline from `findings`. Surviving entries keep
        the justification they carry in `prior` (a Baseline); only
        genuinely new entries get the TODO placeholder. With
        `ran_pass_ids` set (a `--pass`-filtered run), entries for
        passes that did NOT run are retained verbatim instead of being
        silently dropped."""
        prior = prior or Baseline()
        carried = {(e["pass"], e["file"], int(e["line"])):
                   e.get("justification")
                   for e in prior.entries}
        entries = [{"pass": f.pass_id, "file": f.file, "line": f.line,
                    "message": f.message,
                    "justification": carried.get(f.key())
                    or "TODO: justify or fix"}
                   for f in findings]
        if ran_pass_ids is not None:
            have = {f.key() for f in findings}
            entries += [
                e for e in prior.entries
                if e["pass"] not in ran_pass_ids
                and (e["pass"], e["file"], int(e["line"])) not in have]
        entries.sort(key=lambda e: (e["pass"], e["file"], e["line"]))
        with open(path, "w", encoding="utf-8") as f:
            json.dump({"version": 1, "entries": entries}, f, indent=2)
            f.write("\n")


# -- runner ------------------------------------------------------------------

@dataclass
class Report:
    root: str
    pass_ids: list
    new: list              # non-baselined, non-suppressed findings
    baselined: list
    suppressed: list
    warnings: list         # stale baseline entries, unused suppressions
    notes: dict = field(default_factory=dict)   # pass id -> table lines

    @property
    def exit_code(self):
        return 1 if self.new else 0

    def to_json(self):
        """Schema-stable (version 2) document for CI consumption.
        Version 2 (ISSUE 15): findings carry `qualname` and a
        `suppressed` flag (suppressed findings are INCLUDED, flagged
        true, so CI can audit them; only suppressed=false findings
        affect the exit code), plus per-pass `notes` tables (e.g.
        lock-order's canonical acquisition order)."""
        return {
            "version": 2,
            "root": self.root,
            "passes": list(self.pass_ids),
            "findings": [f.to_json() for f in self.new]
            + [f.to_json(suppressed=True) for f in self.suppressed],
            "counts": {"new": len(self.new),
                       "baselined": len(self.baselined),
                       "suppressed": len(self.suppressed)},
            "warnings": list(self.warnings),
            "notes": {k: list(v) for k, v in self.notes.items()},
        }


def default_baseline_path():
    return os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "baseline.json")


def run(root, passes, baseline: Baseline | None = None,
        known_ids=None) -> Report:
    """Build the index once, run `passes` (modules exposing PASS_ID and
    run(index)), fold in framework findings (malformed suppressions),
    then apply suppressions and the baseline. `known_ids` is the FULL
    pass registry (defaults to the ids of `passes`): on a filtered
    `--pass` run, a suppression for a non-running pass is still a known
    pass — neither unknown nor unused."""
    index = build_index(root)
    ran_ids = {p.PASS_ID for p in passes}
    known_ids = set(known_ids) if known_ids else ran_ids

    findings = []
    for p in passes:
        findings.extend(p.run(index))

    # framework-level: a suppression without a justification is a
    # finding in its own right (and is itself unsuppressible)
    for mod in index.modules:
        for no, raw in mod.bad_suppressions:
            findings.append(Finding(
                "suppression", mod.rel, no,
                f"suppression comment has no justification: {raw!r} — "
                "write `# lint: disable=<pass-id> -- <why>`"))

    # central qualname enrichment (AFTER the framework findings so
    # they carry one too): the finding's line names its enclosing
    # def/class, no pass has to carry that plumbing
    enriched = []
    for f in findings:
        if not f.qualname:
            mod = index.by_rel.get(f.file)
            if mod is not None:
                qn = mod.qualname_at(f.line)
                if qn:
                    f = replace(f, qualname=qn)
        enriched.append(f)
    findings = enriched

    notes = {}
    for p in passes:
        summarize = getattr(p, "summarize", None)
        if summarize:
            lines = list(summarize(index))
            if lines:
                notes[p.PASS_ID] = lines

    new, suppressed = [], []
    used = set()                      # (rel, line, pass_id) consumed
    for f in findings:
        mod = index.by_rel.get(f.file)
        ids = mod.suppressions.get(f.line, set()) if mod else set()
        if f.pass_id != "suppression" and f.pass_id in ids:
            suppressed.append(f)
            used.add((f.file, f.line, f.pass_id))
        else:
            new.append(f)

    warnings = []
    for mod in index.modules:
        if mod.parse_error:
            warnings.append(f"{mod.rel}: skipped AST passes "
                            f"({mod.parse_error})")
        for no, ids in sorted(mod.suppressions.items()):
            for pid in sorted(ids):
                if pid not in known_ids and pid != "suppression":
                    warnings.append(
                        f"{mod.rel}:{no}: suppression names unknown "
                        f"pass {pid!r}")
                elif pid in ran_ids and (mod.rel, no, pid) not in used:
                    warnings.append(
                        f"{mod.rel}:{no}: unused suppression for "
                        f"{pid!r} (nothing to suppress — remove it)")

    baseline = baseline or Baseline()
    kept, grandfathered = [], []
    for f in new:
        (grandfathered if baseline.match(f) else kept).append(f)
    for e in baseline.stale(new, ran_pass_ids=ran_ids):
        warnings.append(
            f"stale baseline entry ({e['pass']} {e['file']}:{e['line']})"
            " — the finding is gone; ratchet by deleting the entry")

    kept.sort(key=lambda f: (f.file, f.line, f.pass_id))
    return Report(root=index.root, pass_ids=[p.PASS_ID for p in passes],
                  new=kept, baselined=grandfathered,
                  suppressed=suppressed, warnings=warnings, notes=notes)

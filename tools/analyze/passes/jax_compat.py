"""Pass `jax-compat` — version-fragile jax spellings (line-based).

Port of tools/check_jax_compat.py: `shard_map`, `axis_size` and the
Pallas TPU compiler params have each moved between jax releases, so
paddle_tpu imports them from ONE site, paddle_tpu/core/jax_compat.py
(plain aliases for the installed jax), and this pass keeps bare
spellings from creeping back. Line-based (works on files the AST
passes skip), with the comment/string stripper that keeps a stray
triple-quote in a COMMENT from hiding the rest of the file.

The legacy `scan(root)` surface is kept for tools/check_jax_compat.py
(now a shim) and its tests.
"""
from __future__ import annotations

import os
import re

from tools.analyze.core import Finding, build_index

PASS_ID = "jax-compat"
DESCRIPTION = ("version-fragile jax imports (shard_map/axis_size) — "
               "import them from paddle_tpu.core.jax_compat")

# (pattern, why). Docstrings/comments are excluded by the stripper;
# prose mentions inside docstrings are tolerated (they can't break an
# import).
FRAGILE = [
    (re.compile(r"^\s*from\s+jax\s+import\s+(?:\([^)]*\bshard_map\b"
                r"|.*\bshard_map\b)"),
     "`from jax import shard_map` has moved between releases; import "
     "it from paddle_tpu.core.jax_compat instead"),
    (re.compile(r"\bjax\.shard_map\s*\("),
     "`jax.shard_map(...)` has moved between releases; use "
     "paddle_tpu.core.jax_compat.shard_map"),
    (re.compile(r"^\s*from\s+jax\.experimental\.shard_map\s+import"),
     "import shard_map via paddle_tpu.core.jax_compat, not "
     "jax.experimental directly"),
    (re.compile(r"\bjax\.lax\.axis_size\s*\("),
     "`jax.lax.axis_size` has moved between releases; use "
     "paddle_tpu.core.jax_compat.axis_size"),
]

# the one module allowed to touch the real locations
ALLOWED = {os.path.join("paddle_tpu", "core", "jax_compat.py")}

_PKG = "paddle_tpu" + os.sep


def _strip(line: str, open_q: str | None):
    """One stateful pass per line: returns (code, new_open_q) with
    comment trails and ALL string-literal contents removed. `open_q` is
    the delimiter of a still-open triple-quoted string from earlier
    lines (None when outside). Tracking strings and comments together
    is what keeps a stray triple-quote inside a COMMENT from hiding the
    rest of the file from the scan."""
    out = []
    i = 0
    while i < len(line):
        if open_q:
            j = line.find(open_q, i)
            if j < 0:
                return "".join(out), open_q     # string spans the line
            i = j + len(open_q)
            open_q = None
            continue
        if line.startswith('"""', i) or line.startswith("'''", i):
            open_q = line[i:i + 3]
            i += 3
            continue
        ch = line[i]
        if ch in "\"'":
            j = line.find(ch, i + 1)
            if j < 0:               # unterminated/escaped: drop the rest
                return "".join(out), None
            i = j + 1
            continue
        if ch == "#":
            return "".join(out), None
        out.append(ch)
        i += 1
    return "".join(out), open_q


def _scan_module(mod):
    """Yield (lineno, line, why) for every fragile use in one module."""
    open_q = None
    for no, line in enumerate(mod.lines, 1):
        code, open_q = _strip(line, open_q)
        for pat, why in FRAGILE:
            if pat.search(code):
                yield no, line.rstrip(), why
                break


def _scan_index(index):
    for mod in index.under("paddle_tpu"):
        if mod.rel in ALLOWED:
            continue
        for no, line, why in _scan_module(mod):
            yield mod.rel, no, line, why


def run(index):
    for rel, no, line, why in _scan_index(index):
        yield Finding(PASS_ID, rel, no, f"{line.strip()} -> {why}")


def scan(root: str):
    """Legacy surface (tools/check_jax_compat.py shim + its tests):
    yields (relpath, lineno, line, why) for every fragile use. Indexes
    only paddle_tpu/ — all this scanner ever looked at."""
    return list(_scan_index(build_index(root, subdirs=("paddle_tpu",))))

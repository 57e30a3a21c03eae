"""tools/analyze — the unified static-analysis framework (ISSUE 8).

Running the full suite against the live tree IS the tier-1 wiring (the
check_*_tool.py pattern): any non-baselined finding from the seven
passes anywhere in paddle_tpu/ or tools/ fails this module.
Per-pass behavior is pinned on synthetic fixture modules under
tests/data/analyze/, and the store-server convoy defect the
thread-discipline pass found ships with a behavioral pin here too.
"""
import json
import os
import shutil
import socket
import struct
import subprocess
import sys
import textwrap
import threading
import time

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_DATA = os.path.join(_ROOT, "tests", "data", "analyze")

if _ROOT not in sys.path:
    sys.path.insert(0, _ROOT)

from tools.analyze import analyze_tree  # noqa: E402


def _cli(*args, cwd=_ROOT):
    return subprocess.run([sys.executable, "-m", "tools.analyze",
                           *args],
                          capture_output=True, text=True, timeout=180,
                          cwd=cwd)


def _mini(tmp_path, **files):
    """A fake repo: paddle_tpu/<name>.py for each name=source kwarg
    (or name=<fixture filename> copied from tests/data/analyze)."""
    pkg = tmp_path / "paddle_tpu"
    pkg.mkdir(exist_ok=True)
    for name, src in files.items():
        if src.endswith(".py"):            # fixture file reference
            shutil.copy(os.path.join(_DATA, src), pkg / f"{name}.py")
        else:
            (pkg / f"{name}.py").write_text(textwrap.dedent(src))
    return str(tmp_path)


def _ids(report):
    return sorted({f.pass_id for f in report.new})


# -- tier-1 gate -------------------------------------------------------------

def test_live_tree_is_clean():
    """The real corpus has zero non-baselined findings across all
    eleven passes, and the run stays well under the 30s budget."""
    t0 = time.monotonic()
    proc = _cli(_ROOT)
    elapsed = time.monotonic() - t0
    assert proc.returncode == 0, proc.stderr + proc.stdout
    assert "clean" in proc.stdout
    assert elapsed < 30, f"analyzer took {elapsed:.1f}s (budget 30s)"


def test_json_output_schema_stable():
    proc = _cli(_ROOT, "--json")
    assert proc.returncode == 0, proc.stderr
    doc = json.loads(proc.stdout)
    assert set(doc) == {"version", "root", "passes", "findings",
                       "counts", "warnings", "notes"}
    assert doc["version"] == 2
    assert doc["passes"] == ["jax-compat", "chaos-points",
                             "metric-names", "hot-path-sync",
                             "thread-discipline", "silent-swallow",
                             "disabled-gate", "lock-order",
                             "guarded-field", "cv-discipline",
                             "jax-hazards"]
    assert doc["counts"]["new"] == 0
    # v2: suppressed findings ride along flagged true (auditability);
    # every finding carries its enclosing qualname
    for f in doc["findings"]:
        assert set(f) == {"pass", "severity", "file", "line",
                          "qualname", "message", "suppressed"}
        assert f["suppressed"] is True      # clean tree: only these
    # notes carry the lock-order canonical acquisition table
    assert any("->" in line
               for line in doc["notes"].get("lock-order", []))


def test_exit_nonzero_names_pass_file_and_line(tmp_path):
    root = _mini(tmp_path, bad="swallow_bad.py")
    proc = _cli(root, "--no-baseline")
    assert proc.returncode == 1
    assert "silent-swallow" in proc.stderr
    assert os.path.join("paddle_tpu", "bad.py") + ":8" in proc.stderr


def test_pass_filter_and_unknown_pass(tmp_path):
    root = _mini(tmp_path, bad="swallow_bad.py")
    assert _cli(root, "--no-baseline", "--pass", "jax-compat") \
        .returncode == 0
    assert _cli(root, "--no-baseline", "--pass", "silent-swallow") \
        .returncode == 1
    assert _cli(root, "--pass", "no-such-pass").returncode == 2


# -- per-pass fixtures -------------------------------------------------------

def test_hot_path_pass_fixtures(tmp_path):
    root = _mini(tmp_path, bad="hot_path_bad.py",
                 good="hot_path_good.py")
    rep = analyze_tree(root, ["hot-path-sync"], use_baseline=False)
    files = {f.file for f in rep.new}
    assert files == {os.path.join("paddle_tpu", "bad.py")}
    lines = sorted(f.line for f in rep.new)
    assert lines == [8, 9, 13, 14], rep.new


def test_thread_pass_fixtures(tmp_path):
    root = _mini(tmp_path, bad="threads_bad.py",
                 good="threads_good.py")
    rep = analyze_tree(root, ["thread-discipline"], use_baseline=False)
    assert {f.file for f in rep.new} == \
        {os.path.join("paddle_tpu", "bad.py")}
    msgs = " | ".join(f.message for f in rep.new)
    assert "never join()ed" in msgs
    assert "time.sleep() while holding the lock" in msgs
    assert "blocking .get() with no timeout" in msgs
    assert len(rep.new) == 3


def test_swallow_pass_fixtures(tmp_path):
    root = _mini(tmp_path, bad="swallow_bad.py",
                 good="swallow_good.py")
    rep = analyze_tree(root, ["silent-swallow"], use_baseline=False)
    assert {f.file for f in rep.new} == \
        {os.path.join("paddle_tpu", "bad.py")}
    assert len(rep.new) == 2                # pass-only and continue-only
    assert len(rep.suppressed) == 1         # the justified one in good


def test_gating_pass_fixtures(tmp_path):
    root = _mini(tmp_path, bad="gating_bad.py", good="gating_good.py")
    rep = analyze_tree(root, ["disabled-gate"], use_baseline=False)
    assert {f.file for f in rep.new} == \
        {os.path.join("paddle_tpu", "bad.py")}
    # aliased/inverted x3 + no-alias plain import + direct function import
    assert len(rep.new) == 5, rep.new
    msgs = " | ".join(f.message for f in rep.new)
    assert "paddle_tpu.observability.inc" in msgs
    assert "_inc(" in msgs


def test_jax_compat_pass_through_framework(tmp_path):
    root = _mini(tmp_path, bad="from jax import shard_map\n")
    rep = analyze_tree(root, ["jax-compat"], use_baseline=False)
    assert [f.file for f in rep.new] == \
        [os.path.join("paddle_tpu", "bad.py")]


# -- suppression mechanics ---------------------------------------------------

def test_suppression_requires_justification(tmp_path):
    root = _mini(tmp_path, bad="""
        def f(job):
            try:
                job()
            except Exception:  # lint: disable=silent-swallow
                pass
    """)
    rep = analyze_tree(root, use_baseline=False)
    ids = _ids(rep)
    # the naked suppression is a finding AND does not suppress
    assert "suppression" in ids
    assert "silent-swallow" in ids
    # framework findings go through qualname enrichment like any other
    supp = next(f for f in rep.new if f.pass_id == "suppression")
    assert supp.qualname == "f"


def test_deleting_a_suppression_resurfaces_the_finding(tmp_path):
    src = """
        def f(job):
            try:
                job()
            except Exception:  # lint: disable=silent-swallow -- fixture: deliberately ignored
                pass
    """
    root = _mini(tmp_path, mod=src)
    rep = analyze_tree(root, use_baseline=False)
    assert rep.new == [] and len(rep.suppressed) == 1
    root = _mini(tmp_path, mod=src.replace(
        "  # lint: disable=silent-swallow -- fixture: deliberately ignored", ""))
    rep = analyze_tree(root, use_baseline=False)
    assert [f.pass_id for f in rep.new] == ["silent-swallow"]


def test_single_pass_run_keeps_other_passes_suppressions_quiet(tmp_path):
    """A --pass-filtered run must not call another pass's valid
    suppression 'unknown' or 'unused' — that steered users to delete
    load-bearing suppressions."""
    root = _mini(tmp_path, mod="""
        def f(job):
            try:
                job()
            except Exception:  # lint: disable=silent-swallow -- fixture: deliberate
                pass
    """)
    rep = analyze_tree(root, ["jax-compat"], use_baseline=False)
    assert rep.exit_code == 0
    assert rep.warnings == [], rep.warnings
    # same for baseline entries: a non-running pass's entry is
    # unknowable on a filtered run, not "stale"
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"pass": "silent-swallow", "file": "paddle_tpu/other.py",
         "line": 9, "message": "m", "justification": "j"}]}))
    rep = analyze_tree(root, ["jax-compat"], baseline_path=str(bl))
    assert rep.exit_code == 0
    assert rep.warnings == [], rep.warnings


def test_suppression_in_docstring_is_prose(tmp_path):
    root = _mini(tmp_path, mod='''
        """Docs may quote `# lint: disable=silent-swallow -- why` freely."""

        def f(job):
            try:
                job()
            except Exception:
                pass
    ''')
    rep = analyze_tree(root, use_baseline=False)
    assert [f.pass_id for f in rep.new] == ["silent-swallow"]


# -- baseline mechanics ------------------------------------------------------

def test_baseline_grandfathers_and_ratchets(tmp_path):
    root = _mini(tmp_path, bad="swallow_bad.py")
    rep = analyze_tree(root, use_baseline=False)
    assert len(rep.new) == 2
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"pass": f.pass_id, "file": f.file, "line": f.line,
         "message": f.message, "justification": "fixture"}
        for f in rep.new]}))
    # fully baselined: green
    rep2 = analyze_tree(root, baseline_path=str(bl))
    assert rep2.new == [] and len(rep2.baselined) == 2
    assert rep2.exit_code == 0
    # delete one entry: the finding comes back, naming pass/file/line
    doc = json.loads(bl.read_text())
    dropped = doc["entries"].pop(0)
    bl.write_text(json.dumps(doc))
    rep3 = analyze_tree(root, baseline_path=str(bl))
    assert rep3.exit_code == 1
    assert [(f.pass_id, f.file, f.line) for f in rep3.new] == \
        [(dropped["pass"], dropped["file"], dropped["line"])]


def test_stale_baseline_entry_warns_without_failing(tmp_path):
    root = _mini(tmp_path, ok="x = 1\n")
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"pass": "silent-swallow", "file": "paddle_tpu/gone.py",
         "line": 3, "message": "m", "justification": "j"}]}))
    rep = analyze_tree(root, baseline_path=str(bl))
    assert rep.exit_code == 0
    assert any("stale baseline entry" in w for w in rep.warnings)


def test_write_baseline_merges_instead_of_clobbering(tmp_path):
    """--write-baseline keeps hand-written justifications for surviving
    entries, and a --pass-filtered rewrite retains the other passes'
    entries instead of silently deleting them."""
    root = _mini(tmp_path, bad="swallow_bad.py",
                 frag="from jax import shard_map\n")
    bl = tmp_path / "baseline.json"
    # seed: one justified swallow entry + full write for the rest
    rep = analyze_tree(root, use_baseline=False)
    swallow = [f for f in rep.new if f.pass_id == "silent-swallow"]
    bl.write_text(json.dumps({"version": 1, "entries": [
        {"pass": f.pass_id, "file": f.file, "line": f.line,
         "message": f.message, "justification": "hand-written why"}
        for f in swallow]}))
    proc = _cli(root, "--baseline", str(bl), "--pass", "jax-compat",
                "--write-baseline")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    doc = json.loads(bl.read_text())
    by_pass = {}
    for e in doc["entries"]:
        by_pass.setdefault(e["pass"], []).append(e)
    # the filtered run added its own findings...
    assert len(by_pass["jax-compat"]) == 1
    # ...and did NOT drop the other pass's entries or their wording
    assert [e["justification"] for e in by_pass["silent-swallow"]] == \
        ["hand-written why"] * len(swallow)
    # a full rewrite still carries surviving justifications over
    proc = _cli(root, "--baseline", str(bl), "--write-baseline")
    assert proc.returncode == 0, proc.stderr + proc.stdout
    doc = json.loads(bl.read_text())
    justs = {e["justification"] for e in doc["entries"]
             if e["pass"] == "silent-swallow"}
    assert justs == {"hand-written why"}


def test_shipped_baseline_entries_all_carry_justifications():
    with open(os.path.join(_ROOT, "tools", "analyze",
                           "baseline.json")) as f:
        doc = json.load(f)
    assert doc["version"] == 1
    for e in doc["entries"]:
        assert e["justification"].strip(), e
        assert {"pass", "file", "line", "message"} <= set(e)


# -- the defect the analyzer found (thread-discipline) -----------------------

def test_store_get_reply_does_not_hold_the_lock():
    """Pin for the real defect ISSUE 8's thread-discipline pass found:
    _PyStoreServer._serve sent GET/WAIT replies while holding the
    store's condition lock, so one client stalling mid-read (full TCP
    send buffer — what a preempted rank does) convoyed every other
    rank's store traffic behind its sendall. The reply now goes out
    after the lock is released; a healthy client must keep making
    progress while a sick one sits on an unread 32MB reply."""
    from paddle_tpu.distributed.store import (_PyStoreClient,
                                              _PyStoreServer)
    srv = _PyStoreServer(0)
    setter = healthy = sick = None
    try:
        setter = _PyStoreClient("127.0.0.1", srv.port, timeout=10)
        setter.set("big", b"\x42" * (32 << 20))
        # sick client: requests the 32MB value and never reads a byte
        # of the reply; the tiny receive buffer guarantees the serve
        # thread blocks inside sendall
        sick = socket.socket()
        sick.setsockopt(socket.SOL_SOCKET, socket.SO_RCVBUF, 4096)
        sick.connect(("127.0.0.1", srv.port))
        sick.sendall(b"\x01" + struct.pack("<I", 3) + b"big"
                     + struct.pack("<q", -1))
        time.sleep(0.5)          # let the serve thread enter sendall
        healthy = _PyStoreClient("127.0.0.1", srv.port, timeout=10)
        done = {}

        def ops():
            healthy.set("small", b"ok")
            done["val"] = healthy.get("small", timeout_ms=5000)

        th = threading.Thread(target=ops, daemon=True)
        th.start()
        th.join(timeout=8)
        assert not th.is_alive(), \
            "store ops convoyed behind a stalled client's GET reply"
        assert done["val"] == b"ok"
    finally:
        for c in (setter, healthy):
            if c is not None:
                c.close()
        if sick is not None:
            sick.close()
        srv.stop()

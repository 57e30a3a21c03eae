"""chip_smoke.py off the chip.

The script itself has no way round its device check. This test is the
test-only path: it imports the phase functions and drives them at a tiny
size with the Pallas kernels in interpret mode (and the assertions that
only hold on a TPU — "the Pallas path was the one taken" — switched off
by argument). What it proves is that the phases' plumbing works end to
end: references agree with kernels, the trainer steps and the loss
falls, the server answers every request and everything shuts down.
"""
import json
import os
import subprocess
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)
import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(
    vocab=512, hidden=128, intermediate=256, heads=4, kv_heads=2,
    layers=2, context=64, kern_batch=2, stream_seq=128, ce_chunk=32,
    ce_vocab_block=128, flash_block=32, decode_slots=2, decode_tokens=64,
    train_layers_one_chip=2, batch=2, train_steps=4, slots=2, requests=4,
    prompt_lo=8, prompt_hi=24, new_tokens=6)


def test_phases_tiny_interpret():
    clog = chip_smoke.CompileLog().install()
    kernels = chip_smoke.phase_kernels(TINY, clog, interpret=True,
                                       dtype="float32")
    assert set(kernels["kernels"]) == {
        n for n, _ in chip_smoke.kernel_cases(TINY)}
    train = chip_smoke.phase_train(TINY, clog, on_chip=False)
    # conftest gives 8 CPU devices, so this is the fsdp 2 x mp 2 path
    assert train["train_devices"] == 4
    assert len(train["losses"]) == TINY.train_steps + 1
    assert train["losses"][-1] < train["losses"][0]
    serve = chip_smoke.phase_serve(TINY, clog, on_chip=False)
    assert serve["engine"]["finished"] == TINY.requests
    assert serve["serve_devices"] == 1
    assert clog.durations, "the compile listener saw no compile"


def test_full_size_is_the_published_model():
    f = chip_smoke.FULL
    assert (f.vocab, f.hidden, f.intermediate, f.heads, f.kv_heads,
            f.layers, f.context, f.head_dim) == (
        32000, 2048, 5632, 32, 4, 22, 2048, 64)


def _run_main_with_stub_phases(monkeypatch, capsys, serve):
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "versions": {}, "native": "loaded"}
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: dict(device))
    monkeypatch.setattr(chip_smoke, "phase_kernels", lambda *a, **k: {})
    monkeypatch.setattr(chip_smoke, "phase_train", lambda *a, **k: {})
    monkeypatch.setattr(chip_smoke, "phase_serve", serve)
    rc = chip_smoke.main()
    return rc, capsys.readouterr().out.splitlines()


def test_last_line_is_the_verdict_alone(monkeypatch, capsys):
    """The driver reads the LAST stdout line: one JSON object with
    exactly `ok` and `device` (platform, kind, count). The per-phase
    detail rides the `[summary]` line before it."""
    rc, lines = _run_main_with_stub_phases(monkeypatch, capsys,
                                           lambda *a, **k: {})
    assert rc == 0
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "tpu", "kind": "TPU v5 lite",
                               "count": 1}}
    assert lines[-2].startswith("[summary] ")
    summary = json.loads(lines[-2][len("[summary] "):])
    assert set(summary["phases"]) == {"device", "kernels", "train", "serve"}
    assert lines[-2].endswith('"claim": null}')


def test_failed_phase_is_not_ok(monkeypatch, capsys):
    def serve(*a, **k):
        raise chip_smoke.PhaseFailed("request 3: HTTP 500")
    rc, lines = _run_main_with_stub_phases(monkeypatch, capsys, serve)
    assert rc == 1
    verdict = json.loads(lines[-1])
    assert set(verdict) == {"ok", "device"} and verdict["ok"] is False
    assert "request 3: HTTP 500" in lines[-2]


def test_refuses_to_run_without_a_tpu():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero within
    seconds, says no TPU was found, prints no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, os.path.join(_ROOT,
                                                     "chip_smoke.py")],
                       capture_output=True, text=True, timeout=120,
                       env=env, cwd=_ROOT)
    assert r.returncode not in (0, 134), r.stdout + r.stderr
    assert "no TPU found" in r.stdout
    assert not any(line.lstrip().startswith("{")
                   for line in r.stdout.splitlines()), r.stdout

"""Per-step training telemetry: tokens/sec/chip, MFU, loss, skips.

`TrainingTelemetry` turns (tokens, step wall time) into tokens/sec and
an MFU estimate as a runtime reporter, from the program's one count of a
decoder's training FLOPs a token (`flops_per_token_for`: model work
only, so recomputed layers count once) and the one per-chip peaks table
(device/peaks.py), publishing gauges/histograms into the shared metrics
registry. `parallel/trainer.py` drives it when
observability is enabled; the cost when disabled is one attribute
check in Trainer.step.

Two measurement caveats, both deliberate:
  - step time is the interval between consecutive step() dispatches.
    Dispatch is async, but donated buffers backpressure the host, so
    in steady state the interval converges to device step time.
  - the loss gauge lags `loss_lag` steps: a loss read that young would
    force a host sync and stall the dispatch pipeline; by the time a
    step is `loss_lag` old its value is already on host and float() is
    free.

Importing this module never touches jax; model-specific helpers import
lazily inside functions.
"""
from __future__ import annotations

import collections

from paddle_tpu.observability import metrics as _metrics

__all__ = ["detect_peak_flops", "flops_per_token_for",
           "TrainingTelemetry"]


def detect_peak_flops():
    """bf16 peak FLOP/s of device 0 from the one peaks table, or None
    off-TPU (MFU reads 0 there). An unknown TPU kind raises."""
    from paddle_tpu.device.peaks import detect_peaks
    peaks = detect_peaks()
    return None if peaks is None else peaks.bf16_flops


def _decoder_flops_per_token(cfg, seq_len: int) -> float:
    """Forward + backward of a dense decoder: 6 x the weights every token
    is multiplied by (the layers' matmuls and hidden x vocabulary, tied
    or not; the embedding lookup is a gather) + causal attention, QK^T
    and PV over half the square, three times for training:
    6 x layers x heads x head_dim x seq."""
    d, f = cfg.hidden_size, cfg.intermediate_size
    h, hkv = cfg.num_attention_heads, cfg.num_key_value_heads
    hd = getattr(cfg, "head_dim", None) or d // h
    layer = d * h * hd + 2 * d * hkv * hd + h * hd * d + 3 * d * f
    matmul = cfg.num_hidden_layers * layer + d * cfg.vocab_size
    return 6.0 * matmul + 6.0 * cfg.num_hidden_layers * h * hd * seq_len


def flops_per_token_for(model, seq_len: int) -> float:
    """Training FLOPs/token for `model`: the decoder count above when
    the config quacks like a llama; otherwise the generic 6 x
    trainable-param-count estimate. Recomputed work is not model work,
    so `recompute` changes nothing here."""
    cfg = getattr(model, "config", None)
    if cfg is not None:
        try:
            return _decoder_flops_per_token(cfg, seq_len)
        except AttributeError:
            pass
    n = 0
    for p in getattr(model, "parameters", lambda: [])():
        if not getattr(p, "stop_gradient", False):
            n += int(getattr(p, "size", 0) or 0)
    return 6.0 * n


class TrainingTelemetry:
    """Per-step reporter publishing into a metrics registry.

    flops_per_token: float, or a callable seq_len -> float (so the
    attention term can track the batch's actual sequence length).
    peak_flops: per-chip peak FLOP/s; None disables MFU (reports 0).
    """

    def __init__(self, flops_per_token=None, peak_flops=None,
                 registry=None, loss_lag=8):
        self._fpt = flops_per_token
        self.peak_flops = peak_flops
        self.registry = registry if registry is not None \
            else _metrics.REGISTRY
        self.loss_lag = max(0, int(loss_lag))
        self._loss_buf: collections.deque = collections.deque()
        self.steps = 0
        self.last_tokens_per_sec = 0.0
        self.last_mfu = 0.0
        self.last_loss = None

    @classmethod
    def for_model(cls, model, registry=None, peak_flops=None, **kw):
        """Reporter bound to `model`'s analytic flops-per-token and the
        detected chip peak."""
        if peak_flops is None:
            peak_flops = detect_peak_flops()
        return cls(
            flops_per_token=lambda seq: flops_per_token_for(model, seq),
            peak_flops=peak_flops, registry=registry, **kw)

    def flops_per_token(self, seq_len) -> float:
        if callable(self._fpt):
            return float(self._fpt(seq_len))
        return float(self._fpt or 0.0)

    def mfu(self, tokens_per_sec, seq_len) -> float:
        """tokens/sec/chip x FLOPs/token / chip peak."""
        if not self.peak_flops:
            return 0.0
        return tokens_per_sec * self.flops_per_token(seq_len) \
            / self.peak_flops

    def step(self, tokens, step_time_s, seq_len=None, loss=None,
             grad_norm=None):
        """Report one completed step. `loss` may be lazy (a jax array /
        Tensor); it is buffered and materialized `loss_lag` steps
        later, never blocking the current dispatch."""
        reg = self.registry
        self.steps += 1
        reg.inc("train.steps")
        if step_time_s and step_time_s > 0:
            reg.observe("train.step.seconds", step_time_s)
            tps = tokens / step_time_s
            self.last_tokens_per_sec = tps
            reg.set_gauge("train.tokens_per_sec", tps)
            seq = seq_len if seq_len is not None else tokens
            self.last_mfu = self.mfu(tps, seq)
            reg.set_gauge("train.mfu", self.last_mfu)
        if grad_norm is not None:
            reg.set_gauge("train.grad_norm", float(grad_norm))
        if loss is not None:
            self._loss_buf.append(loss)
            while len(self._loss_buf) > self.loss_lag:
                self._publish_loss(self._loss_buf.popleft())

    def _publish_loss(self, loss):
        try:
            val = float(loss)
        except Exception:
            return              # non-scalar / dead array: drop silently
        self.last_loss = val
        self.registry.set_gauge("train.loss", val)

    def flush(self):
        """Materialize every buffered loss (end of run / snapshot)."""
        while self._loss_buf:
            self._publish_loss(self._loss_buf.popleft())

    def snapshot(self) -> dict:
        self.flush()
        return {"steps": self.steps,
                "tokens_per_sec": round(self.last_tokens_per_sec, 2),
                "mfu": round(self.last_mfu, 4),
                "loss": self.last_loss}

"""Throughput/MFU metering, profiler tracing, and the JSONL step log.

The reference has no profiling at all — its only throughput signal
is tqdm's implicit it/s counter (reference main-single.py:81; SURVEY §5).
Since the driver-defined baseline metric is tokens/sec/chip and MFU
(BASELINE.md), the meter is built into the trainer rather than bolted on:

  - `MFUMeter`: step timing -> tokens/sec, tokens/sec/chip, and model FLOPs
    utilization against the chip's peak bf16 FLOPs.
  - `profiler_trace` context: wraps `jax.profiler.trace` when a profile
    dir is set (request-scoped SERVING traces live in `tpukit.obs.trace`).
  - `StepLogger`: machine-readable JSONL step metrics (the surface
    `tools/report.py` renders).

FLOPs model (PaLM-appendix convention): per token, a forward pass costs
`2 * P_matmul` for the parameter matmuls plus `4 * S * inner_dim` per layer
for the attention score/value matmuls; training costs 3x forward (backward
is 2x). Embedding-table gathers are excluded from P_matmul; the lm_head is
included.
"""

from __future__ import annotations

import contextlib
import json
import time

import jax

from tpukit.model.gpt import GPTConfig

# Peak dense bf16 FLOP/s per chip, keyed by the EXACT `device_kind` string
# jax reports (a v5e chip reports "TPU v5 lite"). Source: Google Cloud TPU
# documentation, the system-architecture page of each generation ("TPU v5e":
# 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s).
_PEAK_FLOPS = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,
    "TPU v5e": 197e12,
    "TPU v5": 459e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,
    "TPU v6e": 918e12,
}


def peak_flops_per_chip(device_kind: str | None = None) -> float | None:
    """Peak bf16 FLOP/s of one chip of `device_kind` (default: the first
    device). None off-TPU — MFU is undefined on the CPU test backend. A TPU
    kind missing from the table raises: a guessed peak is a wrong MFU."""
    kind = device_kind or jax.devices()[0].device_kind
    if kind in _PEAK_FLOPS:
        return _PEAK_FLOPS[kind]
    if kind.startswith("TPU"):
        raise ValueError(
            f"no peak FLOP/s on record for TPU device_kind {kind!r}; add it "
            f"to tpukit/obs/meter.py:_PEAK_FLOPS with its source (known: "
            f"{sorted(_PEAK_FLOPS)})"
        )
    return None


def matmul_param_count(cfg: GPTConfig) -> int:
    """Parameters that participate in matmuls (excludes embedding gathers).
    The lm_head runs at the padded vocab width — count the FLOPs actually
    executed, not the logical vocab."""
    inner = cfg.inner_dim
    per_layer = 3 * cfg.dim * inner + inner * cfg.dim + 2 * cfg.dim * (cfg.dim * cfg.ffn_mult)
    return cfg.num_layers * per_layer + cfg.dim * cfg.padded_vocab_size


def train_flops_per_token(cfg: GPTConfig, seq_len: int) -> float:
    """fwd (2*P + attention) x3 for fwd+bwd."""
    attn = 4 * seq_len * cfg.inner_dim * cfg.num_layers
    return 3 * (2 * matmul_param_count(cfg) + attn)


class MFUMeter:
    """Rolling tokens/sec + MFU over recent steps. `update()` once per step
    with the number of (real, global) tokens processed."""

    def __init__(self, cfg: GPTConfig, seq_len: int, num_chips: int | None = None):
        self.flops_per_token = train_flops_per_token(cfg, seq_len)
        self.num_chips = num_chips or len(jax.devices())
        self.peak = peak_flops_per_chip()
        self.reset()

    def reset(self):
        self._t0 = None
        self._tokens = 0
        self._steps = 0

    def update(self, tokens: int):
        now = time.perf_counter()
        if self._t0 is None:
            self._t0 = now  # first update starts the clock (skips compile)
            return
        self._tokens += tokens
        self._steps += 1
        self._elapsed = now - self._t0

    @property
    def total_tokens(self) -> int:
        """Global real tokens accumulated (timed steps only — the first
        update starts the clock and is not counted)."""
        return self._tokens

    @property
    def tokens_per_sec(self) -> float | None:
        if self._steps == 0 or self._elapsed == 0:
            return None
        return self._tokens / self._elapsed

    @property
    def tokens_per_sec_per_chip(self) -> float | None:
        tps = self.tokens_per_sec
        return tps / self.num_chips if tps else None

    @property
    def mfu(self) -> float | None:
        tps = self.tokens_per_sec_per_chip
        if tps is None or self.peak is None:
            return None
        return tps * self.flops_per_token / self.peak


@contextlib.contextmanager
def profiler_trace(profile_dir: str = ""):
    """jax.profiler trace hook (SURVEY §5 tracing plan). No-op when unset.
    (`tpukit.obs.trace` is the request-scoped serving-trace MODULE; the
    profiler hook carries this unambiguous name.)"""
    if profile_dir:
        with jax.profiler.trace(profile_dir):
            yield
    else:
        yield


class StepLogger:
    """JSONL step-metrics log — the machine-readable observability surface
    the reference lacks (SURVEY §5 metrics plan). No-op when path is empty.

    Schema (docs/DESIGN.md "Telemetry & observability"): one JSON object
    per line, discriminated by `kind` — "train" window records, "validation"
    epoch records, "xla" once-per-compile static analysis, "epoch" span
    summaries, "spike" sentinel events, "compile_cache" hit/miss counts.
    `tools/report.py` renders a run.

    Hot-loop I/O discipline (round 7): the stream is opened ONCE,
    line-buffered, and each record is a single `write` of one complete
    line — no explicit per-record flush call, no reopen. Line buffering
    still pushes every record to the OS at its newline, so the worst a
    crash can leave is one torn final line — exactly what report.py's
    loader tolerates.
    """

    def __init__(self, path: str = ""):
        # buffering=1 = line-buffered text: the newline inside the single
        # write below is the flush point
        self._f = open(path, "a", buffering=1) if path else None

    def log(self, **record):
        if self._f is None:
            return
        record.setdefault("time", time.time())
        self._f.write(json.dumps(record) + "\n")  # one write per record

    def close(self):
        if self._f:
            self._f.close()
            self._f = None

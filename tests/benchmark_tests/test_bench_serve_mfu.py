"""`mfu_pct.tput`: the share of the chip's bf16 peak behind the output tokens
of a traced serving window. The reader on a hand-built record whose answer is
worked out here, at gpt2-medium's widths against the ledger's rate, on records
that lack what it reads, and in a traced CPU rehearsal (no peaks: no number)."""

from pathlib import Path
from types import SimpleNamespace

import pytest
from test_bench_rehearsal import cell, tiny_root  # noqa: F401  (the rehearsal's own fixture, built the same way)
from test_bench_serve_host import QUANTA, TRAFFIC

from benchmark import common, peaks

ROOT = Path(__file__).resolve().parents[2]

# dim 8, two heads of 4, two layers, vocabulary padded to 16:
#   a layer's matmul parameters = q, k, v 3 x 8 x 8 + out 8 x 8 + FFN 2 x 8 x 32 = 768
#   all = 2 x 768 + head 8 x 16 = 1,664; a forward of one token = 2 x 1,664 = 3,328 FLOP
# QUANTA deliver 8 + 6 + 7 = 21 tokens; over a 0.5 s window that is 42 tokens/s,
# 42 x 3,328 = 139,776 FLOP/s; of a 1 MFLOP/s peak, 13.9776 %.
CFG = SimpleNamespace(dim=8, heads=2, head_dim=4, num_layers=2, padded_vocab_size=16, ffn_mult=4)
PEAKS = {"flops_bf16": 1e6, "hbm_bytes_per_s": 1e6}


def read(rec):
    return common.load_by_name("layer_metrics", "mfu_pct.tput", ROOT).read(rec)


def record(**over):
    return {"quanta": QUANTA, "traffic": TRAFFIC, "cfg": CFG, "peaks": PEAKS, "window_s": 0.5, **over}


def test_mfu_pct_tput_on_a_worked_record():
    assert read(record()) == pytest.approx(13.9776)
    # attention and prompt tokens are not credited: only what was delivered counts, whatever the steps
    assert read(record(quanta=[dict(q, steps=4) for q in QUANTA])) == pytest.approx(13.9776)
    assert read(record(window_s=1.0)) == pytest.approx(13.9776 / 2)


def test_mfu_pct_tput_at_the_cells_widths_reads_what_the_ledger_implies():
    """gpt2-medium on a v5e at the ledger's 300.78 tokens/s (PR 25): 353,501,184
    matmul parameters, 0.707 GFLOP a token, 0.108 % of 197 TFLOP/s."""
    cfg = common.gpt_config(common.load_json(ROOT / "benchmark" / "configs" / "gpt2-medium.json"))
    quanta = [{"delivered": 30078, "steps": 4}]
    value = read(record(cfg=cfg, quanta=quanta, window_s=100.0, peaks=peaks.peaks("TPU v5 lite")))
    assert value == pytest.approx(100 * 300.78 * 2 * 353_501_184 / 197e12)
    assert 0.10 < value < 0.11


@pytest.mark.parametrize("missing", ["quanta", "cfg", "peaks", "window_s", "delivered"])
def test_a_record_without_what_it_reads_is_nothing(missing):
    """A program whose `quantum` events carry no `delivered` (before PR 25), a
    CPU rehearsal (no peaks) and an untraced record: None, never a number,
    and no exception."""
    if missing == "delivered":
        rec = record(quanta=[{k: v for k, v in q.items() if k != "delivered"} for q in QUANTA])
    else:
        rec = record(**{missing: None})
        assert read({k: v for k, v in rec.items() if k != missing}) is None
    assert read(rec) is None
    assert read(record(quanta=[])) is None


def test_traced_serving_rehearsal_leaves_the_device_share_out_on_the_cpu(tiny_root):  # noqa: F811
    """The cell lists the metric; the CPU has no peak, so the reader finds
    nothing and the line leaves it out beside the metrics that are there."""
    import json

    bench = json.loads((tiny_root / "BENCHMARK.json").read_text())
    entry = next(m for m in bench["per_layer"] if m["name"] == "mfu_pct.tput")
    assert "tiny.sat" in entry["workloads"] and entry["moves"] == "serve_out_tokens_per_s"
    out = cell(tiny_root, "tiny.sat", trace=True, seconds=0.4)
    assert out["correct"] is True
    assert "mfu_pct.tput" not in out["metrics"] and "slot_occupancy_pct.tput" in out["metrics"]

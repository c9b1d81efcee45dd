#!/usr/bin/env python
"""Render a tpukit metrics JSONL (`--metrics_log run.jsonl`) into a
human-readable run summary.

The trainer's StepLogger writes one JSON object per line, discriminated by
`kind` (docs/DESIGN.md "Telemetry & observability"): "train" window records
(loss, tokens/sec, MFU, goodput breakdown, HBM gauges, optional norms),
"xla" once-per-compile static analysis (FLOPs, bytes, peak memory,
per-collective comm bytes), "validation"/"epoch" per-epoch records,
"spike"/"straggler" sentinel events, and "compile_cache" hit/miss counts.
Train windows from a prefetching run additionally carry
`prefetch_stall_s`/`prefetch_occupancy` (round-7 host overlap), rendered
in the training section. Round-8 failure observability adds "watchdog"
(hang/bundle events — bundles themselves render via tools/flightview.py),
"divergence"/"divergence_check" (cross-replica checksums), and
"anomaly_trace" (trace-on-anomaly lifecycle). Round-9 recovery adds
"rollback" (in-process restores: count, steps lost, quarantined
checkpoints), "preempt" (graceful SIGTERM/SIGINT checkpoint-and-exit),
"retry" (transient host-I/O attempts absorbed by backoff), and "chaos"
(the fault-injection audit trail). Round-10 expert parallelism adds an
all-to-all dispatch audit line to the "xla" section (the strategy's
closed-form payload vs the compiled HLO's). Round 12 adds the quantized
grad-collective audit line to the "xla" section (--comm_dtype: the
closed-form compressed payload vs the compiled HLO, dtype-aware so it is
exact on CPU too). Round-13 elastic resize adds "resize"
(reshard-on-restore: the topology change, bytes read, stale files swept)
and "ckpt_prune" (--keep_checkpoints retention) to the recovery section.
Round-14 serving adds "serve" (per-window continuous-batching telemetry:
tokens/s, slot occupancy, admit/evict counts, prefill/decode/sync wall
split, latency percentiles) and "serve_summary" (whole-run serving
headline) rendered as a "== serving ==" section. Round-17 speculative
decoding adds the spec block on serve windows/summaries (acceptance rate,
accepted-tokens histogram, draft/verify wall split) and the
`--min_accept_rate` gate. Round-20 request tracing adds
"trace_event"/"trace" rows (raw span events and per-request span trees —
rendered in depth by tools/traceview.py), per-phase p50/p99 +
dispatch-vs-device attribution on serve/fleet summaries, and the
`--min_trace_complete` completeness-invariant gate. Round-22 metrics
plane adds "slo" rows (per-window compliance + error-budget burn per
`--slo` target) and "metrics" epilogues (compact per-series summaries
from tpukit/obs/metrics.py), rendered as "== slo ==" / "== metrics =="
sections; `--compare baseline.jsonl` diffs two runs' metric summaries
(per-histogram p50/p99 deltas plus the tokens/s headline); the
`--min_slo_compliance` and `--max_regression_pct` gates CI them. Every
section renders what `fit()`, `ServeEngine`, the fleet and the metrics
plane write themselves; a rate printed here is the run's own wall clock
on whatever machine wrote the log — the chip's rates are the benchmark's
(`benchmark/run.py`, `PERF_LEDGER.jsonl`). The per-gate argparse/dispatch
boilerplate is the declarative GATES table below — one row per gate.
This tool needs NOTHING but the file — no jax import, so it runs anywhere
the log was copied to.

Usage: python tools/report.py run.jsonl [--min_goodput 0.8]
                                        [--min_accept_rate 0.3]
                                        [--max_deadline_miss_pct 0]
                                        [--min_trace_complete 1.0]
                                        [--min_slo_compliance 0.99]
                                        [--compare baseline.jsonl]
                                        [--max_regression_pct 10]
"""

from __future__ import annotations

import argparse
import json
import sys


def human_bytes(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for unit in ("B", "KiB", "MiB", "GiB", "TiB"):
        if abs(n) < 1024 or unit == "TiB":
            return f"{n:.2f} {unit}" if unit != "B" else f"{int(n)} B"
        n /= 1024
    return f"{n:.2f} TiB"


def human_count(n) -> str:
    if n is None:
        return "-"
    n = float(n)
    for scale, suffix in ((1e12, "T"), (1e9, "G"), (1e6, "M"), (1e3, "k")):
        if abs(n) >= scale:
            return f"{n / scale:.2f}{suffix}"
    return f"{n:.0f}"


def load(path: str) -> list[dict]:
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            try:
                records.append(json.loads(line))
            except ValueError:
                continue  # torn final line from a killed run
    return records


def _rows(records: list[dict], kind: str) -> list[dict]:
    return [r for r in records if r.get("kind") == kind]


def _fmt_fractions(frac: dict) -> str:
    return " ".join(
        f"{k}={v * 100:.0f}%"
        for k, v in sorted(frac.items(), key=lambda kv: -kv[1])
        if v >= 0.005
    )


def _fmt_labels(labels) -> str:
    """Compact `{k=v,...}` suffix for a metric series; empty labels
    render as nothing."""
    if not labels:
        return ""
    return "{" + ",".join(f"{k}={v}" for k, v in sorted(labels.items())) + "}"


def _fmt_seconds(v) -> str:
    """Latency cell: ms below 1s, seconds above, '-' for empty series."""
    if v is None:
        return "-"
    return f"{v * 1e3:.2f}ms" if v < 1.0 else f"{v:.2f}s"


def _phase_lines(r: dict) -> list[str]:
    """Round-20 request-trace rows on a serve_summary / fleet_summary:
    per-phase p50/p99 walls and the span-tree completeness fraction."""
    out = []
    p50, p99 = r.get("phase_p50"), r.get("phase_p99")
    if isinstance(p50, dict) and isinstance(p99, dict):
        cells = [
            f"{ph} {1e3 * p50[ph]:.1f}/{1e3 * p99[ph]:.1f}"
            for ph in ("queue_wait", "prefill", "handoff", "decode",
                       "sync_stall", "other")
            if p50.get(ph) is not None
        ]
        if cells:
            out.append("  request phases p50/p99 (ms): " + "  ".join(cells))
    comp = r.get("trace_complete")
    if comp is not None:
        out.append(f"  traces: {100 * comp:.0f}% complete span trees"
                   + ("" if comp >= 1.0 else "  <- INCOMPLETE TREES"))
    # round-22: the recorder's ring evictions, surfaced per summary — a
    # saturated ring silently truncates span trees, so a nonzero count
    # gets a visible warning instead of hiding in the raw record
    dropped = r.get("trace_dropped")
    if dropped:
        by_rep = r.get("trace_dropped_by_replica")
        out.append(
            f"  trace ring evicted {dropped} span event(s)"
            + (f" ({', '.join(f'r{k}: {v}' for k, v in sorted(by_rep.items()))})"
               if by_rep else "")
            + "  <- DROPPED EVENTS (grow --trace_capacity)")
    slo_c = r.get("slo_overall_compliance")
    if slo_c is not None:
        out.append(f"  slo: overall compliance {100 * slo_c:.2f}%"
                   + ("" if slo_c >= 1.0 else "  (see == slo ==)"))
    return out


def summarize(records: list[dict]) -> str:
    out: list[str] = []
    w = out.append

    train = _rows(records, "train")
    times = [r["time"] for r in records if "time" in r]
    w("== run ==")
    if times:
        w(f"  duration: {max(times) - min(times):.1f}s "
          f"({len(records)} records, {len(train)} train windows)")

    if train:
        last = train[-1]
        losses = [r["loss"] for r in train if r.get("loss") is not None]
        tps = [r["tokens_per_sec"] for r in train if r.get("tokens_per_sec")]
        mfu = [r["mfu"] for r in train if r.get("mfu")]
        w("== training ==")
        w(f"  steps: {last.get('step', '-')}   "
          f"loss first->last: {losses[0]:.4f} -> {losses[-1]:.4f}   "
          f"min: {min(losses):.4f}")
        if tps:
            w(f"  tokens/sec last: {human_count(tps[-1])}   best: {human_count(max(tps))}"
              + (f"   MFU last: {mfu[-1] * 100:.1f}%   best: {max(mfu) * 100:.1f}%"
                 if mfu else ""))
        goodput = [r["goodput"] for r in train if r.get("goodput") is not None]
        if goodput:
            mean_gp = sum(goodput) / len(goodput)
            w(f"  goodput (time in compiled step): mean {mean_gp * 100:.1f}%  "
              f"min {min(goodput) * 100:.1f}%")
            span_keys: dict[str, list[float]] = {}
            for r in train:
                for k, v in (r.get("spans") or {}).items():
                    span_keys.setdefault(k, []).append(v)
            w("  span split (mean): "
              + _fmt_fractions({k: sum(v) / len(v) for k, v in span_keys.items()}))
        # round-7 prefetch gauges: how much of the window wall-clock the
        # training thread still blocked on input AFTER overlap, and how
        # full the prefetch buffer ran (near-depth = producer ahead,
        # near-0 = input bound)
        pstall = [
            (r["prefetch_stall_s"], r.get("window_s", 0.0))
            for r in train
            if r.get("prefetch_stall_s") is not None
        ]
        if pstall:
            tot_win = sum(wsec for _, wsec in pstall)
            share = sum(s for s, _ in pstall) / tot_win if tot_win else 0.0
            occ = [
                r["prefetch_occupancy"] for r in train
                if r.get("prefetch_occupancy") is not None
            ]
            w(f"  prefetch: stall {share * 100:.1f}% of window wall-clock"
              + (f"   buffer occupancy mean {sum(occ) / len(occ):.2f}"
                 if occ else ""))
        hbm_peaks = [
            (r.get("hbm") or {}).get("peak_bytes_in_use")
            or (r.get("hbm") or {}).get("bytes_in_use")
            for r in train
        ]
        hbm_peaks = [p for p in hbm_peaks if p]
        if hbm_peaks:
            limit = next(
                ((r.get("hbm") or {}).get("bytes_limit") for r in train
                 if (r.get("hbm") or {}).get("bytes_limit")), None)
            w(f"  peak HBM in use: {human_bytes(max(hbm_peaks))}"
              + (f" of {human_bytes(limit)}" if limit else ""))
        norms = [r for r in train if "grad_norm" in r]
        if norms:
            gn = [r["grad_norm"] for r in norms]
            w(f"  grad norm last: {gn[-1]:.4g}   max: {max(gn):.4g}   "
              f"param norm last: {norms[-1].get('param_norm', float('nan')):.4g}")

    for r in _rows(records, "xla"):
        w(f"== xla static analysis: {r.get('fn', '?')} "
          f"[{r.get('strategy', '?')}] ==")
        w(f"  flops/step: {human_count(r.get('flops'))}   "
          f"bytes accessed/step: {human_bytes(r.get('bytes_accessed'))}")
        mem = r.get("memory") or {}
        if mem:
            w(f"  memory: args {human_bytes(mem.get('argument_size_in_bytes'))}  "
              f"temp {human_bytes(mem.get('temp_size_in_bytes'))}  "
              f"peak est {human_bytes(mem.get('peak_bytes_estimate'))}")
        coll = r.get("collectives") or {}
        # Declared-empty (comm_ops = (), e.g. single device: EVERY collective
        # is a surprise) is distinct from undeclared (key absent in a foreign
        # log: nothing can be flagged).
        raw_expected = r.get("expected_comm_ops")
        expected = None if raw_expected is None else set(raw_expected)
        if coll:
            w("  comm bytes/step (from compiled HLO):")
            for op, rec in sorted(coll.items(), key=lambda kv: -kv[1]["bytes"]):
                flag = (
                    "  <- UNEXPECTED"
                    if expected is not None and op not in expected
                    else ""
                )
                w(f"    {op:<20} x{rec['count']:<4} {human_bytes(rec['bytes'])}{flag}")
        elif expected:
            w(f"  comm: none found (strategy expected {sorted(expected)})")
        # round-10 hand-scheduled dispatch audit: the strategy's closed-form
        # all-to-all payload vs what the compiled HLO actually moves.
        # Round-12 expectations carry a "wire" marker: the formula already
        # priced in the backend's wire dtype (XLA:CPU upcasts bf16 payloads
        # to f32), so bytes compare EXACTLY — no soft excuse. Older logs
        # without the marker keep the CPU bf16-upcast allowance.
        a2a_exp = r.get("a2a_expected")
        if a2a_exp is not None:
            meas = coll.get("all-to-all") or {"count": 0, "bytes": 0}
            count_ok = meas["count"] == a2a_exp.get("count")
            bytes_ok = meas["bytes"] == a2a_exp.get("bytes")
            dtype_aware = a2a_exp.get("wire") is not None
            if count_ok and bytes_ok:
                verdict = "  OK"
            elif count_ok and not dtype_aware and r.get("backend") == "cpu":
                # pre-round-12 record: the expectation was the nominal
                # accelerator size, so CPU's bf16->f32 upcast doubled the
                # measured bytes legitimately
                verdict = "  counts OK (bytes differ: CPU bf16-upcast)"
            else:
                verdict = "  <- MISMATCH"
            w(f"  all-to-all dispatch audit: measured x{meas['count']} "
              f"{human_bytes(meas['bytes'])} vs expected "
              f"x{a2a_exp.get('count')} {human_bytes(a2a_exp.get('bytes'))}"
              + (f" [{a2a_exp['wire']}]" if dtype_aware else "")
              + verdict)
        # round-12 quantized grad-collective audit (--comm_dtype): the
        # closed-form compressed grad payload (ddp two-shot all-reduce /
        # fsdp reduce-scatter a2a) vs the compiled HLO, op kind by op kind.
        # Always dtype-aware, so a byte drift is a hard flag everywhere.
        gexp = r.get("quant_grad_expected")
        if gexp is not None:
            w(f"  quantized grad audit (--comm_dtype "
              f"{r.get('comm_dtype', '?')}):")
            for op, rec in sorted(gexp.items()):
                meas = coll.get(op) or {"count": 0, "bytes": 0}
                ok = (meas["count"] == rec["count"]
                      and meas["bytes"] == rec["bytes"])
                w(f"    {op:<12} measured x{meas['count']} "
                  f"{human_bytes(meas['bytes'])} vs expected x{rec['count']} "
                  f"{human_bytes(rec['bytes'])}"
                  + ("  OK" if ok else "  <- MISMATCH"))
        # round-16 hlolint verdicts (tpukit/analysis): the rule-engine
        # summary fit() stamped on the record — CommPlan diff + the named
        # anti-pattern rules, one line unless something fired.
        hl = r.get("hlolint")
        if hl is not None:
            if hl.get("clean"):
                line = "  hlolint: clean"
            else:
                line = (f"  hlolint: {hl.get('errors', '?')} violation(s) "
                        f"<- {', '.join(hl.get('violations') or [])}")
            if hl.get("warnings"):
                line += (f"   ({hl['warnings']} warning(s): "
                         f"{', '.join(hl.get('warned') or [])})")
            ov = hl.get("overlap")
            if ov:
                line += (f"   overlap: {ov.get('overlapped', 0)}/"
                         f"{ov.get('pairs', 0)} async pairs hide compute")
            og = hl.get("overlap_gate")
            if og:
                line += (f"   overlap gate: {og.get('overlappable', 0)}/"
                         f"{og.get('declared', 0)} bucket wires hidden"
                         + (" OK" if og.get("ok") else " <- FAIL"))
            w(line)

    # standalone hlolint findings (tools/hlolint.py --out, or its JSONL
    # appended to a run log): grouped by world/source, errors first
    hlolint_rows = _rows(records, "hlolint")
    if hlolint_rows:
        w("== xla static analysis: hlolint findings ==")
        by_src: dict[str, list] = {}
        for r in hlolint_rows:
            by_src.setdefault(r.get("world") or r.get("source") or "?", []).append(r)
        for src, rows in sorted(by_src.items()):
            errs = sum(1 for r in rows if r.get("severity") == "error")
            w(f"  {src}: {len(rows)} finding(s), {errs} error(s)")
            for r in rows:
                w(f"    [{r.get('severity', '?'):<5}] {r.get('rule', '?')}: "
                  f"{r.get('message', '')}")

    val = _rows(records, "validation")
    epochs = _rows(records, "epoch")
    if val or epochs:
        w("== epochs ==")
    for r in val:
        w(f"  epoch {r.get('epoch', '?')}: val loss {r.get('loss', float('nan')):.4f}  "
          f"accuracy {r.get('accuracy', float('nan')):.2f}%")
    for r in epochs:
        w(f"  epoch {r.get('epoch', '?')} wallclock {r.get('total_s', 0):.1f}s  "
          f"goodput {r.get('goodput', 0) * 100:.1f}%  "
          f"[{_fmt_fractions(r.get('fractions') or {})}]")

    spikes = _rows(records, "spike")
    if spikes:
        w("== sentinel events ==")
        for r in spikes:
            w(f"  {r.get('event', '?'):<6} step {r.get('step', '?'):<8} "
              f"loss {r.get('loss')}"
              + (f"  (mean {r['mean']:.4f} std {r['std']:.4f})"
                 if r.get("mean") is not None else "")
              + f"  action={r.get('action', '?')}")
    stragglers = _rows(records, "straggler")
    if stragglers:
        w("== stragglers ==")
        for r in stragglers:
            w(f"  step {r.get('step', '?')}: {r.get('stragglers')}")
    # round-9 recovery: in-process rollbacks, graceful preemption, retried
    # transient I/O, and the chaos audit trail; round-13 elastic resizes
    # (reshard-on-restore) and checkpoint-retention prunes render here too
    # — recovery is the section an operator reads after a relaunch, and a
    # topology change IS a recovery event.
    rollbacks = _rows(records, "rollback")
    preempts = _rows(records, "preempt")
    retries = _rows(records, "retry")
    chaos = _rows(records, "chaos")
    resizes = _rows(records, "resize")
    prunes = _rows(records, "ckpt_prune")
    if rollbacks or preempts or retries or chaos or resizes or prunes:
        w("== recovery ==")
    for r in resizes:
        w(f"  resized: {r.get('mismatch', '?')} — resumed step "
          f"{r.get('step', '?')} from {r.get('checkpoint', '?')} "
          f"({r.get('format', '?')} reshard, "
          f"{human_bytes(r.get('bytes_read'))} in "
          f"{r.get('blocks_read', '?')} blocks, {r.get('wall_s', '?')}s"
          + (f"; swept {len(r['swept'])} stale file(s)"
             if r.get("swept") else "")
          + ")")
    if rollbacks:
        lost = sum(r.get("steps_lost", 0) for r in rollbacks)
        w(f"  rollbacks: {len(rollbacks)}   total steps lost: {lost}")
        for r in rollbacks:
            w(f"    #{r.get('seq', '?')} [{r.get('reason', '?')}] at step "
              f"{r.get('anomaly_step', '?')} -> restored step "
              f"{r.get('target_step', '?')} "
              f"({r.get('steps_lost', '?')} steps lost"
              + (f", {len(r['quarantined'])} checkpoint(s) quarantined"
                 if r.get("quarantined") else "")
              + ")")
    for r in preempts:
        w(f"  preempted: {r.get('signal', '?')} at step {r.get('step', '?')} "
          f"-> checkpoint {r.get('checkpoint', '?')} "
          f"(resume at epoch {r.get('epoch', '?')}, "
          f"batch {r.get('batch_in_epoch', '?')})")
    if retries:
        by_label: dict[str, int] = {}
        for r in retries:
            by_label[r.get("label", "?")] = by_label.get(r.get("label", "?"), 0) + 1
        w(f"  io retries: {len(retries)} ("
          + "  ".join(f"{k} x{v}" for k, v in sorted(by_label.items())) + ")")
    if chaos:
        # occurrence-indexed I/O faults also carry a drain-time "step"
        # (the trainer stamps one on every chaos event), so the
        # occurrence — the index the spec named — must win when present
        w(f"  chaos faults fired: {len(chaos)} ("
          + ", ".join(
              f"{r.get('fault', '?')}@{r.get('occurrence', r.get('step', '?'))}"
              for r in chaos) + ")")
    if prunes:
        total = sum(len(r.get("pruned") or []) for r in prunes)
        w(f"  checkpoint retention: {total} pruned over {len(prunes)} "
          f"sweep(s) (--keep_checkpoints {prunes[-1].get('keep', '?')})")
    # round-8 failure observability: hang-watchdog events, cross-replica
    # divergence, anomaly-trace lifecycle
    watchdog = _rows(records, "watchdog")
    if watchdog:
        w("== watchdog ==")
        for r in watchdog:
            if r.get("event") == "hang":
                w(f"  HANG surfaced at step {r.get('step', '?')} "
                  f"(total {r.get('hangs', '?')}); bundles: "
                  + ", ".join(r.get("bundles") or []))
            else:
                w(f"  bundle [{r.get('reason', '?')}] step {r.get('step', '?')}: "
                  f"{r.get('bundle', '?')}")
        w("  (render a bundle: python tools/flightview.py <bundle.json>)")
    divergence = _rows(records, "divergence")
    if divergence:
        w("== DIVERGENCE ==")
        for r in divergence:
            for m in r.get("mismatches") or []:
                w(f"  step {m.get('checksum_step', '?')}: process "
                  f"{m.get('process', '?')} checksum {m.get('checksum')} "
                  f"!= majority {m.get('expected')}")
    div_checks = _rows(records, "divergence_check")
    if div_checks:
        last = div_checks[-1]
        w("== divergence checks ==")
        w(f"  {len(div_checks)} checks"
          + (", no mismatches" if not divergence else "")
          + f"; last: step {last.get('step', '?')} "
          f"checksum {last.get('checksum')}")
    traces = _rows(records, "anomaly_trace")
    if traces:
        w("== anomaly trace ==")
        for r in traces:
            ev = r.get("event", "?")
            line = f"  {ev} at step {r.get('step', '?')}"
            if ev == "armed":
                line += f" (reason: {r.get('reason', '?')})"
            if r.get("dir"):
                line += f" -> {r['dir']}"
            w(line)
    # round-14 serving (tpukit/serve): per-window continuous-batching
    # telemetry + the whole-run summary. Rendered for both a recipe-9
    # --metrics_log and any log a ServeEngine wrote into.
    serve_wins = _rows(records, "serve")
    serve_sums = _rows(records, "serve_summary")
    if serve_wins or serve_sums:
        w("== serving ==")
    for r in serve_sums:
        w(f"  {r.get('requests', '?')} requests over {r.get('slots', '?')} "
          f"slots (buckets {r.get('buckets', '?')}, used "
          f"{r.get('buckets_used', '?')}): "
          f"{human_count(r.get('tokens_per_sec'))} tokens/s  "
          f"occupancy {100 * (r.get('mean_occupancy') or 0):.0f}%")
        p50e, p99e = r.get("p50_e2e_s"), r.get("p99_e2e_s")
        p50t, p99t = r.get("p50_token_s"), r.get("p99_token_s")
        if p50e is not None:
            w(f"  latency e2e p50/p99: {p50e * 1e3:.1f}/{p99e * 1e3:.1f} ms   "
              f"per-token p50/p99: {p50t * 1e3:.2f}/{p99t * 1e3:.2f} ms")
        w(f"  {r.get('generated_tokens', '?')} tokens in "
          f"{r.get('decode_steps', '?')} decode steps over "
          f"{r.get('wall_s', 0):.2f}s  (prefill {r.get('prefill_s', 0):.2f}s"
          f" / decode {r.get('decode_s', 0):.2f}s"
          f" / sync {r.get('sync_s', 0):.2f}s"
          + (f" / other {r['other_s']:.2f}s" if r.get("other_s") is not None
             else "")
          + f")   evicted: "
          f"{r.get('evicted_eos', 0)} eos, {r.get('evicted_length', 0)} length")
        for ln in _phase_lines(r):
            w(ln)
        # round-15 paged KV: pool pressure + the prefill work prefix
        # reuse deleted (fields only present on paged runs)
        if r.get("page_size"):
            hit_s = r.get("admit_latency_hit_s")
            cold_s = r.get("admit_latency_cold_s")
            w(f"  paged KV: {r.get('num_pages', '?')} pages x "
              f"{r.get('page_size', '?')} tokens ({r.get('kv_dtype', '?')}), "
              f"occupancy {100 * (r.get('page_occupancy') or 0):.0f}%, "
              f"{r.get('pages_per_request') or 0:.1f} pages/request   "
              f"prefix hits {r.get('prefix_hits', 0)} "
              f"({100 * (r.get('prefix_hit_rate') or 0):.0f}%), "
              f"{r.get('prefix_pages_reused', 0)} pages skipped"
              + (f"   admit hit/cold {hit_s * 1e3:.1f}/{cold_s * 1e3:.1f} ms"
                 if hit_s is not None and cold_s is not None else ""))
        # round-17 speculative decoding: acceptance health + the
        # draft/verify wall split (fields only present on --draft runs)
        sp = r.get("spec")
        if isinstance(sp, dict):
            rate = sp.get("accept_rate")
            w(f"  speculative ({sp.get('draft', '?')}, k={sp.get('k', '?')}): "
              f"accepted {sp.get('accepted', 0)}/{sp.get('proposed', 0)} "
              f"draft tokens"
              + (f" ({100 * rate:.0f}%)" if rate is not None else "")
              + (f"   draft {r.get('draft_s', 0):.2f}s / verify "
                 f"{r.get('verify_s', 0):.2f}s"))
            hist = sp.get("accepted_hist")
            if hist:
                total = max(sum(hist), 1)
                w("  appended/verify histogram: "
                  + "  ".join(f"{i}:{100 * h / total:.0f}%"
                              for i, h in enumerate(hist)))
    if serve_wins:
        occ = [r["occupancy"] for r in serve_wins if r.get("occupancy") is not None]
        tps = [r["tokens_per_sec"] for r in serve_wins if r.get("tokens_per_sec")]
        w(f"  {len(serve_wins)} serve windows: occupancy mean "
          f"{100 * sum(occ) / len(occ):.0f}%"
          + (f"   tokens/s last {human_count(tps[-1])} best "
             f"{human_count(max(tps))}" if tps else "")
          + f"   queue depth last {serve_wins[-1].get('queue_depth', '?')}")

    # round-19 fleet serving (tpukit/serve/fleet): the router's aggregate
    # records plus the per-replica serve windows it tagged — fleet
    # tokens/s, per-replica occupancy spread, fleet p50/p99 e2e latency
    # (ROADMAP #1a), failure/requeue and autoscale accounting.
    fleet_wins = _rows(records, "fleet")
    fleet_sums = _rows(records, "fleet_summary")
    fleet_events = _rows(records, "fleet_event")
    if fleet_wins or fleet_sums:
        w("== fleet ==")
    for r in fleet_sums:
        w(f"  {r.get('requests', '?')} requests over "
          f"{r.get('replicas_final', '?')} replica(s) "
          f"(peak {r.get('replicas_peak', '?')}): "
          f"{human_count(r.get('tokens_per_sec'))} fleet tokens/s  "
          f"({r.get('generated_tokens', '?')} tokens in "
          f"{r.get('wall_s', 0):.2f}s)")
        p50, p99 = r.get("p50_e2e_s"), r.get("p99_e2e_s")
        if p50 is not None:
            w(f"  fleet latency e2e p50/p99: "
              f"{p50 * 1e3:.1f}/{p99 * 1e3:.1f} ms")
        for ln in _phase_lines(r):
            w(ln)
        if r.get("kills") or r.get("requeued"):
            dups = r.get("duplicate_completions", 0)
            w(f"  failures: {r.get('kills', 0)} replica kill(s), "
              f"{r.get('requeued', 0)} request(s) re-queued, "
              f"{dups} duplicate completion(s)"
              + ("" if not dups else "  <- EXACTLY-ONCE VIOLATED"))
        # round-24 fleet recovery: the crash-tolerance plane's accounting —
        # liveness deaths, lease revocation/requeue, deadline misses,
        # backpressure sheds, terminal failures, ledger replay, retried
        # transient I/O. Rendered whenever any of it is nonzero (or a
        # ledger ran), so a clean run stays one line shorter.
        led = r.get("ledger")
        recovery = [r.get("replicas_dead"), r.get("leases_revoked"),
                    r.get("deadline_misses"), r.get("rejected"),
                    r.get("request_failures"), r.get("retry_total"),
                    r.get("respawns")]
        if any(recovery) or isinstance(led, dict):
            n_req = max(r.get("requests") or 0, 1)
            miss = r.get("deadline_misses", 0) or 0
            w(f"  fleet recovery: {r.get('replicas_dead', 0) or 0} liveness "
              f"death(s), {r.get('leases_revoked', 0) or 0} lease(s) "
              f"revoked, {r.get('requeued', 0)} requeued, "
              f"{miss} deadline miss(es) "
              f"({100.0 * miss / n_req:.1f}%), "
              f"{r.get('rejected', 0) or 0} shed by backpressure, "
              f"{r.get('request_failures', 0) or 0} terminal failure(s)"
              + (f", {r.get('retry_total')} transient I/O retried"
                 if r.get("retry_total") else "")
              + (f", {r.get('respawns')} respawn(s)"
                 if r.get("respawns") else ""))
        if isinstance(led, dict):
            w(f"  ledger: {led.get('completed', 0)} durable completion "
              f"record(s), {led.get('replayed', 0)} replayed on restart, "
              f"{led.get('duplicates', 0)} duplicate record(s)"
              + ("" if not led.get("duplicates")
                 else "  <- EXACTLY-ONCE VIOLATED"))
        codes = r.get("worker_exit_codes")
        if isinstance(codes, dict) and codes:
            w("  worker exit codes: " + "  ".join(
                f"r{k}={'SIGKILL' if v == -9 else v}"
                for k, v in sorted(codes.items(), key=lambda kv: str(kv[0]))))
        if r.get("scale_ups") or r.get("scale_downs"):
            w(f"  autoscale: {r.get('scale_ups', 0)} up / "
              f"{r.get('scale_downs', 0)} down")
        dp = r.get("disagg_prefill")
        if isinstance(dp, dict):
            w(f"  disaggregated prefill: {dp.get('handoffs', 0)} handoffs, "
              f"{dp.get('worker_prefix_hits', 0)} worker prefix hits, "
              f"{dp.get('worker_pages_reused', 0)} pages of prefill "
              f"skipped")
        if r.get("params_placements") is not None:
            w(f"  cold start: {r['params_placements']} params placement(s) "
              f"from one host copy")
    # per-replica occupancy spread from the replica-tagged serve windows
    # (each replica is a full engine emitting its own kind="serve" rows)
    by_rep: dict = {}
    for r in serve_wins:
        if r.get("replica") is not None and r.get("occupancy") is not None:
            by_rep.setdefault(r["replica"], []).append(r["occupancy"])
    if by_rep and (fleet_wins or fleet_sums):
        means = {k: sum(v) / len(v) for k, v in sorted(by_rep.items(),
                                                       key=lambda kv: str(kv[0]))}
        spread = (max(means.values()) - min(means.values())
                  if len(means) > 1 else 0.0)
        w("  per-replica occupancy: "
          + "  ".join(f"r{k}={100 * m:.0f}%" for k, m in means.items())
          + f"   spread {100 * spread:.0f}%")
    if fleet_wins:
        occ = [r["occupancy"] for r in fleet_wins
               if r.get("occupancy") is not None]
        tps = [r["tokens_per_sec"] for r in fleet_wins
               if r.get("tokens_per_sec")]
        w(f"  {len(fleet_wins)} fleet windows: occupancy mean "
          f"{100 * sum(occ) / max(len(occ), 1):.0f}%"
          + (f"   tokens/s last {human_count(tps[-1])} best "
             f"{human_count(max(tps))}" if tps else "")
          + f"   queue depth last {fleet_wins[-1].get('queue_depth', '?')}")
    if fleet_events:
        w(f"  events: " + ", ".join(
            f"{r.get('event', '?')}"
            + (f"(r{r['replica']})" if r.get("replica") is not None else "")
            for r in fleet_events))

    # round-22 SLO accounting (tpukit/obs/metrics.py): declared targets,
    # cumulative compliance, and error-budget burn. The LAST record
    # carries the run-level cumulative rows (sample-weighted), earlier
    # ones are per-window snapshots; burn > 1 means the run is consuming
    # error budget faster than the objective allows.
    slo_rows = _rows(records, "slo")
    if slo_rows:
        last = slo_rows[-1]
        w("== slo ==")
        oc = last.get("overall_compliance")
        w(f"  {len(slo_rows)} slo window(s); overall compliance: "
          + (f"{100 * oc:.2f}%" if oc is not None else "no samples"))
        for t in last.get("targets") or []:
            cc, cb = t.get("cum_compliance"), t.get("cum_burn")
            if cc is None:
                w(f"  {t.get('slo', '?'):<20} no samples")
                continue
            met = cc >= (t.get("q") or 0)
            w(f"  {t.get('slo', '?'):<20} compliance {100 * cc:.2f}% "
              f"over {t.get('cum_n', '?')} samples   burn {cb:.2f}x budget"
              + ("" if met else "  <- VIOLATED"))
    # round-22 metrics epilogues: the registry's compact per-series
    # summaries (full bucket tables live in --metrics_dir snapshots).
    # Counters one line, histograms a small table — enough to eyeball a
    # run without the live dashboard (tools/top.py renders the same
    # registry continuously).
    for r in _rows(records, "metrics"):
        w(f"== metrics ({r.get('source', '?')}) ==")
        counters = r.get("counters") or []
        if counters:
            w("  counters: " + "  ".join(
                f"{c['name']}{_fmt_labels(c.get('labels'))}="
                f"{human_count(c['value'])}"
                for c in counters))
        hists = r.get("hists") or []
        if hists:
            w(f"  {'histogram':<36} {'count':>8} {'p50':>10} {'p99':>10}")
            for h in hists:
                p50, p99 = h.get("p50"), h.get("p99")
                # the `_s` suffix convention names the time-valued series;
                # everything else (token counts, ...) renders as a count
                fmt = (_fmt_seconds if h["name"].endswith("_s")
                       else lambda v: "-" if v is None else human_count(v))
                w(f"  {h['name'] + _fmt_labels(h.get('labels')):<36} "
                  f"{human_count(h.get('count')):>8} "
                  f"{fmt(p50):>10} {fmt(p99):>10}")

    cache_rows = _rows(records, "compile_cache")
    if cache_rows:
        w("== compile cache ==")
    for r in cache_rows:
        hits, misses = r.get("hits"), r.get("misses")
        w(f"  {r.get('dir', '?')}: "
          + (f"hits {hits}  misses {misses}  "
             if hits is not None else "")
          + f"entries {r.get('entries', '-')} (+{r.get('new_entries', 0)} this run)")
    return "\n".join(out)


def check_min_goodput(records: list[dict], threshold: float) -> tuple[bool, str]:
    """Cheap perf-regression gate (`--min_goodput`): mean goodput over the
    run's train windows must reach `threshold`. Returns (ok, message)."""
    gp = [
        r["goodput"] for r in _rows(records, "train")
        if r.get("goodput") is not None
    ]
    if not gp:
        return False, "--min_goodput: no train windows with goodput in the log"
    mean_gp = sum(gp) / len(gp)
    verdict = "OK" if mean_gp >= threshold else "FAIL"
    return mean_gp >= threshold, (
        f"--min_goodput {verdict}: mean goodput {mean_gp:.3f} over "
        f"{len(gp)} windows (threshold {threshold:.3f})"
    )


def check_min_accept_rate(records: list[dict], threshold: float) -> tuple[bool, str]:
    """Speculative-decoding health gate (`--min_accept_rate`, round 17):
    the run's `kind="serve_summary"` spec acceptance rate must reach
    `threshold`. Returns (ok, message) — a log without a spec summary
    fails, so the gate can't pass vacuously when someone drops `--draft`
    from the smoke invocation."""
    sums = [r for r in _rows(records, "serve_summary")
            if isinstance(r.get("spec"), dict)
            and r["spec"].get("accept_rate") is not None]
    if not sums:
        return False, ("--min_accept_rate: no serve_summary with a spec "
                       "accept_rate in the log (was the run --draft'ed?)")
    sp = sums[-1]["spec"]
    rate = sp["accept_rate"]
    verdict = "OK" if rate >= threshold else "FAIL"
    return rate >= threshold, (
        f"--min_accept_rate {verdict}: {rate:.3f} "
        f"({sp.get('accepted', 0)}/{sp.get('proposed', 0)} draft tokens, "
        f"{sp.get('draft', '?')} k={sp.get('k', '?')}; "
        f"threshold {threshold:.3f})"
    )


def check_max_deadline_miss_pct(records: list[dict],
                                threshold: float) -> tuple[bool, str]:
    """Deadline-miss CI gate (`--max_deadline_miss_pct`, round 24): the
    last `kind="fleet_summary"` record's deadline_misses as a percentage
    of served requests must be <= `threshold`. Returns (ok, message) — a
    log without a fleet summary, or a summary missing the
    deadline_misses field (a pre-round-24 log), FAILS: the gate can't
    pass vacuously against a run that never accounted deadlines (the
    `--min_accept_rate` discipline)."""
    sums = _rows(records, "fleet_summary")
    if not sums:
        return False, ("--max_deadline_miss_pct: no fleet_summary record "
                       "in the log (was the run --replicas'ed?)")
    s = sums[-1]
    miss = s.get("deadline_misses")
    if miss is None:
        return False, ("--max_deadline_miss_pct: fleet_summary carries no "
                       "deadline_misses field (pre-round-24 log? rerun "
                       "with the current recipe)")
    n_req = s.get("requests") or 0
    pct = 100.0 * miss / n_req if n_req else 0.0
    ok = pct <= threshold
    verdict = "OK" if ok else "FAIL"
    return ok, (
        f"--max_deadline_miss_pct {verdict}: {miss}/{n_req} requests "
        f"missed their deadline ({pct:.2f}%; threshold {threshold:.2f}%)"
    )


def check_min_trace_complete(records: list[dict], threshold: float) -> tuple[bool, str]:
    """Trace-completeness CI gate (`--min_trace_complete`, round 20): the
    fraction of `kind="trace"` span trees satisfying the completeness
    invariant (closed — enqueue, >=1 admit, exactly one finish — AND
    named phase walls summing to <= e2e + 1e-3 s) must reach
    `threshold`. Returns (ok, message) — a log without trace rows fails,
    so the gate can't pass vacuously when someone passes `--no_trace` to
    the smoke invocation (the `--min_accept_rate` discipline)."""
    trees = _rows(records, "trace")
    if not trees:
        return False, ("--min_trace_complete: no trace record in the log "
                       "(was the run started with --no_trace?)")
    n_complete = sum(1 for t in trees if t.get("complete"))
    n_open = sum(1 for t in trees if not t.get("closed"))
    frac = n_complete / len(trees)
    ok = frac >= threshold
    verdict = "OK" if ok else "FAIL"
    return ok, (
        f"--min_trace_complete {verdict}: {n_complete}/{len(trees)} span "
        f"trees complete ({frac:.3f}; {n_open} open; threshold "
        f"{threshold:.3f})"
    )


# ---- round-22 cross-run comparison (--compare baseline.jsonl) ------------


def _metric_series(records: list[dict]) -> tuple[dict, dict]:
    """Index the LAST `kind="metrics"` epilogue per source: histograms
    keyed by (source, name, labels) and tokens/s-style gauges the same
    way. Later epilogues supersede earlier ones (a train run followed by
    a serve run in one log compares source by source)."""
    hists: dict = {}
    gauges: dict = {}
    for r in _rows(records, "metrics"):
        src = r.get("source", "?")
        for h in r.get("hists") or []:
            key = (src, h["name"], tuple(sorted((h.get("labels") or {}).items())))
            hists[key] = h
        for g in r.get("gauges") or []:
            if g["name"].endswith("tokens_per_sec"):
                key = (src, g["name"],
                       tuple(sorted((g.get("labels") or {}).items())))
                gauges[key] = g["value"]
    return hists, gauges


def compare_runs(current: list[dict], baseline: list[dict],
                 baseline_path: str = "") -> dict:
    """Diff two runs' metric summaries: per-histogram p50/p99 deltas
    (positive = current slower — a regression for latency series) and
    tokens/s deltas (negative = regression). Returns a `kind="compare"`
    record; worst_regression_pct is the single gated number — the worst
    drift across every comparable series, sign-normalized so bigger is
    always worse."""
    cur_h, cur_g = _metric_series(current)
    base_h, base_g = _metric_series(baseline)
    rows, thr_rows = [], []
    worst: tuple | None = None

    def consider(delta_pct: float, name: str):
        nonlocal worst
        if worst is None or delta_pct > worst[0]:
            worst = (delta_pct, name)

    for key in sorted(set(cur_h) & set(base_h), key=str):
        src, name, lk = key
        bh, ch = base_h[key], cur_h[key]
        row = {"source": src, "name": name, "labels": dict(lk)}
        have = False
        for q in ("p50", "p99"):
            b, c = bh.get(q), ch.get(q)
            if b is None or c is None or b <= 0:
                continue
            d = 100.0 * (c - b) / b
            row[f"base_{q}"], row[f"cur_{q}"] = b, c
            row[f"{q}_delta_pct"] = d
            have = True
            # only the `_s` (time-valued) series gate as latency
            # regressions; count-valued histograms are informational
            if name.endswith("_s"):
                consider(d, f"{src}/{name}{_fmt_labels(dict(lk))} {q}")
        if have:
            rows.append(row)
    for key in sorted(set(cur_g) & set(base_g), key=str):
        src, name, lk = key
        b, c = base_g[key], cur_g[key]
        if not b:
            continue
        d = 100.0 * (c - b) / b
        thr_rows.append({"source": src, "name": name, "labels": dict(lk),
                         "base": b, "cur": c, "delta_pct": d})
        consider(-d, f"{src}/{name} tokens/s")
    # summary-record throughput rides along even without metrics
    # epilogues, so --compare works on pre-round-22 baselines too
    for kind in ("serve_summary", "fleet_summary"):
        b = [r for r in _rows(baseline, kind) if r.get("tokens_per_sec")]
        c = [r for r in _rows(current, kind) if r.get("tokens_per_sec")]
        if b and c:
            bv, cv = b[-1]["tokens_per_sec"], c[-1]["tokens_per_sec"]
            d = 100.0 * (cv - bv) / bv
            thr_rows.append({"source": kind, "name": "tokens_per_sec",
                             "labels": {}, "base": bv, "cur": cv,
                             "delta_pct": d})
            consider(-d, f"{kind} tokens/s")
    return {
        "kind": "compare", "baseline": baseline_path,
        "rows": rows, "throughput": thr_rows,
        "worst_regression_pct": None if worst is None else worst[0],
        "worst_name": None if worst is None else worst[1],
    }


def render_compare(cmp: dict) -> str:
    out: list[str] = []
    w = out.append
    w(f"== compare (vs {cmp.get('baseline') or 'baseline'}) ==")
    rows, thr = cmp.get("rows") or [], cmp.get("throughput") or []
    if not rows and not thr:
        w("  no comparable metric series between the runs")
        return "\n".join(out)
    for t in thr:
        w(f"  {t['source'] + '/' + t['name'] + _fmt_labels(t['labels']):<44} "
          f"{human_count(t['base']):>9} -> {human_count(t['cur']):>9} "
          f"tokens/s  {t['delta_pct']:+.1f}%"
          + ("" if t["delta_pct"] >= 0 else "  <- SLOWER"))
    if rows:
        w(f"  {'histogram':<40} {'p50 base->cur':>22} {'Δ%':>7} "
          f"{'p99 base->cur':>22} {'Δ%':>7}")
    for row in rows:
        fmt = (_fmt_seconds if row["name"].endswith("_s")
               else lambda v: "-" if v is None else human_count(v))
        cells = f"  {row['name'] + _fmt_labels(row['labels']):<40}"
        for q in ("p50", "p99"):
            d = row.get(f"{q}_delta_pct")
            if d is None:
                cells += f" {'-':>22} {'-':>7}"
            else:
                cells += (f" {fmt(row[f'base_{q}']) + ' -> ' + fmt(row[f'cur_{q}']):>22}"
                          f" {d:+6.1f}%")
        w(cells)
    wr = cmp.get("worst_regression_pct")
    if wr is not None:
        w(f"  worst regression: {wr:+.1f}% ({cmp.get('worst_name')})")
    return "\n".join(out)


def check_min_slo_compliance(records: list[dict],
                             threshold: float) -> tuple[bool, str]:
    """SLO gate (`--min_slo_compliance`, round 22): the LAST
    `kind="slo"` record's overall_compliance (the worst cumulative
    per-target compliance, sample-weighted) must reach `threshold`.
    Returns (ok, message) — a log without slo rows fails, so the gate
    can't pass vacuously when someone drops `--slo` from the smoke
    invocation; so does a declared target that never saw a sample."""
    slo = _rows(records, "slo")
    if not slo:
        return False, ("--min_slo_compliance: no slo record in the log "
                       "(was the run started with --slo?)")
    last = slo[-1]
    comp = last.get("overall_compliance")
    if comp is None:
        return False, ("--min_slo_compliance FAIL: declared slo targets "
                       "saw no samples")
    targets = [t for t in last.get("targets") or []
               if t.get("cum_compliance") is not None]
    worst = min(targets, key=lambda t: t["cum_compliance"]) if targets else None
    ok = comp >= threshold
    verdict = "OK" if ok else "FAIL"
    return ok, (
        f"--min_slo_compliance {verdict}: overall compliance {comp:.4f} "
        f"over {len(slo)} slo window(s)"
        + (f", worst target {worst['slo']} at "
           f"{worst['cum_compliance']:.4f} (burn {worst['cum_burn']:.2f}x)"
           if worst is not None else "")
        + f" (threshold {threshold:.4f})"
    )


def check_max_regression_pct(records: list[dict],
                             threshold: float) -> tuple[bool, str]:
    """Cross-run regression gate (`--max_regression_pct`, round 22):
    the `--compare` diff's worst sign-normalized drift (latency p50/p99
    up, or tokens/s down) must stay <= `threshold` percent. Reads the
    `kind="compare"` record main() appends after diffing, so it slots
    into the same declarative gate table as every other checker; without
    `--compare` there is nothing to gate and the check fails loudly."""
    cmps = _rows(records, "compare")
    if not cmps:
        return False, ("--max_regression_pct: no comparison in the log "
                       "(pass --compare baseline.jsonl)")
    cmp = cmps[-1]
    worst = cmp.get("worst_regression_pct")
    if worst is None:
        return False, ("--max_regression_pct FAIL: no comparable metric "
                       "series between the runs")
    ok = worst <= threshold
    verdict = "OK" if ok else "FAIL"
    return ok, (
        f"--max_regression_pct {verdict}: worst drift {worst:+.1f}% "
        f"({cmp.get('worst_name')}) vs baseline "
        f"(threshold {threshold:.1f}%)"
    )


# ---- the gate table (round 22) -------------------------------------------
#
# Every CI gate is one row: (flag dest, metavar, checker, help). main()
# generates the argparse options AND the check-dispatch loop from this
# table, so a new gate is a one-row diff instead of the two copy-pasted
# blocks each of the first five gates accreted. Row order is evaluation
# order (and --help order) — it preserves the pre-table behavior exactly.
# Checkers keep the uniform (records, threshold) -> (ok, message)
# contract; anything extra a checker needs (the --compare diff) is
# materialized into `records` first.

GATES: tuple = (
    ("min_goodput", "FRACTION", check_min_goodput,
     "assert mean train-window goodput >= FRACTION (exit 2 below "
     "it) — a cheap perf regression gate for CI"),
    ("min_accept_rate", "FRACTION", check_min_accept_rate,
     "assert the serve_summary speculative-decoding acceptance "
     "rate >= FRACTION (exit 2 below it, or when the log has no spec "
     "summary) — the draft-health regression gate for CI"),
    ("max_deadline_miss_pct", "PERCENT", check_max_deadline_miss_pct,
     "assert the fleet_summary's deadline_misses <= PERCENT of served "
     "requests (exit 2 above it, or when the log has no fleet summary "
     "or the summary predates deadline accounting) — the round-24 "
     "request-deadline regression gate for CI"),
    ("min_trace_complete", "FRACTION", check_min_trace_complete,
     "assert the fraction of complete request span trees "
     "(kind=\"trace\" rows: closed AND phase walls summing to e2e "
     "within 1e-3 s) >= FRACTION (exit 2 below it, or when the log "
     "has no trace rows) — the tracing-integrity gate for CI"),
    ("min_slo_compliance", "FRACTION", check_min_slo_compliance,
     "assert the run's cumulative SLO compliance (worst target in the "
     "last kind=\"slo\" record) >= FRACTION (exit 2 below it, or when "
     "the log has no slo rows) — the round-22 SLO regression gate for CI"),
    ("max_regression_pct", "PERCENT", check_max_regression_pct,
     "assert the --compare diff's worst drift (latency p50/p99 up or "
     "tokens/s down, sign-normalized) <= PERCENT (exit 2 above it, or "
     "without --compare) — the round-22 cross-run regression gate"),
)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("log", help="metrics JSONL written via --metrics_log")
    ap.add_argument(
        "--compare", default=None, metavar="BASELINE_JSONL",
        help="diff this run's metric summaries (kind=\"metrics\" "
        "histogram p50/p99, tokens/s headline) against a baseline run's "
        "JSONL; gate the worst drift with --max_regression_pct",
    )
    for dest, metavar, _check, help_text in GATES:
        ap.add_argument(
            f"--{dest}", type=float, default=None, metavar=metavar,
            help=help_text,
        )
    args = ap.parse_args(argv)
    records = load(args.log)
    if not records:
        print(f"{args.log}: no records", file=sys.stderr)
        return 1
    print(summarize(records))
    if args.compare is not None:
        baseline = load(args.compare)
        if not baseline:
            print(f"{args.compare}: no records", file=sys.stderr)
            return 1
        cmp = compare_runs(records, baseline, baseline_path=args.compare)
        print(render_compare(cmp))
        records.append(cmp)  # --max_regression_pct reads it like any row
    rc = 0
    for dest, _metavar, check, _help in GATES:
        threshold = getattr(args, dest)
        if threshold is None:
            continue
        ok, msg = check(records, threshold)
        print(msg, file=sys.stdout if ok else sys.stderr)
        rc = rc if ok else 2
    return rc


if __name__ == "__main__":
    sys.exit(main())

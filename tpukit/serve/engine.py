"""Continuous-batching serving engine: the host-side slot scheduler.

Round 14 (ROADMAP #1): the "millions of users" half of the north star.
The device programs live in `tpukit/serve/decode.py`; this module owns
everything around them — admission, eviction, the request stream, and
the serving telemetry — in the shape real TPU serving engines take:

  - A **slot ring**: `slots` decode lanes over one preallocated KV ring
    (the model's `init_kv_cache(cfg, slots, width)`). A free-list (ring order)
    assigns arriving requests to lanes; eviction on EOS/length returns
    the lane, and the next prefill alone makes it safe to reuse (stale
    cache garbage above the new cursor is never attended — decode.py).
  - **Prefill/decode phase separation**: arrivals are admitted BETWEEN
    decode quanta via `prefill_slots`, which touches only the free
    lanes — active slots never stall on an arriving prompt. Prompts pad
    to a small declared set of length buckets and admit-batches pad to
    powers of two, so the serve path compiles at most
    `ServeConfig.compile_budget` programs (asserted in
    tests/test_serve.py).
  - **Continuous decode**: one `decode_step` advances every active lane
    one token; the per-step host sync is one `[N]` cursor/flag fetch —
    the EOS-detection cost every host-scheduled engine pays.
  - **Serving telemetry** through the SAME stack that covers training
    (spans -> JSONL -> flight recorder -> tools/report.py): per-window
    `kind="serve"` records (tokens/s, occupancy, admit/evict counts,
    prefill/decode/sync wall split + explicit `other_s` residual,
    per-window dispatch-vs-device attribution, per-token + end-to-end
    latency percentiles) and one final `kind="serve_summary"`. With a
    `tracer` (round 20, tpukit/obs/trace.py) the step primitives also
    emit per-request span events — enqueue/admit/prefill/quantum/finish
    — merged into span trees with per-phase p50/p99 in the summary.

Sharded serving: pass `mesh` (and params placed at their training
shardings) and the engine places the KV ring `[L, N, H, W, D]` as
`P(None, "data", "model", None, None)` — slots data-parallel, heads
tensor-parallel — with the per-slot host state sharded over `data`.
The decode step's per-step collectives then match the closed form
`decode.decode_step_comm` (audited against compiled HLO in tests).
"""

from __future__ import annotations

import dataclasses
import logging
import statistics
import time
from collections import deque

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from tpukit.model import family
from tpukit.obs import SpanTimeline
from tpukit.obs import metrics as metrics_lib
from tpukit.obs import trace as trace_lib
from tpukit.serve import decode as serve_decode

log = logging.getLogger(__name__)

# A quantum whose period (sync return to sync return) exceeds this many times
# the median of the last SLOW_QUANTUM_HISTORY periods is logged, traced or not.
SLOW_QUANTUM_FACTOR = 10.0
SLOW_QUANTUM_HISTORY = 64
SLOW_QUANTUM_MIN_HISTORY = 8  # no verdict from the first few (compiles) alone


@dataclasses.dataclass(frozen=True)
class Request:
    """One inference request: a tokenized prompt plus its decode budget.
    `arrival_s` is the offset (seconds, stream-relative) at which the
    request becomes visible to the scheduler — 0 for an offered-up-front
    batch, spaced for an arrival process. `trace` is the request's trace
    id (round 20, tpukit/obs/trace.py); -1 defaults it to the rid. A
    requeued-after-kill attempt reuses the SAME Request, so both
    attempts share one trace id by construction. `deadline_ms` (round 24)
    is an end-to-end latency bound measured from `arrival_s`: 0 disables
    it, >0 makes the engine EVICT the request once exceeded (reason
    \"deadline\", partial output kept). `priority` orders backpressure
    shedding in the fleet router — lower sheds first; it never reorders
    admission (FIFO within the arrived set is the latency contract)."""

    rid: int
    ids: tuple[int, ...]
    max_new_tokens: int = 20
    seed: int = 0
    arrival_s: float = 0.0
    trace: int = -1
    deadline_ms: float = 0.0
    priority: int = 0


def trace_id(req: Request) -> int:
    """Effective trace id: an explicit `trace` field wins, else the rid —
    requeued attempts reuse the SAME Request object, so both attempts land
    under one id either way."""
    return req.trace if req.trace >= 0 else req.rid


@dataclasses.dataclass
class Completion:
    """A finished request. `ids` holds prompt + generated tokens;
    timestamps are engine-clock seconds (run-relative). The paged fields
    (round 15) are 0/absent under the ring cache: `pages` is the request's
    page footprint, `prefix_pages` how many of them were shared-prefix
    hits, and `active_s` when its prefill finished and decode began
    (== `admit_s` for the ring's one-shot prefill).

    `active_s` and `done_s` are stamped when the host DISPATCHED the last
    prefill chunk and ENTERED the last sync. What a client can see is in
    `deliveries`: one `(t, n)` per host sync at which this request's cursor
    advanced — `n` tokens reached the host at run-clock `t`, stamped after
    the fetch returned. A latency metric reads `first_token_s - arrival_s`
    for TTFT and the gaps between deliveries for the inter-token tail, at
    a quantum's grain: that is what a client of this engine sees."""

    rid: int
    ids: np.ndarray
    prompt_len: int
    generated: int
    reason: str  # "eos" | "length" | "deadline"
    arrival_s: float
    admit_s: float
    done_s: float
    pages: int = 0
    prefix_pages: int = 0
    active_s: float = 0.0
    deliveries: tuple[tuple[float, int], ...] = ()

    @property
    def first_token_s(self) -> float | None:
        """When a streaming client could first see a token (None: none
        was generated)."""
        return self.deliveries[0][0] if self.deliveries else None

    @property
    def last_token_s(self) -> float | None:
        return self.deliveries[-1][0] if self.deliveries else None

    @property
    def admit_latency_s(self) -> float:
        """Slot-assignment to decode-ready: the prefill cost a request
        actually paid — what shared-prefix reuse shrinks."""
        return max(self.active_s - self.admit_s, 0.0)

    @property
    def e2e_s(self) -> float:
        """End-to-end latency including queue wait — what a user sees."""
        return self.done_s - self.arrival_s

    @property
    def per_token_s(self) -> float:
        """Decode-resident seconds per generated token."""
        return (self.done_s - self.admit_s) / max(self.generated, 1)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """Engine shape. `buckets` is the DECLARED prompt-length set — the
    whole compile budget of the serve path (one prefill program per
    bucket + one decode step). Prompts longer than `max(buckets)` are
    rejected at admission (callers truncate upstream, the reference's
    own prompt contract). The KV ring width is
    `max(buckets) + max_new_tokens` unless `max_len` pins it."""

    slots: int = 8
    buckets: tuple[int, ...] = (16, 32, 64)
    max_new_tokens: int = 20
    temperature: float = 0.0
    top_k: int = 0
    window_steps: int = 32  # decode steps per kind="serve" JSONL window
    max_len: int = 0
    # Decode QUANTUM: tokens decoded per runtime dispatch (and per host
    # sync). 1 = per-token scheduling (tightest admit/evict latency);
    # larger amortizes the per-dispatch host overhead that otherwise
    # dominates small-model decode (decode.decode_step docstring). Token
    # streams are identical at any quantum — finished slots freeze
    # mid-quantum — only latency granularity changes.
    decode_quantum: int = 4
    # Paged KV (round 15, ROADMAP #2). 0 = the round-14 per-slot ring
    # (byte-identical behavior). > 0 = fixed-size pages of this many token
    # positions + per-slot block tables (serve/paged.py): requests hold
    # ceil(min(prompt+budget, width)/page_size) pages instead of a
    # full-width slot, prompt prefixes are shared page-granular across
    # requests, and prefill runs CHUNKED between decode quanta. Page size
    # must divide every bucket so admit chunks stay page-aligned.
    page_size: int = 0
    # Page-pool size; 0 derives the ring-equivalent pool (slots x
    # pages-per-slot + the null page) — same KV HBM, so the paged win
    # reads as footprint, not as a bigger budget. The bench shrinks/grows
    # it explicitly for the equal-HBM comparison.
    num_pages: int = 0
    # Page payload storage: "f32"/"bf16" store that dtype (token-exact
    # when it equals the compute dtype); "int8" block-quantizes page rows
    # with quant_comm's 256-element-block quantizer for ~4x pages per HBM
    # byte — lossy, gated by a token-level tolerance test, never claimed
    # token-exact. Non-f32 requires the paged cache.
    kv_dtype: str = "f32"
    # Chunked-prefill chunk (tokens per prefill dispatch, page multiple);
    # 0 = one page per chunk. A lane advances one chunk per scheduler
    # iteration with decode quanta in between, so a long prompt can never
    # stall active slots for more than one chunk's compute.
    prefill_chunk: int = 0
    # Speculative decoding (round 17, ROADMAP #3; tpukit/serve/spec.py).
    # "" = vanilla decode quanta. "ngram" = self-speculation: prompt-
    # lookup drafting from each slot's own history, no second model.
    # "model" = a small tpukit GPT draft model (pass draft_params /
    # draft_cfg to the engine). Either way the target scores all
    # spec_k + 1 positions in ONE batched forward and rejection sampling
    # keeps the output distribution EXACT: greedy output is token-
    # identical to vanilla decode, sampled output is an exact target-
    # distribution sample (spec.py module docstring). Requires the ring
    # cache (page_size == 0): the multi-token verify write-back does not
    # fit the paged whole-page write contract this round.
    draft: str = ""  # "" | "ngram" | "model"
    # Draft tokens proposed per slot per quantum (the verify window is
    # spec_k + 1 wide). The KV ring over-allocates this many scratch
    # positions past `width` so a lane near its limit still writes a full
    # verify window without update-slice clamping (spec.py docstring).
    spec_k: int = 4
    # Longest n-gram the self-speculation proposer matches (it falls back
    # through shorter suffixes down to 1).
    ngram_max: int = 3
    # The on-device scheduler loop (round 21). False (default): the
    # per-quantum decode_step. True (paged only): each quantum dispatches
    # decode.decode_loop_window: scheduler state (cursors, EOS/limit
    # flags, the freed-page account) lives on device across up to
    # `decode_quantum` ticks with early exit when every lane finishes or
    # enough pages free to admit the head-of-queue request. Token streams
    # are identical either way; only the host sync cadence changes. How a
    # decode tick READS the page pool is not this field's business: the
    # model decides it from the backend (gpt.decode_read_in_kernel).
    fused_decode: bool = False

    def __post_init__(self):
        if self.draft not in ("", "ngram", "model"):
            raise ValueError(
                f"draft={self.draft!r} must be '', 'ngram' or 'model'"
            )
        if self.draft:
            if self.spec_k < 1:
                raise ValueError(
                    f"spec_k={self.spec_k} must be >= 1 with draft="
                    f"{self.draft!r} — a 0-token draft is vanilla decode"
                )
            if self.ngram_max < 1:
                raise ValueError(f"ngram_max={self.ngram_max} must be >= 1")
            if self.page_size:
                raise ValueError(
                    f"draft={self.draft!r} requires the ring cache "
                    f"(page_size=0, got {self.page_size}): the k+1-token "
                    f"verify write-back is not page-aligned, and the paged "
                    f"write contract only covers whole pages — speculative "
                    f"+ paged is a future round (DESIGN.md §16)"
                )
        if self.slots < 1:
            raise ValueError(f"slots={self.slots} must be >= 1")
        if self.decode_quantum < 1:
            raise ValueError(
                f"decode_quantum={self.decode_quantum} must be >= 1"
            )
        b = tuple(self.buckets)
        if not b or list(b) != sorted(set(b)) or b[0] < 1:
            raise ValueError(
                f"buckets={self.buckets} must be unique, ascending and >= 1 "
                f"— the bucket set IS the declared compile budget"
            )
        if self.max_new_tokens < 1:
            raise ValueError(f"max_new_tokens={self.max_new_tokens} must be >= 1")
        if self.max_len and self.max_len < max(b):
            raise ValueError(
                f"max_len={self.max_len} is smaller than the largest bucket "
                f"({max(b)}) — a prompt admitted at that bucket could not fit "
                f"the KV ring (it would crash at prefill, not here)"
            )
        from tpukit.serve import paged as paged_lib

        if self.kv_dtype not in paged_lib.KV_DTYPES:
            raise ValueError(
                f"kv_dtype must be one of {paged_lib.KV_DTYPES}, "
                f"got {self.kv_dtype!r}"
            )
        if self.page_size < 0:
            raise ValueError(f"page_size={self.page_size} must be >= 0")
        if self.page_size == 0:
            if self.fused_decode:
                raise ValueError(
                    "fused_decode=True requires the paged cache "
                    "(page_size > 0) — the on-device window keeps the "
                    "freed-page account; the ring path keeps its round-14 "
                    "trace"
                )
            if self.kv_dtype != "f32":
                raise ValueError(
                    f"kv_dtype={self.kv_dtype!r} requires the paged cache "
                    f"(page_size > 0) — the ring stores the compute dtype"
                )
            for name in ("num_pages", "prefill_chunk"):
                if getattr(self, name):
                    raise ValueError(
                        f"{name}={getattr(self, name)} requires the paged "
                        f"cache (page_size > 0)"
                    )
            return
        bad = [x for x in b if x % self.page_size]
        if bad:
            raise ValueError(
                f"page_size={self.page_size} must divide every bucket "
                f"width (buckets {bad} don't tile) — admit chunks are "
                f"page-aligned whole-page writes"
            )
        if self.prefill_chunk and self.prefill_chunk % self.page_size:
            raise ValueError(
                f"prefill_chunk={self.prefill_chunk} must be a multiple of "
                f"page_size={self.page_size} — chunks write whole pages"
            )
        if self.prefill_chunk:
            bad = [x for x in b if x % self.prefill_chunk]
            if bad:
                raise ValueError(
                    f"prefill_chunk={self.prefill_chunk} must divide every "
                    f"bucket width (buckets {bad} don't tile) — a partial "
                    f"tail chunk would write past its bucket row"
                )
        if self.num_pages and self.num_pages - 1 < self.pages_per_slot:
            raise ValueError(
                f"num_pages={self.num_pages} cannot hold even one "
                f"worst-case request ({self.pages_per_slot} pages for "
                f"width {self.width}, plus the reserved null page)"
            )

    @property
    def width(self) -> int:
        return self.max_len or (max(self.buckets) + self.max_new_tokens)

    @property
    def paged(self) -> bool:
        return self.page_size > 0

    @property
    def pages_per_slot(self) -> int:
        """Block-table width: pages covering the worst-case logical
        sequence. Only meaningful when paged."""
        return -(-self.width // self.page_size)

    @property
    def padded_width(self) -> int:
        """Logical per-slot width of the paged view (width rounded up to
        whole pages); == `width` for the ring."""
        return self.pages_per_slot * self.page_size if self.paged else self.width

    @property
    def chunk(self) -> int:
        """Chunked-prefill chunk actually used (paged only)."""
        return self.prefill_chunk or self.page_size

    @property
    def kv_width(self) -> int:
        """Physical KV-ring width: the logical width plus the spec-decode
        scratch tail (`spec_k` positions a verify window near the buffer
        end spills into — never appended, never attended; spec.py)."""
        return self.padded_width + (self.spec_k if self.draft else 0)

    @property
    def compile_budget(self) -> int:
        """Declared ceiling on serve-path compiles: ONE decode program
        (at this quantum) plus one prefill program per admit size — the
        admit batcher pads group sizes to powers of two precisely so this
        stays a small static set (asserted in tests). Ring prefills
        compile per (bucket, admit size); paged chunked prefills have ONE
        static chunk width, so only the admit sizes multiply.

        Speculative decoding swaps the decode program for ONE verify
        program; the "model" draft adds one draft-propose loop and a
        second prefill program per (bucket, admit size) — the draft ring
        is prefilled by the same batched program as the target's."""
        admit_sizes = (self.slots - 1).bit_length() + 1
        if self.paged:
            return 1 + admit_sizes
        prefills = len(self.buckets) * admit_sizes
        if self.draft == "model":
            return 2 + 2 * prefills
        return 1 + prefills


@dataclasses.dataclass
class _Lane:
    req: Request
    admit_s: float
    prompt_len: int
    bucket: int
    # paged-only state (round 15): the lane's page footprint (shared
    # prefix first, then private pages), how many lead pages are shared
    # read-only hits, the chunked-prefill cursor (next chunk start; the
    # lane is decoding once it reaches `prefill_end`), and when decode
    # became ready.
    pages: list[int] = dataclasses.field(default_factory=list)
    # pages of the model's further page kinds, by block-table key (a window
    # layer's ring): allocated with `pages`, released with them
    more_pages: dict = dataclasses.field(default_factory=dict)
    shared: int = 0
    next_chunk: int = 0
    prefill_end: int = 0
    phase: str = "decode"  # "prefill" until the last chunk is dispatched
    active_s: float = 0.0
    # per-request PRNG key bytes, computed ONCE at admission — chunk
    # dispatches must not pay a device round-trip per lane per iteration
    key: np.ndarray | None = None
    # (run-clock time, tokens) of every sync at which this lane's cursor
    # advanced, and their running total (== cursor - prompt_len as of the
    # last sync)
    deliveries: list = dataclasses.field(default_factory=list)
    delivered: int = 0


def _pct(vals, q) -> float | None:
    return float(np.percentile(np.asarray(vals), q)) if vals else None


class ServeEngine:
    """Host-side continuous-batching loop over the decode.py programs.

    `params` must already sit at the caller's serving shardings (the
    training shardings under a TP mesh, or any single-device/replicated
    layout); the engine never moves them. `logger`/`recorder` take the
    trainer's StepLogger / FlightRecorder — pass None for silent runs.
    """

    def __init__(self, params, cfg, serve: ServeConfig,
                 eos_id: int, mesh=None, logger=None, recorder=None,
                 draft_params=None, draft_cfg=None, replica=None,
                 tracer=None, metrics=None, slo=None, metrics_dir=None):
        # the model is reached through what its family offers
        # (tpukit/model/__init__.py); nothing below names a model's
        # functions or sizes
        model = self._model = family(cfg)
        if serve.kv_width > model.max_context(cfg):
            raise ValueError(
                f"KV ring width {serve.kv_width} (max bucket "
                f"{max(serve.buckets)} + max_new_tokens "
                f"{serve.max_new_tokens}"
                + (f" + spec_k {serve.spec_k} verify scratch"
                   if serve.draft else "")
                + f") exceeds the model's longest context "
                f"({model.max_context(cfg)}): a learned position table "
                f"would silently clamp beyond it instead of erroring"
            )
        if serve.draft == "model":
            if draft_params is None or draft_cfg is None:
                raise ValueError(
                    "draft='model' requires draft_params and draft_cfg "
                    "(a tpukit GPT draft — restore one via "
                    "checkpoint.restore_params, main-serve.py "
                    "--draft_checkpoint)"
                )
            # Named at construction, not a shape error at the first
            # verify: the acceptance test compares p and q elementwise
            # over the logits axis, so the draft must speak the TARGET's
            # token ids — same tokenizer vocab AND the same padded width.
            if (draft_cfg.vocab_size != cfg.vocab_size
                    or draft_cfg.padded_vocab_size != cfg.padded_vocab_size):
                raise ValueError(
                    f"draft model vocab (vocab_size "
                    f"{draft_cfg.vocab_size}, padded "
                    f"{draft_cfg.padded_vocab_size}) does not match the "
                    f"target ({cfg.vocab_size}, padded "
                    f"{cfg.padded_vocab_size}) — draft and target must "
                    f"share one tokenizer; the rejection-sampling "
                    f"correction compares their distributions token id "
                    f"by token id"
                )
            if serve.kv_width > family(draft_cfg).max_context(draft_cfg):
                raise ValueError(
                    f"draft model's longest context (its position table, "
                    f"{family(draft_cfg).max_context(draft_cfg)}) is smaller "
                    f"than the KV ring width {serve.kv_width} — the "
                    f"draft decodes the same positions the target serves"
                )
        elif draft_params is not None or draft_cfg is not None:
            raise ValueError(
                f"draft_params/draft_cfg passed but ServeConfig.draft="
                f"{serve.draft!r} — set draft='model' to use them"
            )
        self.params = params
        self.cfg = cfg
        self.serve = serve
        self.eos_id = int(eos_id)
        self.mesh = mesh
        self.logger = logger
        self.recorder = recorder
        # Fleet identity (round 19, tpukit/serve/fleet.py): stamped on
        # every serve window/summary this engine emits so the fleet report
        # can aggregate per-replica telemetry. None = standalone engine,
        # records unchanged.
        self.replica = replica
        # Request-scoped tracing (round 20, tpukit/obs/trace.py): a
        # shared TraceRecorder the step primitives emit span events into.
        # None = tracing off — every tracer touch below is gated so the
        # token stream and schedule are bit-identical either way
        # (asserted in tests/test_trace.py).
        self.tracer = tracer
        # Metrics plane (round 22, tpukit/obs/metrics.py): a shared
        # MetricRegistry observed at WINDOW boundaries only — every
        # histogram is DERIVED from the completions / trace trees /
        # quantum events the engine already produces, so the step
        # primitives and the token stream are bit-identical with
        # metrics on or off (asserted in tests/test_metrics.py).
        # `slo` is a list of parsed SloTargets (metrics_lib.parse_slo);
        # a fleet passes slo=None to its replicas and accounts SLOs at
        # the router, mirroring the shared-tracer flush discipline.
        self.metrics = metrics
        self.slo_accountant = (
            metrics_lib.SloAccountant(slo)
            if (metrics is not None and slo) else None
        )
        self.metrics_dir = metrics_dir
        self._metrics_traces_seen: set = set()
        self._metrics_q_mark = -1.0  # quantum watermark (t1 run-clock)
        # the quantum in flight: its dispatch half (`_open_quantum`), closed
        # and emitted by the sync that fetches it
        self._quantum: dict | None = None
        self._quanta = 0  # quanta synced so far: the slow-quantum line's index
        # run-clock time the last sync returned (0 = the run's start): where
        # the next quantum's `host` account and period begin
        self._host_from = 0.0
        self._periods: deque[float] = deque(maxlen=SLOW_QUANTUM_HISTORY)
        # fused windows (round 21): the device tick counter of the last
        # decode_loop_window dispatch, fetched at the window-boundary sync
        # (the loop may exit early, so the host can't assume the quantum)
        self._pending_ticks = None
        self.draft_params = draft_params
        self.draft_cfg = draft_cfg
        # lax.top_k rejects k beyond the logits width — clamp like generate()
        self._top_k = min(int(serve.top_k), cfg.padded_vocab_size)
        n, w = serve.slots, serve.padded_width

        if serve.paged:
            from tpukit.serve import paged as paged_lib

            # the model's own statement of the pages it keeps (a layout it
            # cannot store is named here, at construction, not by an XLA
            # shape error at the first write)
            kinds = model.page_kinds(cfg, serve.page_size, serve.kv_dtype)
            for kind in kinds:
                if kind.ring_pages and serve.chunk > kind.ring_pages * serve.page_size:
                    raise ValueError(
                        f"prefill_chunk={serve.chunk} is more than the "
                        f"{kind.ring_pages}-page ring of page kind "
                        f"{kind.table!r} holds ({kind.ring_pages} x "
                        f"{serve.page_size} tokens): a chunk's pages would "
                        f"overwrite each other"
                    )
        if mesh is not None:
            from tpukit.mesh import place_host_array

            if jax.process_count() > 1:
                raise NotImplementedError(
                    "ServeEngine schedules from ONE host (per-quantum "
                    "cursor fetches via device_get are not legal on "
                    "cross-host sharded arrays) — run one engine per host "
                    "over that host's devices; cross-host serving is a "
                    "future round"
                )
            d = mesh.shape.get("data", 1)
            if serve.paged and d > 1:
                raise ValueError(
                    f"paged serving requires a model-only grid (data axis "
                    f"1, got data={d}): the page pool is replicated across "
                    f"`data` and a data-sharded slot set would make the "
                    f"pool write-back an unauditable cross-shard scatter "
                    f"(decode.decode_step_comm) — shrink the data axis or "
                    f"use the ring cache (page_size=0)"
                )
            if n % d:
                raise ValueError(
                    f"slots={n} must be a multiple of the mesh's data axis "
                    f"({d}) — slots shard over it"
                )
            m = mesh.shape.get("model", 1)
            heads_ax = "model" if (m > 1 and model.kv_heads(cfg) % m == 0) else None
            batch_ax = "data" if d > 1 else None
            # place_host_array: multi-host safe (every process calls with
            # the same value; single-process is a plain device_put)
            place = lambda x, spec: place_host_array(
                np.asarray(x), NamedSharding(mesh, spec)
            )
            cache_spec = P(None, batch_ax, heads_ax, None, None)
            pool_spec = P(None, None, heads_ax, None, None)
            scale_spec = P(None, None, heads_ax, None)
            slot_spec = P(batch_ax)
        else:
            place = lambda x, spec: jnp.asarray(x)
            cache_spec = pool_spec = scale_spec = slot_spec = P()
        self._place = place
        # kept for the fleet page handoff: a copied page block lands at the
        # destination pool's layout (fleet._copy_pages, round 19)
        self._pool_spec = pool_spec
        self._scale_spec = scale_spec

        self.buf = place(np.zeros((n, w), np.int32), P(*slot_spec, None))
        if serve.paged:
            # one pool, one allocator and one host block table per page
            # kind. The first kind is the primary one: `num_pages` sizes
            # it, the prefix registry and the fleet handoff speak of it
            # (`self.allocator`, `self._bt`, `_Lane.pages`); any further
            # kind gets the ring-equivalent pool, every slot's worst case
            self._kinds = kinds
            self.num_pages = serve.num_pages or n * serve.pages_per_slot + 1
            pool_pages = {
                k.table: n * k.pages_for(serve.padded_width, serve.page_size) + 1
                for k in kinds
            }
            pool_pages[kinds[0].table] = self.num_pages
            tree = model.init_paged_cache(
                cfg, pool_pages, serve.page_size, serve.pages_per_slot,
                n, serve.kv_dtype,
            )
            specs = {"k": pool_spec, "v": pool_spec, "ks": scale_spec,
                     "vs": scale_spec}
            self.cache = {key: place(val, specs.get(key, P()))
                          for key, val in tree.items()}
            self.allocators = {
                k.table: paged_lib.PageAllocator(pool_pages[k.table], serve.page_size)
                for k in kinds
            }
            self.allocator = self.allocators[kinds[0].table]
            self.kv_bytes = paged_lib.pool_bytes(
                cfg, pool_pages, serve.page_size, serve.kv_dtype
            )
            self._tables = {
                k.table: np.zeros(tree[k.table].shape, np.int32) for k in kinds
            }
            self._bt = self._tables[kinds[0].table]
            self._bt_dirty = False
        else:
            self._kinds = ()
            self.num_pages = 0
            self.allocator = None
            ring = model.init_kv_cache(cfg, n, serve.kv_width)
            self.kv_bytes = sum(
                int(np.prod(c.shape)) * c.dtype.itemsize for c in ring.values()
            )
            self.cache = jax.tree.map(lambda c: place(c, cache_spec), ring)
        self._slot_spec = slot_spec
        self.draft_cache = None
        if serve.draft == "model":
            # the draft's own ring, same slots/width discipline as the
            # target's; REPLICATED under a mesh (the draft is small — its
            # forward is not the audited program, and replication keeps
            # any head count legal whatever the model axis)
            self.draft_cache = jax.tree.map(
                lambda c: place(c, P()),
                family(draft_cfg).init_kv_cache(draft_cfg, n, serve.kv_width),
            )
        # spec telemetry (round 17): proposed/accepted draft tokens, the
        # appended-tokens-per-verify histogram (index 0..spec_k+1), and
        # the host-side snapshot pending the next sync drain
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_hist = [0] * (serve.spec_k + 2) if serve.draft else []
        self._pending_spec = None
        self.cursors = place(np.zeros((n,), np.int32), slot_spec)
        self.active = place(np.zeros((n,), bool), slot_spec)
        self.limits = place(np.zeros((n,), np.int32), slot_spec)
        self.keys = place(np.zeros((n, 2), np.uint32), P(*slot_spec, None))

        self._free = deque(range(n))
        self._lanes: dict[int, _Lane] = {}
        self._pending: deque[Request] = deque()
        self.completions: list[Completion] = []
        # THE host-span primitive (tpukit/obs/spans.py): phase sums, the
        # profiler's host line (`tpukit:<name>`) and the run-clock stamps the
        # tracer's events are built from, all from one `with`
        self.spans = SpanTimeline(annotation=jax.profiler.TraceAnnotation)
        self.buckets_used: set[int] = set()
        self.steps = 0
        self.admitted = 0
        self.max_live = 0
        self.evicted = {"eos": 0, "length": 0, "deadline": 0}
        # rids pinned past natural retirement (stuck_request@RID chaos,
        # round 24): _sync_evict refuses to retire them so the lane holds
        # its slot until deadline_ms eviction reclaims it — pure host-side
        # control plane, the compiled decode step is untouched
        self.stuck_rids: set[int] = set()
        self._gen_total = 0
        # cumulative device counters the model's cache carries
        # (`model.counters`), as of the last sync: a quantum reports the
        # difference under the model's names for them
        self._counters = 0
        self.last_summary: dict | None = None
        # per-window deltas
        self._win = dict(steps=0, gen0=0, admit0=0, comps0=0, hits0=0,
                         prop0=0, acc0=0, hist0=list(self.spec_hist))
        self._window_idx = 0

    # ---- scheduling ------------------------------------------------------

    def bucket_for(self, prompt_len: int) -> int:
        """Smallest declared bucket that fits the prompt; admission-time
        rejection for prompts beyond the largest bucket keeps the compile
        budget exactly the declared set."""
        for b in self.serve.buckets:
            if prompt_len <= b:
                return b
        raise ValueError(
            f"prompt of {prompt_len} tokens exceeds the largest declared "
            f"bucket ({max(self.serve.buckets)}) — truncate upstream or "
            f"declare a larger bucket"
        )

    def _admit_batch(self, reqs: list[Request], now: float) -> None:
        """Admit up to `len(self._free)` arrived requests: group by bucket
        and prefill each group in ONE `prefill_slots` dispatch (one
        batched forward for the whole group — per-request prefill calls
        would pay the per-dispatch host overhead A times). Each group's
        admit-batch is padded to the next power of two by REPEATING the
        last entry (a repeated admit rewrites the same slot with the same
        values — idempotent), so prefill compiles stay bounded by
        buckets x admit sizes (`ServeConfig.compile_budget`)."""
        # Validate EVERY request before popping any slot: a mid-batch raise
        # after partial pops would leak lanes out of the free list and drop
        # the already-popped requests from both queues.
        with self.spans.span("admit"):
            validated = []
            for req in reqs:
                prompt_len = len(req.ids)
                if prompt_len < 1:
                    raise ValueError(f"request {req.rid}: empty prompt")
                validated.append((req, prompt_len, self.bucket_for(prompt_len)))
            groups: dict[int, list[tuple[int, Request, int]]] = {}
            for req, prompt_len, bucket in validated:
                groups.setdefault(bucket, []).append(
                    (self._free.popleft(), req, prompt_len)
                )
        tr = self.tracer
        for bucket, entries in sorted(groups.items()):
            # the ring's admission dispatches its prefill: the two spans run
            # in turn, never nested, so `prefill` keeps the dispatch wall
            with self.spans.span("admit"):
                a = 1 << (len(entries) - 1).bit_length()  # pad to power of two
                rows = np.zeros((a, bucket), np.int32)
                slots = np.zeros((a,), np.int32)
                plens = np.zeros((a,), np.int32)
                lims = np.zeros((a,), np.int32)
                keys = np.zeros((a, 2), np.uint32)
                for i in range(a):
                    slot, req, plen = entries[min(i, len(entries) - 1)]
                    rows[i, :plen] = req.ids
                    slots[i], plens[i] = slot, plen
                    lims[i] = min(plen + req.max_new_tokens, self.serve.width)
                    keys[i] = np.asarray(jax.random.PRNGKey(req.seed), np.uint32)
            with self.spans.span("prefill") as sp:
                (self.buf, self.cache, self.cursors, self.active, self.limits,
                 self.keys) = serve_decode.prefill_slots(
                    self.params, self.cfg, self.buf, self.cache, self.cursors,
                    self.active, self.limits, self.keys,
                    self._place(slots, P()), self._place(rows, P()),
                    self._place(plens, P()), self._place(lims, P()),
                    self._place(keys, P()),
                )
                if self.serve.draft == "model":
                    # prefill the DRAFT ring for the same admit batch —
                    # the same batched program against the draft's
                    # params/cache; the non-cache outputs are identical
                    # values to the target call's and are discarded
                    _, self.draft_cache, *_ = serve_decode.prefill_slots(
                        self.draft_params, self.draft_cfg, self.buf,
                        self.draft_cache, self.cursors, self.active,
                        self.limits, self.keys,
                        self._place(slots, P()), self._place(rows, P()),
                        self._place(plens, P()), self._place(lims, P()),
                        self._place(keys, P()),
                    )
            self.buckets_used.add(bucket)
            for slot, req, plen in entries:
                self._lanes[slot] = _Lane(req, now, plen, bucket, active_s=now)
                self.admitted += 1
                if tr is not None:
                    tid = trace_id(req)
                    tr.emit("admit", tid, rid=req.rid, t=now, slot=slot,
                            replica=self.replica)
                    tr.emit("prefill", tid, rid=req.rid, t0=sp.t0, t1=sp.t1,
                            chunk=0, replica=self.replica)
                    tr.emit("prefill_done", tid, rid=req.rid, t=sp.t1,
                            replica=self.replica)
        self.max_live = max(self.max_live, len(self._lanes))

    # ---- paged scheduling (round 15) -------------------------------------

    def _admit_paged_one(self, req: Request, now: float) -> bool:
        """Admit one request into the paged pool, or return False when the
        pool cannot cover it yet (head-of-line admission control — pages,
        not just lanes, are the capacity). The request's whole worst case
        — `ceil(min(prompt + budget, width) / P)` pages — is allocated up
        front, so decode can never starve mid-request; the savings vs the
        ring is the footprint (actual need, not bucket width), plus every
        shared-prefix page the registry already holds.

        Prefix reuse: the registry walk is capped at `(prompt_len-1) // P`
        (the last prompt position's page must stay private — it is
        rewritten by the first decode tick) and aligned DOWN to the
        prefill chunk so the remaining suffix starts on a chunk boundary.
        Shared pages are claimed (refcounted) before the private
        allocation so the allocator's retained-LRU reclaim can't steal
        them in between."""
        plen = len(req.ids)
        if plen < 1:
            raise ValueError(f"request {req.rid}: empty prompt")
        bucket = self.bucket_for(plen)
        p, c = self.serve.page_size, self.serve.chunk
        limit = min(plen + req.max_new_tokens, self.serve.width)
        total = -(-limit // p)
        # a shared prefix skips its chunks' prefill, which only a model whose
        # every layer keeps the whole context can do: a window layer's ring
        # would miss the tokens before the suffix
        matched = (self.allocator.lookup_prefix(req.ids, (plen - 1) // p)
                   if len(self._kinds) == 1 else [])
        s_tokens = (len(matched) * p // c) * c
        shared = matched[: s_tokens // p]
        self.allocator.claim(shared)
        fresh = self.allocator.alloc(total - len(shared))
        more = {}
        for kind in self._kinds[1:]:
            held = (None if fresh is None else
                    self.allocators[kind.table].alloc(kind.pages_for(limit, p)))
            if held is None:  # a pool cannot cover the request yet: give back what was taken
                for table, taken in more.items():
                    self.allocators[table].release(taken)
                if fresh is not None:
                    self.allocator.release(fresh)
                fresh = None
                break
            more[kind.table] = held
        if fresh is None:
            self.allocator.release(shared)
            return False
        slot = self._free.popleft()
        pages = list(shared) + fresh
        self._bt[slot] = 0
        self._bt[slot, : len(pages)] = pages
        for table, held in more.items():
            self._tables[table][slot] = 0
            self._tables[table][slot, : len(held)] = held
        self._bt_dirty = True
        # prefill only the chunks that hold prompt tokens — the ring
        # prefilled the whole bucket, but bucket-pad K/V is causally dead
        # (never attended), so chunks past ceil(plen/chunk) would be pure
        # padding forwards that delay decode arming and inflate admit
        # latency. Position plen-1 always lands in the last dispatched
        # chunk (s_tokens <= ((plen-1)//p)*p < plen <= prefill_end).
        prefill_end = -(-plen // c) * c
        self._lanes[slot] = _Lane(
            req, now, plen, bucket, pages=pages, more_pages=more,
            shared=len(shared), next_chunk=s_tokens, prefill_end=prefill_end, phase="prefill",
            key=np.asarray(jax.random.PRNGKey(req.seed), np.uint32),
        )
        self.admitted += 1
        self.max_live = max(self.max_live, len(self._lanes))
        self.buckets_used.add(bucket)
        if self.tracer is not None:
            self.tracer.emit("admit", trace_id(req), rid=req.rid, t=now,
                             slot=slot, replica=self.replica)
        if shared:
            self.allocator.stats.prefix_hits += 1
            self.allocator.stats.prefix_pages_reused += len(shared)
        return True

    def _dispatch_prefill_chunks(self, now: float) -> None:
        """Advance every prefilling lane by ONE chunk in one batched
        dispatch (`decode.prefill_chunk_paged`), interleaved with decode
        quanta by the run loop — the chunked-prefill contract: a long
        prompt costs active slots at most one chunk of compute per
        scheduler iteration, and a prefix-hit admission starts at its
        first UNSHARED chunk (a full-prefix hit dispatches only the final
        chunk holding the private last-prompt page). Lanes finishing
        their last chunk arm decode state on-device and are registered
        into the prefix registry here (host metadata; device ordering
        guarantees the chunk's writes land before any later read)."""
        prefilling = [(slot, lane) for slot, lane in self._lanes.items()
                      if lane.phase == "prefill"]
        if not prefilling:
            return
        c = self.serve.chunk
        # two `prefill` spans with `place` between them (spans never nest
        # here: nested time would go to the outer one): the first is the
        # host assembling the chunk batch, the second the dispatch whose
        # wall the tracer's `prefill` events carry
        with self.spans.span("prefill"):
            entries = []
            for slot, lane in prefilling:
                start = lane.next_chunk
                seg = lane.req.ids[start : start + c]
                row = np.zeros((c,), np.int32)
                row[: len(seg)] = seg
                entries.append((slot, lane, start, row, start + c >= lane.prefill_end))
            a = 1 << (len(entries) - 1).bit_length()  # pad to power of two
            rows = np.zeros((a, c), np.int32)
            slots = np.zeros((a,), np.int32)
            starts = np.zeros((a,), np.int32)
            last = np.zeros((a,), bool)
            plens = np.zeros((a,), np.int32)
            lims = np.zeros((a,), np.int32)
            keys = np.zeros((a, 2), np.uint32)
            for i in range(a):  # repeats are idempotent (round-14 admit trick)
                slot, lane, start, row, is_last = entries[min(i, len(entries) - 1)]
                rows[i], slots[i], starts[i], last[i] = row, slot, start, is_last
                plens[i] = lane.prompt_len
                lims[i] = min(lane.prompt_len + lane.req.max_new_tokens,
                              self.serve.width)
                keys[i] = lane.key
        self._refresh_bt()
        tr = self.tracer
        with self.spans.span("prefill") as sp:
            (self.buf, self.cache, self.cursors, self.active, self.limits,
             self.keys) = serve_decode.prefill_chunk_paged(
                self.params, self.cfg, self.buf, self.cache, self.cursors,
                self.active, self.limits, self.keys,
                self._place(slots, P()), self._place(rows, P()),
                self._place(starts, P()), self._place(last, P()),
                self._place(plens, P()), self._place(lims, P()),
                self._place(keys, P()),
            )
        for slot, lane, start, row, is_last in entries:
            lane.next_chunk = start + c
            if tr is not None:
                tid = trace_id(lane.req)
                tr.emit("prefill", tid, rid=lane.req.rid, t0=sp.t0, t1=sp.t1,
                        chunk=start // c, replica=self.replica,
                        tokens=min(start + c, lane.prompt_len) - start)
                if is_last:
                    tr.emit("prefill_done", tid, rid=lane.req.rid, t=sp.t1,
                            replica=self.replica)
            if is_last:
                lane.phase = "decode"
                lane.active_s = now
                reg = (lane.prompt_len - 1) // self.serve.page_size
                self.allocator.register(lane.req.ids, lane.pages[:reg])

    def _refresh_bt(self) -> None:
        """Push the host block tables to the device copy the programs
        read. Tables change only at admission/eviction; between those the
        cached device array rides along unchanged through every jit."""
        if self._bt_dirty:
            with self.spans.span("place"):
                for table, host in self._tables.items():
                    self.cache[table] = self._place(host, P())
            self._bt_dirty = False

    def _open_quantum(self, t0: float, t1: float, steps: int) -> None:
        """The dispatch half of the quantum record: `[t0, t1]` is the async
        dispatch wall, and the counters are read here, where the work
        happens. `sync()` adds the wall-to-sync half and emits ONE ring
        record per quantum, not per lane — the ring stays O(quanta)."""
        decoding = [l for _, l in sorted(self._lanes.items())
                    if l.phase == "decode"]
        self._quantum = dict(
            t0=t0, t1=t1, steps=steps,
            lanes=[trace_id(l.req) for l in decoding],
            decoding=len(decoding),
            prefilling=len(self._lanes) - len(decoding),
            pending=len(self._pending),
            free_pages=(self.allocator.available_pages
                        if self.serve.paged else None),
            # what the decoding lanes have behind them (as of the last
            # sync) and what the page pools hold for every live lane
            ctx_tokens=sum(l.prompt_len + l.delivered for l in decoding),
            kv_bytes=(sum(k.layers * k.page_bytes * self.allocators[k.table].live_pages
                          for k in self._kinds)
                      if self.serve.paged else self.kv_bytes),
        )

    def _step(self) -> None:
        if self.serve.paged:
            self._refresh_bt()
        if self.serve.fused_decode:
            # round 21: the whole quantum runs as ONE on-device
            # while_loop dispatch (decode.decode_loop_window) — cursors,
            # EOS/limit flags and the freed-page account advance on
            # device, and the loop hands back early when every lane is
            # done or finished lanes have freed enough pages to admit
            # the head of the queue (its worst-case footprint; 1<<30
            # disables the exit when nothing is waiting — a spurious
            # early exit only costs one extra host round-trip, so the
            # conservative target is safe). The tick count is a DEVICE
            # scalar; `_sync_evict` fetches it with the cursors and
            # accounts steps there — the host never assumes the quantum
            # ran to completion.
            ph = np.zeros((self.serve.slots,), np.int32)
            for s, lane in self._lanes.items():
                if lane.phase == "decode":
                    ph[s] = len(lane.pages)
            if self._pending:
                head = self._pending[0]
                need = -(-min(len(head.ids) + head.max_new_tokens,
                              self.serve.width) // self.serve.page_size)
            else:
                need = 1 << 30
            with self.spans.span("decode") as sp:
                (self.buf, self.cache, self.cursors, self.active, ticks,
                 _) = serve_decode.decode_loop_window(
                    self.params, self.cfg, self.buf, self.cache,
                    self.cursors, self.active, self.limits, self.keys,
                    self._place(ph, self._slot_spec),
                    self._place(np.asarray(self.serve.decode_quantum,
                                           np.int32), P()),
                    self._place(np.asarray(need, np.int32), P()),
                    self.eos_id, float(self.serve.temperature),
                    self._top_k, self.mesh,
                )
            self._pending_ticks = ticks
            # steps is filled at sync, once the device count lands
            self._open_quantum(sp.t0, sp.t1, steps=0)
            return
        with self.spans.span("decode") as sp:
            self.buf, self.cache, self.cursors, self.active = serve_decode.decode_step(
                self.params, self.cfg, self.buf, self.cache, self.cursors,
                self.active, self.limits, self.keys, self.eos_id,
                float(self.serve.temperature), self._top_k, self.mesh,
                steps=self.serve.decode_quantum,
            )
        self._open_quantum(sp.t0, sp.t1, steps=self.serve.decode_quantum)
        self.steps += self.serve.decode_quantum
        self._win["steps"] += self.serve.decode_quantum

    # ---- speculative decoding (round 17, tpukit/serve/spec.py) ----------

    def _spec_step(self) -> None:
        """One draft-and-verify quantum: propose up to `spec_k` tokens per
        slot ("draft" span — a host n-gram lookup or the draft model's
        jitted loop), then score all spec_k+1 positions in ONE batched
        target forward and accept a per-slot prefix ("verify" span).
        Counts as ONE step; a verify can append up to spec_k+1 tokens per
        slot, which is the whole speculation win."""
        from tpukit.serve import spec as spec_lib

        k, n = self.serve.spec_k, self.serve.slots
        # lanes live at dispatch (last sync's view): proposal targets and
        # the telemetry denominator
        live = np.zeros((n,), bool)
        for s, lane in self._lanes.items():
            if lane.phase == "decode":
                live[s] = True
        if self.serve.draft == "model":
            with self.spans.span("draft") as sp:
                draft_toks, draft_q, self.draft_cache = spec_lib.draft_propose(
                    self.draft_params, self.draft_cfg, self.buf,
                    self.draft_cache, self.cursors, self.keys,
                    k=k, temperature=float(self.serve.temperature),
                    top_k=self._top_k,
                )
                dlen = np.where(live, k, 0).astype(np.int32)
                draft_len = self._place(
                    np.full((n,), k, np.int32), self._slot_spec
                )
            t0 = sp.t0  # the quantum's dispatch wall spans draft and verify
            with self.spans.span("verify") as sp:
                (self.buf, self.cache, self.cursors, self.active, acc,
                 napp) = spec_lib.verify_step(
                    self.params, self.cfg, self.buf, self.cache,
                    self.cursors, self.active, self.limits, self.keys,
                    draft_toks, draft_q, draft_len, self.eos_id,
                    float(self.serve.temperature), self._top_k, k=k,
                    mesh=self.mesh,
                )
        else:
            # self-speculation: the n-gram proposal is FUSED into the
            # verify program (spec.spec_ngram_step) — one dispatch and
            # one sync per quantum, the vanilla step's host rhythm; a
            # host-side proposer would pay buf D2H + draft H2D + a
            # second dispatch every quantum
            with self.spans.span("verify") as sp:
                (self.buf, self.cache, self.cursors, self.active, acc,
                 napp, dlen) = spec_lib.spec_ngram_step(
                    self.params, self.cfg, self.buf, self.cache,
                    self.cursors, self.active, self.limits, self.keys,
                    self.eos_id, float(self.serve.temperature),
                    self._top_k, k=k, max_ngram=self.serve.ngram_max,
                    mesh=self.mesh,
                )
            t0 = sp.t0
        self._pending_spec = (live, dlen, acc, napp)
        self._open_quantum(t0, sp.t1, steps=1)
        self.steps += 1
        self._win["steps"] += 1

    def _drain_spec(self) -> None:
        """Fold the last verify's device counters into the spec telemetry
        (called from the sync fetch — the accepted/appended arrays ride
        the same D2H boundary as the cursors)."""
        if self._pending_spec is None:
            return
        live, dlen, acc, napp = self._pending_spec
        self._pending_spec = None
        acc = np.asarray(jax.device_get(acc))
        napp = np.asarray(jax.device_get(napp))
        for s in np.flatnonzero(live):
            self.spec_proposed += int(dlen[s])
            self.spec_accepted += int(min(acc[s], dlen[s]))
            self.spec_hist[int(napp[s])] += 1

    def _fetch_cursors(self):
        """The per-quantum D2H: cursors + active flags, with whatever device
        counters the last dispatch left pending coalesced into the same
        round trip. One small fetch per quantum — the price of host-side EOS
        detection. Returns host `(cursors, active)`."""
        if self._pending_spec is not None:
            # dlen is a device array on the fused ngram path, host numpy on
            # the model path — device_get passes the latter through untouched
            live, dlen, acc, napp = self._pending_spec
            cur, act, dlen, acc, napp = map(np.asarray, jax.device_get(
                (self.cursors, self.active, dlen, acc, napp)))
            self._pending_spec = (live, dlen, acc, napp)
        elif self._pending_ticks is not None:
            # fused window (round 21): the actual tick count rides the
            # same D2H round-trip as the cursors — the loop may have
            # exited early, so steps are accounted HERE, from the
            # device's answer, never assumed from the quantum
            cur, act, ticks = map(np.asarray, jax.device_get(
                (self.cursors, self.active, self._pending_ticks)))
            self._pending_ticks = None
            ran = int(ticks)
            self.steps += ran
            self._win["steps"] += ran
            if self._quantum is not None:
                self._quantum["steps"] = ran
        else:
            # whatever counters the model keeps in its cache ride the same
            # round trip (none for a model that keeps none): an integer
            # array counts since the cache was made and the quantum gets the
            # difference, a float array holds gauges, reported as fetched
            names, counters = self._model.counters(self.cache)
            cur, act, *counters = map(np.asarray, jax.device_get(
                (self.cursors, self.active, *counters)))
            if names:
                gauge = np.concatenate([np.full(c.size, c.dtype.kind == "f") for c in counters])
                seen = np.concatenate([c.ravel().astype(np.float64) for c in counters])
                if self._quantum is not None:
                    since = np.where(gauge, seen, seen - self._counters)
                    self._quantum.update(
                        (k, float(v) if g else int(v)) for k, v, g in zip(names, since, gauge))
                self._counters = seen
        self._drain_spec()
        return cur, act

    def _retire(self, now: float, cur, act, fetched_s: float) -> tuple[int, int]:
        """After the fetch returned (run clock `fetched_s`): stamp the tokens
        each decoding lane's cursor advanced by as delivered, retire the
        lanes that finished, and account generated tokens. Returns this
        sync's `(delivered tokens, finished lanes)`."""
        tr = self.tracer
        delivered = 0
        for s, lane in self._lanes.items():
            if lane.phase != "decode":
                continue
            n = int(cur[s]) - lane.prompt_len - lane.delivered
            if n > 0:
                lane.deliveries.append((fetched_s, n))
                lane.delivered += n
                delivered += n
        # prefilling paged lanes are act=False by design, not finished;
        # stuck_request-pinned lanes (chaos, round 24) are REFUSED
        # retirement — they hold their slot until deadline eviction
        finished = [
            s for s, lane in self._lanes.items()
            if lane.phase == "decode" and not act[s]
            and lane.req.rid not in self.stuck_rids
        ]
        gen_live = sum(
            lane.delivered for s, lane in self._lanes.items()
            if lane.phase == "decode" and s not in finished
        )
        if finished:
            host_buf = np.asarray(jax.device_get(self.buf))
            for s in finished:
                lane = self._lanes.pop(s)
                length = int(cur[s])
                generated = length - lane.prompt_len
                ids = host_buf[s, :length].copy()
                if self.serve.paged:
                    # a prefix-hit admission SKIPS its shared chunks, so the
                    # buffer row never received those prompt tokens (their
                    # K/V lives in the shared pages; decode never reads buf
                    # below prompt_len-1, which is always in a dispatched
                    # chunk) — the completion's prompt comes from the
                    # request itself
                    ids[: lane.prompt_len] = lane.req.ids
                reason = (
                    "length"
                    if length >= min(lane.prompt_len + lane.req.max_new_tokens,
                                     self.serve.width)
                    else "eos"
                )
                self._complete(s, lane, ids, generated, reason, now, fetched_s)
        self._gen_total = sum(c.generated for c in self.completions) + gen_live
        return delivered, len(finished)

    def _complete(self, slot: int, lane: _Lane, ids, generated: int,
                  reason: str, now: float, fetched_s: float) -> None:
        """Turn a popped lane into its Completion and hand back what it held
        (natural retirement and deadline eviction alike)."""
        self.evicted[reason] += 1
        self.completions.append(Completion(
            rid=lane.req.rid, ids=ids,
            prompt_len=lane.prompt_len, generated=generated,
            reason=reason, arrival_s=lane.req.arrival_s,
            admit_s=lane.admit_s, done_s=now,
            pages=len(lane.pages), prefix_pages=lane.shared,
            active_s=lane.active_s or lane.admit_s,
            deliveries=tuple(lane.deliveries),
        ))
        if self.tracer is not None:
            # finish is stamped when the fetch RETURNED (> done_s=now,
            # captured before the sync): the last quantum's sync wall
            # belongs inside the tree's lifetime, so the phase walls can
            # sum to the tree's e2e
            self.tracer.emit("finish", trace_id(lane.req), rid=lane.req.rid,
                             t=fetched_s, reason=reason, generated=generated,
                             replica=self.replica)
        if self.serve.paged:
            # drop this lane's references: private pages free (or retire
            # into the prefix LRU if registered), shared pages survive for
            # their other readers — and zero the block-table row so any
            # stale in-flight write lands in the null page, never in a
            # re-issued one
            self._release_pages(slot, lane)
        self._free.append(slot)

    def _release_pages(self, slot: int, lane: _Lane) -> None:
        """Hand back every page `lane` held, of every kind, and zero the
        slot's block-table rows."""
        self.allocator.release(lane.pages)
        for table, held in lane.more_pages.items():
            self.allocators[table].release(held)
        for host in self._tables.values():
            host[slot] = 0
        self._bt_dirty = True

    def _evict_deadlines(self, now: float, fetched_s: float) -> int:
        """Retire decode-resident lanes whose end-to-end deadline_ms has
        expired (round 24): the partial output becomes a Completion with
        reason=\"deadline\" plus a `kind=\"deadline_miss\"` JSONL record,
        and the paged engine parks the lane's pages cheaply (release →
        registered lead pages retire into the prefix LRU, private pages
        free, block-table row zeroed — the same write-safety spelling as
        natural retirement). Runs AFTER `_retire`, so the quantum is
        already synced and the extra cursor/buffer fetch happens only on
        the rare eviction path. Prefill-phase lanes wait for their decode
        transition (one chunk of grace) so an in-flight chunk never
        targets released pages. Returns the number of lanes evicted."""
        over = [
            (s, lane) for s, lane in self._lanes.items()
            if lane.phase == "decode" and lane.req.deadline_ms > 0
            and (now - lane.req.arrival_s) * 1e3 > lane.req.deadline_ms
        ]
        if not over:
            return 0
        cur, host_buf = map(
            np.asarray, jax.device_get((self.cursors, self.buf))
        )
        for s, lane in over:
            self._lanes.pop(s)
            length = int(cur[s])
            generated = max(length - lane.prompt_len, 0)
            ids = host_buf[s, :length].copy()
            if self.serve.paged:
                ids[: lane.prompt_len] = lane.req.ids
            over_ms = (now - lane.req.arrival_s) * 1e3 - lane.req.deadline_ms
            if self.logger is not None:
                rec = dict(
                    kind="deadline_miss", rid=lane.req.rid,
                    deadline_ms=lane.req.deadline_ms,
                    over_ms=round(over_ms, 3), generated=generated,
                )
                if self.replica is not None:
                    rec["replica"] = self.replica
                self.logger.log(**rec)
            if self.metrics is not None:
                self.metrics.inc("serve_deadline_miss")
            self._complete(s, lane, ids, generated, "deadline", now, fetched_s)
        # _gen_total is untouched: the evicted tokens were already counted
        # through the last sync's gen_live term, and the next _retire
        # recomputes from completions + live lanes
        return len(over)

    def _close_quantum(self, host: dict, s0: float, s1: float,
                       delivered: int, finished: int) -> None:
        """Complete the dispatch+sync pair `_open_quantum` started: `[s0, s1]`
        is the wall-to-sync (device) wall, `host` the walls of the spans that
        ran between the previous sync's return and `s0`, by name, with
        `other` so that they sum to that gap — the serial host time no
        device work hides except an in-flight prefill chunk. Says so on
        `logging` when the period is far beyond the recent median, traced
        or not, and emits the quantum event when traced."""
        q, self._quantum = self._quantum, None
        since, self._host_from = self._host_from, s1
        if q is None:  # a sync with nothing dispatched (step primitives driven by hand)
            return
        host["other"] = max(s0 - since - sum(host.values()), 0.0)
        period = s1 - since
        if len(self._periods) >= SLOW_QUANTUM_MIN_HISTORY:
            median = statistics.median(self._periods)
            if period > SLOW_QUANTUM_FACTOR * median:
                log.warning(
                    "slow quantum %d%s: period %.1f ms against a median of "
                    "%.1f ms over the last %d; sync wait %.1f ms, host ms %s, "
                    "decoding %d, prefilling %d, pending %d",
                    self._quanta,
                    "" if self.replica is None else f" (replica {self.replica})",
                    period * 1e3, median * 1e3, len(self._periods),
                    (s1 - s0) * 1e3,
                    {k: round(v * 1e3, 3) for k, v in host.items()},
                    q["decoding"], q["prefilling"], q["pending"],
                )
        self._periods.append(period)
        self._quanta += 1
        if self.tracer is not None:
            self.tracer.emit("quantum", -1, s0=s0, s1=s1, host=host,
                             delivered=delivered, finished=finished,
                             replica=self.replica, **q)

    # ---- telemetry -------------------------------------------------------

    def _emit_window(self) -> None:
        b = self.spans.window()
        comps = self.completions[self._win["comps0"]:]
        new_tokens = self._gen_total - self._win["gen0"]
        steps = self._win["steps"]
        # occupancy = slot-step utilization: the fraction of slot x decode-
        # tick capacity this window that actually yielded a token (frozen
        # finished lanes and drained tails read as idle — honest). Under
        # speculation a "step" is one verify dispatch with a per-slot
        # emission capacity of spec_k + 1, so the denominator widens.
        cap = (self.serve.spec_k + 1) if self.serve.draft else 1
        rec = dict(
            kind="serve", window=self._window_idx, steps=steps,
            new_tokens=new_tokens,
            tokens_per_sec=(new_tokens / b["total_s"]) if b["total_s"] else None,
            occupancy=(new_tokens / (self.serve.slots * steps * cap))
            if steps else 0.0,
            admitted=self.admitted - self._win["admit0"],
            completed=len(comps), queue_depth=len(self._pending),
            slots=self.serve.slots, window_s=b["total_s"],
            seconds=b["seconds"], fractions=b["fractions"],
            p50_e2e_s=_pct([c.e2e_s for c in comps], 50),
            p99_e2e_s=_pct([c.e2e_s for c in comps], 99),
            p50_token_s=_pct([c.per_token_s for c in comps], 50),
            p99_token_s=_pct([c.per_token_s for c in comps], 99),
            # explicit residual (round 20, the fit() goodput discipline):
            # the window's named spans + other_s sum to window_s exactly
            # — drift can't silently vanish
            other_s=b["seconds"].get("other", 0.0),
            # per-window dispatch-vs-device attribution (ROADMAP #3):
            # decode/draft/verify spans ARE the async dispatch calls;
            # the device's compute wall surfaces as the sync span
            dispatch_overhead_s=(b["seconds"].get("decode", 0.0)
                                 + b["seconds"].get("draft", 0.0)
                                 + b["seconds"].get("verify", 0.0)),
            device_s=b["seconds"].get("sync", 0.0),
        )
        if self.serve.paged:
            # the paged health triple (round 15): pool pressure, how much
            # admission work prefix reuse is deleting, and the per-request
            # footprint the ring design couldn't see
            hits = self.allocator.stats.prefix_hits - self._win["hits0"]
            rec["page_occupancy"] = self.allocator.occupancy
            rec["prefix_hit_rate"] = (
                hits / rec["admitted"] if rec["admitted"] else None
            )
            rec["pages_per_request"] = (
                float(np.mean([c.pages for c in comps])) if comps else None
            )
        if self.serve.draft:
            # the spec health triple (round 17): how much of the draft the
            # target accepted, the per-verify emission shape, and the
            # draft/verify wall split (rides the spans already in rec)
            prop = self.spec_proposed - self._win["prop0"]
            acc = self.spec_accepted - self._win["acc0"]
            rec["spec"] = dict(
                draft=self.serve.draft, k=self.serve.spec_k,
                proposed=prop, accepted=acc,
                accept_rate=(acc / prop) if prop else None,
                accepted_hist=[
                    h - h0 for h, h0 in zip(self.spec_hist, self._win["hist0"])
                ],
            )
        if self.replica is not None:
            rec["replica"] = self.replica
        if self.logger is not None:
            self.logger.log(**rec)
        if self.recorder is not None:
            self.recorder.record(
                "serve", window=self._window_idx, steps=steps,
                new_tokens=new_tokens, occupancy=rec["occupancy"],
                completed=len(comps),
            )
        if self.metrics is not None:
            self._metrics_window(comps, rec)
        self._window_idx += 1
        self._win = dict(
            steps=0, gen0=self._gen_total, admit0=self.admitted,
            comps0=len(self.completions),
            hits0=self.allocator.stats.prefix_hits if self.serve.paged else 0,
            prop0=self.spec_proposed, acc0=self.spec_accepted,
            hist0=list(self.spec_hist),
        )

    def _metrics_window(self, comps, rec: dict) -> None:
        """Fold one window into the metric registry and account the
        declared SLOs — pure derivation from already-produced data
        (completions, trace trees, quantum events); the step primitives
        never see this code."""
        m = self.metrics
        rep = self.replica
        # per-completion latency histograms + deterministic counters.
        # ttft = arrival -> decode-ready (queue wait + prefill +
        # handoff), the trace-tree partition read off the Completion
        # timestamps the engine already stamps.
        for c in comps:
            m.observe("serve_e2e_s", c.e2e_s, replica=rep)
            m.observe("serve_ttft_s", max(c.active_s - c.arrival_s, 0.0),
                      replica=rep)
            m.observe("serve_queue_wait_s", max(c.admit_s - c.arrival_s, 0.0),
                      replica=rep)
            m.observe("serve_tpot_s", c.per_token_s, replica=rep)
            m.observe("serve_tokens_per_request", c.generated, replica=rep)
            m.inc("serve_requests", 1, replica=rep, reason=c.reason)
            m.inc("serve_tokens", c.generated, replica=rep)
        # window gauges (point-in-time; replica-labeled so merges keep
        # every replica's latest)
        if rec.get("tokens_per_sec") is not None:
            m.gauge("serve_tokens_per_sec", rec["tokens_per_sec"], replica=rep)
        m.gauge("serve_occupancy", rec["occupancy"], replica=rep)
        m.gauge("serve_queue_depth", rec["queue_depth"], replica=rep)
        if self.serve.paged:
            m.gauge("serve_page_occupancy", rec["page_occupancy"], replica=rep)
        if self.tracer is not None:
            # phase walls from newly-closed span trees (trees are cheap
            # to rebuild at window cadence; the seen-set keeps each
            # request observed exactly once even though the ring is
            # fleet-shared)
            rids = {c.rid for c in self.completions}
            for t in trace_lib.build_trees(self.tracer.snapshot()):
                if (t["trace"] in self._metrics_traces_seen
                        or not t["closed"] or t["rid"] not in rids):
                    continue
                self._metrics_traces_seen.add(t["trace"])
                for ph, wall in t["phases"].items():
                    m.observe("serve_phase_s", wall, replica=rep, phase=ph)
            # per-quantum dispatch-vs-sync walls, watermarked so each
            # quantum lands once (events are time-sorted by snapshot())
            mark = self._metrics_q_mark
            for ev in self.tracer.snapshot():
                if (ev.get("ev") != "quantum"
                        or ev.get("replica") != rep
                        or ev.get("t1", 0.0) <= mark):
                    continue
                self._metrics_q_mark = max(self._metrics_q_mark, ev["t1"])
                m.observe("serve_dispatch_s", ev["t1"] - ev["t0"],
                          replica=rep, phase="dispatch")
                if "s1" in ev:
                    m.observe("serve_sync_s", ev["s1"] - ev["s0"],
                              replica=rep, phase="sync")
        if self.slo_accountant is not None:
            samples = {
                "e2e": [c.e2e_s for c in comps],
                "ttft": [max(c.active_s - c.arrival_s, 0.0) for c in comps],
                "queue_wait": [max(c.admit_s - c.arrival_s, 0.0) for c in comps],
                "tpot": [c.per_token_s for c in comps],
            }
            slo_rec = dict(kind="slo", window=self._window_idx,
                           **self.slo_accountant.evaluate(samples))
            if self.replica is not None:
                slo_rec["replica"] = self.replica
            if self.logger is not None:
                self.logger.log(**slo_rec)
            if self.recorder is not None:
                self.recorder.record(
                    "slo", window=self._window_idx,
                    overall_compliance=slo_rec["overall_compliance"],
                )
        if self.metrics_dir:
            metrics_lib.publish_snapshot(
                self.metrics_dir, self.replica or 0, m,
                time_s=time.time(),
            )

    def summary(self, wall_s: float) -> dict:
        comps = self.completions
        rec = dict(
            kind="serve_summary", requests=len(comps),
            slots=self.serve.slots, buckets=list(self.serve.buckets),
            buckets_used=sorted(self.buckets_used),
            generated_tokens=sum(c.generated for c in comps),
            decode_steps=self.steps, wall_s=wall_s,
            tokens_per_sec=(sum(c.generated for c in comps) / wall_s)
            if wall_s else None,
            mean_occupancy=(
                sum(c.generated for c in comps)
                / (self.serve.slots * self.steps
                   * ((self.serve.spec_k + 1) if self.serve.draft else 1))
            ) if self.steps else 0.0,
            admitted=self.admitted, evicted_eos=self.evicted["eos"],
            evicted_length=self.evicted["length"],
            evicted_deadline=self.evicted["deadline"],
            p50_e2e_s=_pct([c.e2e_s for c in comps], 50),
            p99_e2e_s=_pct([c.e2e_s for c in comps], 99),
            p50_token_s=_pct([c.per_token_s for c in comps], 50),
            p99_token_s=_pct([c.per_token_s for c in comps], 99),
        )
        if self.replica is not None:
            rec["replica"] = self.replica
        ep = self.spans.epoch()
        rec["prefill_s"] = ep["seconds"].get("prefill", 0.0)
        rec["decode_s"] = ep["seconds"].get("decode", 0.0)
        rec["sync_s"] = ep["seconds"].get("sync", 0.0)
        # wall clock outside every span, surfaced instead of silently
        # vanishing (the run loop resets the span epoch at its t0, so a
        # standalone run's named + other walls sum to wall_s)
        named = (rec["prefill_s"] + rec["decode_s"] + rec["sync_s"]
                 + ep["seconds"].get("draft", 0.0)
                 + ep["seconds"].get("verify", 0.0))
        rec["other_s"] = max(wall_s - named, 0.0)
        rec["dispatch_overhead_s"] = (rec["decode_s"]
                                      + ep["seconds"].get("draft", 0.0)
                                      + ep["seconds"].get("verify", 0.0))
        rec["device_s"] = rec["sync_s"]
        rec["max_live_slots"] = self.max_live
        rec["kv_bytes"] = self.kv_bytes
        if self.serve.draft:
            rec["draft_s"] = ep["seconds"].get("draft", 0.0)
            rec["verify_s"] = ep["seconds"].get("verify", 0.0)
            rec["spec"] = dict(
                draft=self.serve.draft, k=self.serve.spec_k,
                proposed=self.spec_proposed, accepted=self.spec_accepted,
                accept_rate=(self.spec_accepted / self.spec_proposed)
                if self.spec_proposed else None,
                accepted_hist=list(self.spec_hist),
            )
        if self.serve.paged:
            st = self.allocator.stats
            hit = [c.admit_latency_s for c in comps if c.prefix_pages > 0]
            cold = [c.admit_latency_s for c in comps if c.prefix_pages == 0]
            rec.update(
                page_size=self.serve.page_size, num_pages=self.num_pages,
                kv_dtype=self.serve.kv_dtype,
                prefix_hits=st.prefix_hits,
                prefix_hit_rate=st.prefix_hits / max(self.admitted, 1),
                prefix_pages_reused=st.prefix_pages_reused,
                reclaimed_pages=st.reclaimed,
                page_occupancy=self.allocator.occupancy,
                pages_per_request=float(np.mean([c.pages for c in comps]))
                if comps else None,
                admit_latency_hit_s=float(np.mean(hit)) if hit else None,
                admit_latency_cold_s=float(np.mean(cold)) if cold else None,
            )
        if self.tracer is not None:
            # per-request phase latency percentiles from THIS engine's
            # completed span trees (the tracer may be fleet-shared, so
            # restrict to our own completions)
            rids = {c.rid for c in comps}
            trees = [t for t in trace_lib.build_trees(self.tracer.snapshot())
                     if t["rid"] in rids]
            rec["phase_p50"], rec["phase_p99"] = trace_lib.phase_stats(trees)
            rec["trace_complete"] = trace_lib.completeness(trees)
            # ring evictions poison every aggregate above — surface them
            # instead of letting a saturated ring read as complete
            # (report.py warns when nonzero)
            rec["trace_dropped"] = self.tracer.dropped_by_replica.get(
                self.replica, 0
            )
        if self.slo_accountant is not None:
            rec["slo_overall_compliance"] = (
                self.slo_accountant.overall_compliance()
            )
        return rec

    # ---- step primitives (the fleet hooks, round 19) ---------------------
    # `run()` below is spelled entirely in terms of these, so a FleetRouter
    # (tpukit/serve/fleet.py) driving N engines round-robin exercises the
    # exact scheduling code the standalone loop does — the token-parity
    # guarantee transfers instead of being re-proven.

    @property
    def free_slots(self) -> int:
        return len(self._free)

    @property
    def live_lanes(self) -> int:
        return len(self._lanes)

    @property
    def decoding_lanes(self) -> int:
        return sum(1 for l in self._lanes.values() if l.phase == "decode")

    @property
    def generated_tokens(self) -> int:
        """Tokens generated so far (completed + live lanes, as of the last
        sync) — the fleet router's aggregation counter."""
        return self._gen_total

    @property
    def free_pages(self) -> int:
        """Pages an admission could obtain (free + reclaimable retained);
        the ring has no page budget, so it reports effectively-infinite —
        the router's least-loaded tiebreak never binds on it."""
        return self.allocator.available_pages if self.serve.paged else (1 << 30)

    def admit(self, reqs: list[Request], now: float) -> list[Request]:
        """Admit as many of `reqs` (in order) as capacity allows; returns
        the un-admitted tail. Ring: up to the free-slot count in ONE
        batched bucket-grouped prefill. Paged: head-of-line page-aware
        admission — stops at the first request the pool cannot cover
        (FIFO, no starvation), exactly the run-loop semantics."""
        if not self.serve.paged:
            take = reqs[: len(self._free)]
            if take:
                self._admit_batch(take, now)
            return list(reqs[len(take):])
        left = list(reqs)
        if left:
            with self.spans.span("admit"):
                while left and self._free:
                    if not self._admit_paged_one(left[0], now):
                        break
                    left.pop(0)
        return left

    def poll_prefill(self, now: float) -> None:
        """Advance every prefilling paged lane one chunk (no-op on the
        ring, whose prefill is one-shot at admission)."""
        if self.serve.paged:
            self._dispatch_prefill_chunks(now)

    def dispatch_decode(self) -> bool:
        """Dispatch one decode quantum (or spec draft-and-verify quantum)
        if any lane is decoding; returns whether anything was dispatched.
        The dispatch is async — callers overlap several engines' quanta by
        dispatching all of them before the first `sync`."""
        if not any(l.phase == "decode" for l in self._lanes.values()):
            return False
        if self.serve.draft:
            self._spec_step()
        else:
            self._step()
        return True

    def sync(self, now: float) -> None:
        """The per-quantum host sync: fetch cursors/flags (`sync`, the wait
        for the device), then deliver tokens, retire finished lanes and
        evict deadline-expired ones (`retire`), close the quantum's record,
        and emit a `kind="serve"` window when one is due (`window`)."""
        host = self.spans.lap()  # what the host did since the last sync returned
        with self.spans.span("sync") as sp:
            cur, act = self._fetch_cursors()
        self.spans.lap()  # the wait is no host work: the next account starts at s1
        with self.spans.span("retire"):
            delivered, finished = self._retire(now, cur, act, sp.t1)
            finished += self._evict_deadlines(now, sp.t1)
        self._close_quantum(host, sp.t0, sp.t1, delivered, finished)
        if self._win["steps"] >= self.serve.window_steps:
            with self.spans.span("window"):
                self._emit_window()

    def finish(self, wall_s: float) -> list[Completion]:
        """Flush the partial window and emit the `kind="serve_summary"`
        record; returns the completions. The run loop's epilogue, exposed
        so the fleet can finalize each replica at fleet shutdown."""
        if self._win["steps"]:
            self._emit_window()
        rec = self.last_summary = self.summary(wall_s)
        if self.logger is not None:
            self.logger.log(**rec)
        if self.recorder is not None:
            self.recorder.record(
                "serve_summary", requests=rec["requests"],
                tokens_per_sec=rec["tokens_per_sec"],
                mean_occupancy=rec["mean_occupancy"],
            )
        if self.tracer is not None and self.replica is None:
            # standalone epilogue: persist the ring + span trees into the
            # JSONL (fleet replicas share the router's tracer — the
            # router flushes ONCE at fleet shutdown, covering killed
            # replicas that never reach finish())
            trace_lib.flush_to_logger(
                self.tracer, self.logger,
                trace_lib.build_trees(self.tracer.snapshot()),
            )
        if self.metrics is not None and self.replica is None:
            # standalone metrics epilogue (a fleet's router owns this,
            # same ownership rule as the tracer flush above): the
            # kind="metrics" summary row plus the snapshot-file merge
            rec_m = dict(kind="metrics", source="serve",
                         **self.metrics.summary())
            if self.logger is not None:
                self.logger.log(**rec_m)
            if self.recorder is not None:
                self.recorder.record(
                    "metrics", source="serve",
                    hists=len(rec_m["hists"]),
                    tokens=self.metrics.sum_counter("serve_tokens"),
                )
            if self.metrics_dir:
                metrics_lib.publish_snapshot(
                    self.metrics_dir, self.replica or 0, self.metrics,
                    time_s=time.time(),
                )
                merged, meta = metrics_lib.merge_snapshot_dir(self.metrics_dir)
                metrics_lib.write_merged(self.metrics_dir, merged, meta=meta)
        return self.completions

    def requeue_live(self) -> list[Request]:
        """The in-flight requests of this replica, reconstructed from the
        Request objects themselves — the completion-carries-prompt
        invariant (round 15) means a lane's original prompt never depends
        on device state, so a chaos-killed replica's work re-queues onto
        survivors losslessly: same prompt, same per-request seed, hence
        (engine parity) the same tokens. Partial output is discarded, so
        each request's tokens are emitted exactly once, by whichever
        replica finishes it. Does not mutate the engine — a killed
        replica is simply dropped."""
        return sorted((l.req for l in self._lanes.values()),
                      key=lambda r: r.rid)

    # ---- disaggregated prefill (round 19, tpukit/serve/fleet.py) ---------

    def release_lane(self, slot: int) -> None:
        """Retire lane `slot` WITHOUT a completion — the prefill worker's
        half of the page handoff: once a finished prefix is copied to a
        decode replica, the worker drops its references (registered lead
        pages retire into the prefix LRU for future hits, private pages
        free) and zeroes the block-table row so any stale in-flight write
        lands in the null page (write-safety invariant 2)."""
        lane = self._lanes.pop(slot)
        if self.serve.paged:
            self._release_pages(slot, lane)
        self._free.append(slot)

    def adopt_prefilled(self, req: Request, pages: list[int], shared: int,
                        admit_s: float, now: float, key) -> int:
        """Decode-replica half of the disaggregated handoff: arm a lane
        whose K/V pages were prefilled ELSEWHERE (already copied into this
        engine's pool at `pages` by fleet._copy_pages) — the replica never
        runs a prefill program, so its serve-path compile budget is one
        decode program plus this (dynamic-update-slice-only) arm.

        `pages` must already be allocated/claimed on THIS engine's
        allocator (`shared` = how many lead pages are decode-side registry
        claims); the block-table row, buffer row (the full prompt — the
        first decode tick re-forwards position prompt_len-1) and per-slot
        decode state are armed here. Registers the lead
        `(prompt_len-1)//P` pages so later handoffs of the same prefix
        claim them instead of re-copying (write-safety invariant 1: the
        last prompt position's page stays private)."""
        if not self.serve.paged:
            raise ValueError(
                "adopt_prefilled requires the paged cache (page_size > 0) "
                "— the disaggregated handoff rides page granularity"
            )
        if len(self._kinds) > 1:
            raise NotImplementedError(
                f"the prefill handoff copies one kind of page; "
                f"{type(self.cfg).__name__} keeps "
                f"{[k.table for k in self._kinds]}"
            )
        plen = len(req.ids)
        slot = self._free.popleft()
        self._bt[slot] = 0
        self._bt[slot, : len(pages)] = pages
        self._bt_dirty = True
        self._refresh_bt()
        row = np.zeros((self.serve.padded_width,), np.int32)
        row[:plen] = req.ids
        limit = min(plen + req.max_new_tokens, self.serve.width)
        key = np.asarray(key, np.uint32)
        (self.buf, self.cursors, self.active, self.limits,
         self.keys) = serve_decode.adopt_slot(
            self.buf, self.cursors, self.active, self.limits, self.keys,
            self._place(np.asarray(slot, np.int32), P()),
            self._place(row, P()),
            self._place(np.asarray(plen, np.int32), P()),
            self._place(np.asarray(limit, np.int32), P()),
            self._place(key, P()),
        )
        reg = (plen - 1) // self.serve.page_size
        self.allocator.register(req.ids, pages[:reg])
        self._lanes[slot] = _Lane(
            req, admit_s, plen, self.bucket_for(plen), pages=list(pages),
            shared=shared, next_chunk=0, prefill_end=0, phase="decode",
            active_s=now, key=key,
        )
        self.admitted += 1
        self.max_live = max(self.max_live, len(self._lanes))
        if self.tracer is not None:
            self.tracer.emit("adopt", trace_id(req), rid=req.rid, t=now,
                             slot=slot, replica=self.replica)
        if shared:
            self.allocator.stats.prefix_hits += 1
            self.allocator.stats.prefix_pages_reused += shared
        return slot

    # ---- the loop --------------------------------------------------------

    def begin_run(self, t0: float, joined_s: float = 0.0) -> None:
        """Pin the run clock at `t0` (perf_counter) and start every account
        at run-clock `joined_s` (0: the run's start; later for a replica
        scaled up mid-run): the timeline was constructed earlier, and the
        construction->run gap would otherwise leak into the summary's
        `other_s` residual and the first quantum's `host`. A loop that
        drives the step primitives itself (`FleetRouter`) calls this with
        its own t0, so span times compare with the `now` it passes in."""
        self.spans.set_epoch(t0)
        self.spans.epoch()
        self._host_from = joined_s

    def run(self, requests, max_wall_s: float | None = None) -> list[Completion]:
        """Serve `requests` (admitted no earlier than their `arrival_s`)
        to completion. Admission fills free slots between decode steps —
        an arriving prefill never stalls an active slot's decode — and a
        request whose prompt exceeds every bucket raises at admission.
        Emits a `kind="serve"` window every `window_steps` decode steps
        and a final `kind="serve_summary"`; returns the completions in
        finish order."""
        self._pending = deque(sorted(requests, key=lambda r: (r.arrival_s, r.rid)))
        t0 = time.perf_counter()
        self.begin_run(t0)
        if self.tracer is not None:
            self.tracer.set_epoch(t0)
            for r in self._pending:
                self.tracer.emit("enqueue", trace_id(r), rid=r.rid,
                                 t=r.arrival_s, replica=self.replica)
        now = 0.0
        while self._pending or self._lanes:
            now = time.perf_counter() - t0
            if max_wall_s is not None and now > max_wall_s:
                raise TimeoutError(
                    f"serve run exceeded max_wall_s={max_wall_s} with "
                    f"{len(self._pending)} pending / {len(self._lanes)} live"
                )
            # page-aware admission control (paged): a request needs a free
            # lane AND its worst-case page footprint; the head of the queue
            # waits (FIFO, no starvation) when the pool can't cover it
            ready: list[Request] = []
            while (self._pending and len(ready) < len(self._free)
                   and self._pending[0].arrival_s <= now):
                ready.append(self._pending.popleft())
            for req in reversed(self.admit(ready, now)):
                self._pending.appendleft(req)
            self.poll_prefill(time.perf_counter() - t0)
            if not self.dispatch_decode():
                if not self._lanes and self._pending:
                    # nothing decoding and the next arrival is in the future
                    wait = self._pending[0].arrival_s - now
                    if wait > 0:
                        # an open loop's waiting must not read as host work
                        with self.spans.span("idle"):
                            time.sleep(min(wait, 0.05))
                continue
            self.sync(time.perf_counter() - t0)
        return self.finish(time.perf_counter() - t0)


STREAM_PROFILES = ("uniform", "repetitive", "shared_prefix")


def synthetic_request_stream(tokenizer, n: int, *, seed: int = 0,
                             max_new_tokens: int = 16,
                             buckets=(16, 32), qps: float = 0.0,
                             corpus=None, lengths=None,
                             shared_prefix: int = 0,
                             stream_profile: str = "uniform") -> list[Request]:
    """Seeded synthetic request stream: prompts cut from the offline
    fixture corpus at seeded lengths spanning the bucket set, arrivals
    all-at-once (qps=0, an offered-load saturation test) or spaced by a
    seeded exponential process (qps>0). Deterministic per seed — the
    serving bench compares continuous vs serial on the SAME stream.
    `lengths` restricts the drawn prompt lengths to a fixed set (the
    bench uses it so the SERIAL baseline's per-prompt-length compiles
    stay bounded; the engine is bucket-bounded either way).

    `stream_profile` (round 17) names the workload SHAPE so a bench or
    test run is reproducible from one spelling (`--stream_profile` in
    main-serve.py):

      - "uniform" (default): the original per-request corpus cuts.
      - "repetitive": each prompt is a short seeded phrase (2-4 tokens)
        TILED to its target length — the structured/templated traffic
        shape where self-speculation (n-gram drafting, spec.py) wins:
        histories recur by construction, so prompt-lookup proposals land.
      - "shared_prefix": every request shares one system prompt; uses
        `shared_prefix` (defaulting it to half the largest bucket when
        unset) — the paged prefix-reuse shape (round 15).

    `shared_prefix > 0` prepends the SAME `shared_prefix`-token system
    prompt (cut from the corpus head) to every request — the
    millions-of-users-one-system-prompt shape that paged prefix reuse
    (round 15) exists for. Bodies stay per-request; combined prompts are
    truncated to the largest bucket."""
    from tpukit.data import synthetic_stories

    if stream_profile not in STREAM_PROFILES:
        raise ValueError(
            f"stream_profile={stream_profile!r} must be one of "
            f"{STREAM_PROFILES}"
        )
    rng = np.random.RandomState(seed)
    corpus = corpus if corpus is not None else synthetic_stories(max(64, n))
    if stream_profile == "shared_prefix" and shared_prefix <= 0:
        shared_prefix = max(buckets) // 2
    prefix: list[int] = []
    if shared_prefix > 0:
        prefix = list(tokenizer(
            [" ".join(corpus)], truncation=True, max_length=shared_prefix
        )["input_ids"][0])
    out = []
    t = 0.0
    for i in range(n):
        text = corpus[int(rng.randint(len(corpus)))]
        if lengths is not None:
            target = int(lengths[int(rng.randint(len(lengths)))])
        else:
            target = int(rng.randint(4, max(buckets) + 1))
        ids = tokenizer([text], truncation=True, max_length=target)["input_ids"][0]
        if stream_profile == "repetitive":
            phrase = list(ids)[: int(rng.randint(2, 5))]
            reps = -(-target // max(len(phrase), 1))
            ids = (phrase * reps)[:target]
        ids = (prefix + list(ids))[: max(buckets)]
        if qps > 0:
            t += float(rng.exponential(1.0 / qps))
        out.append(Request(
            rid=i, ids=tuple(int(x) for x in ids),
            max_new_tokens=max_new_tokens, seed=seed + i, arrival_s=t,
        ))
    return out

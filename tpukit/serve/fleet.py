"""Fleet serving: a request router over N engine replicas (round 19).

ROADMAP #1's last open stage. The round-14 engine is deliberately a
single-host scheduler over ONE device grid; this module is the layer that
takes it to "millions of users" shape: N data-parallel `ServeEngine`
replicas, each constructed on a DISJOINT device subset of the host's mesh
(the round-15 grid picker already takes device subsets, so N replicas x
model-parallel grids coexist in one process), behind one router that owns
the shared request stream. Three capabilities ride on that:

  - **Least-loaded admission**: the router holds the global FIFO queue and
    assigns each arrived request to the replica with the most free slots
    (ties broken by free pages, then lowest replica id). Per-request seeds
    travel WITH the request, and every replica's scheduling is the proven
    engine (each completion is token-for-token the serial cached decode of
    its own prompt + seed, whatever the admit/evict interleaving) — so the
    fleet's output is token-identical to a single engine consuming the
    same stream, the parity bar every serve round has held
    (tests/test_fleet.py).
  - **Disaggregated prefill** (`FleetConfig.disagg_prefill`, paged only):
    a dedicated prefill worker runs chunked prefill into its OWN paged
    pool; a finished prefix hands off to a decode replica as pages — the
    decode side first CLAIMS any already-registered prefix pages from its
    own registry (refcounted read-only, the round-15 machinery), then the
    remaining written pages are copied device-to-device
    (`paged.extract_pages` -> one `jax.device_put` at the destination
    layout -> `paged.insert_pages`) into freshly allocated exclusive
    pages, and `ServeEngine.adopt_prefilled` arms the lane. Decode
    replicas never execute a prefill program: their serve-path compile
    budget shrinks to ONE decode program plus the trivial
    `decode.adopt_slot` arm.
  - **Occupancy-driven autoscale + replica failure**: between fleet
    windows the router compares mean slot occupancy against the
    up/down thresholds and grows (build a fresh grid on a free device
    subset — the reshard `resize@N:M` pattern: rebuild, don't mutate) or
    shrinks (drain: no new admissions, in-flight requests finish, then
    the replica retires and its devices free). A chaos-killed replica
    (`replica_kill@R[:idx]`, tpukit/chaos.py — fleet-scoped grammar) is
    dropped mid-flight: its in-flight requests re-queue onto survivors
    with the prompt reconstructed from the Request itself
    (completion-carries-prompt, round 15) and the same per-request seed,
    so each request's tokens are emitted EXACTLY once and are identical
    to the un-killed run's.

Comm story: the router is pure host-side scheduling — it adds ZERO
collectives. Each replica's decode program is the round-14 program on a
subset mesh, audited unchanged against `decode_step_comm`'s closed form
(`analysis.plan.fleet_decode_comm_plan`, the hlolint `fleet_decode`
world). Decode quanta for all replicas are DISPATCHED before any is
synced, so disjoint-subset replicas overlap on the device side; the
router's own work between dispatches is queue arithmetic.

Telemetry: replicas emit their usual `kind="serve"` windows tagged
`replica=<id>`; the router adds `kind="fleet"` windows (aggregate
tokens/s, per-replica occupancy, queue depth), `kind="fleet_event"`
(scale/kill/requeue) and one `kind="fleet_summary"` — rendered by
`tools/report.py` "== fleet ==" with the `--min_fleet_tps` CI gate.
With a shared `tracer` (round 20, tpukit/obs/trace.py) the router also
emits route/handoff/requeue span events — merged with the replicas'
admit/prefill/quantum/finish events into per-request span trees whose
fleet-wide per-phase p50/p99 and completeness land on the summary, and
which flush to `kind="trace_event"`/`kind="trace"` JSONL rows for the
`--min_trace_complete` gate and `tools/traceview.py`.
"""

from __future__ import annotations

import dataclasses
import time
from collections import deque
from pathlib import Path

import numpy as np

from tpukit import chaos as chaos_lib
from tpukit import recovery as recovery_lib
from tpukit import retry as retry_lib
from tpukit.obs import metrics as metrics_lib
from tpukit.obs import trace as trace_lib
from tpukit.serve import ledger as ledger_lib
from tpukit.serve import paged as paged_lib
from tpukit.serve.engine import (
    Completion,
    Request,
    ServeConfig,
    ServeEngine,
    trace_id,
)


def pick_serve_grid(n_devices: int, heads: int, slots: int,
                    paged: bool = False) -> dict:
    """(data x model) serving grid: the largest model degree <= 4 dividing
    both the device count and the head count (the KV ring shards heads
    over `model`; main-tp.py's rule), remaining devices data-parallel —
    shrunk to the largest divisor of the slot count, since slots shard
    over `data`. Paged serving (round 15) requires a MODEL-ONLY grid —
    the page pool is replicated across `data`, so a data axis > 1 would
    make the pool write-back an unauditable cross-shard scatter
    (serve.decode.decode_step_comm) — and therefore drops the <= 4 cap:
    `model` grows to the LARGEST head-dividing degree so devices the
    ring would have used as `data` aren't silently stranded.

    Moved here from main-serve.py in round 19: the fleet builds one grid
    PER REPLICA over that replica's device subset, so the picker is
    shared infrastructure, not recipe code."""
    if paged:
        # data is pinned to 1, so n_devices divisibility buys nothing —
        # create_mesh takes a device subset when model < n_devices; only
        # the head count constrains the degree
        for model in range(min(n_devices, heads), 0, -1):
            if heads % model == 0:
                if model < n_devices:
                    print(f"paged serving uses a model-only grid: "
                          f"model={model} of {n_devices} devices "
                          f"(model degree is capped by heads={heads})")
                return {"data": 1, "model": model}
    for model in (4, 2, 1):
        if n_devices % model == 0 and heads % model == 0:
            data = n_devices // model
            while data > 1 and slots % data:
                data -= 1
            return {"data": data, "model": model}
    return {"data": 1, "model": 1}


def place_replica_params(host_params, mesh):
    """Place ONE host copy of the params at a replica's shardings — the
    shared-cold-start half the router leans on: the checkpoint is read
    once (`checkpoint.restore_params(..., sharding_tree=None)` keeps the
    leaves on host), and every replica placement is a device_put of the
    SAME host arrays, no further I/O. Meshless replicas (mesh=None) get
    plainly-committed arrays; meshed replicas get the TensorParallel
    training shardings over their own subset mesh (the round-14 serving
    placement)."""
    import jax
    import jax.numpy as jnp

    if mesh is None:
        return jax.tree.map(lambda x: jnp.asarray(np.asarray(x)), host_params)
    from tpukit.shardings import TensorParallel

    strat = TensorParallel(mesh)
    shapes = jax.eval_shape(lambda: jax.tree.map(np.asarray, host_params))
    sharding = strat.state_sharding(shapes)
    return jax.tree.map(
        lambda x, s: jax.device_put(np.asarray(x), s), host_params, sharding
    )


@dataclasses.dataclass(frozen=True)
class FleetConfig:
    """Router shape. Replicas share ONE `ServeConfig` (the per-replica
    engine shape); the fleet adds the topology — how many engines, over
    which device subsets — and the control loops on top."""

    # Initial replica count. Each replica is a full ServeEngine with its
    # own KV cache/pool on its own device subset.
    replicas: int = 2
    # Devices per replica subset. 0 = meshless replicas (every engine on
    # the default device — the test/CPU shape, where the router logic is
    # identical and only the grids are trivial). > 0 carves
    # jax.devices() into disjoint subsets of this size; each replica's
    # grid comes from pick_serve_grid over its subset.
    devices_per_replica: int = 0
    # Autoscale bounds. max_replicas 0 = the initial count (no scale-up
    # headroom); with devices_per_replica > 0 the device list must cover
    # max_replicas subsets (validated at construction).
    min_replicas: int = 1
    max_replicas: int = 0
    # Occupancy thresholds (fraction of live-replica slot capacity holding
    # a decoding lane, mean over a fleet window). 0 disables that
    # direction. Scale-up builds a fresh grid on a free subset; scale-down
    # DRAINS the highest-id live replica (no new admissions, in-flight
    # requests finish) then retires it — never evicts work.
    scale_up_occupancy: float = 0.0
    scale_down_occupancy: float = 0.0
    # Fleet window cadence, in dispatch rounds (a round = one decode
    # quantum dispatched per live replica). Windows drive both the
    # kind="fleet" record and the autoscale check.
    window_steps: int = 16
    # Disaggregated prefill (paged only): one dedicated prefill worker
    # owns admission + chunked prefill; decode replicas only decode.
    disagg_prefill: bool = False
    prefill_slots: int = 0  # 0 = the ServeConfig's slot count
    prefill_pages: int = 0  # 0 = the ServeConfig's pool default
    # Deterministic replica failure: the fleet-scoped chaos grammar
    # (chaos.validate_fleet_spec — ONE parse/validation path with
    # --chaos_spec since round 24): replica_kill@R[:idx],
    # replica_sigkill@R[:idx], slow_replica@R:ms, stuck_request@RID,
    # ledger_io_fail@K[:c].
    kill_spec: str = ""
    # Crash-consistency plane (round 24, serve/ledger.py). fleet_dir
    # roots the durable request ledger (write-ahead leases, exactly-once
    # completion records, replay on restart) and the replica heartbeat
    # files; empty keeps the round-19 in-memory lifecycle.
    fleet_dir: str = ""
    # Liveness: a replica whose heartbeat is older than this (seconds)
    # is declared dead — leases revoked, in-flight requests requeued
    # onto survivors. 0 disables the check; > 0 requires fleet_dir (the
    # liveness plane IS the heartbeat files).
    replica_timeout: float = 0.0
    # Requeue budget per request: a request survives at most this many
    # REASSIGNMENTS after its first (jittered-backoff-spaced, the
    # retry.backoff_delay spelling); exhaustion lands it as a named
    # `request_failed` event, never an infinite kill->requeue loop.
    request_retries: int = 3
    # Backpressure: when more than this many ARRIVED requests are
    # queued, the lowest-priority (then latest) admissions shed with a
    # named `request_rejected` event instead of queueing unboundedly.
    # 0 = unbounded (the round-19 behavior).
    max_queue_depth: int = 0

    def __post_init__(self):
        if self.replicas < 1:
            raise ValueError(f"replicas={self.replicas} must be >= 1")
        if self.min_replicas < 1 or self.min_replicas > self.replicas:
            raise ValueError(
                f"min_replicas={self.min_replicas} must be in "
                f"[1, replicas={self.replicas}]"
            )
        if self.max_replicas and self.max_replicas < self.replicas:
            raise ValueError(
                f"max_replicas={self.max_replicas} must be 0 (= replicas) "
                f"or >= replicas={self.replicas}"
            )
        if self.devices_per_replica < 0:
            raise ValueError(
                f"devices_per_replica={self.devices_per_replica} must be >= 0"
            )
        for name in ("scale_up_occupancy", "scale_down_occupancy"):
            v = getattr(self, name)
            if not 0.0 <= v <= 1.0:
                raise ValueError(f"{name}={v} must be in [0, 1]")
        if (self.scale_up_occupancy and self.scale_down_occupancy
                and self.scale_down_occupancy >= self.scale_up_occupancy):
            raise ValueError(
                f"scale_down_occupancy={self.scale_down_occupancy} must be "
                f"< scale_up_occupancy={self.scale_up_occupancy} — equal or "
                f"inverted thresholds would oscillate every window"
            )
        if self.window_steps < 1:
            raise ValueError(f"window_steps={self.window_steps} must be >= 1")
        for name in ("prefill_slots", "prefill_pages"):
            if getattr(self, name) < 0:
                raise ValueError(f"{name}={getattr(self, name)} must be >= 0")
        if (self.prefill_slots or self.prefill_pages) and not self.disagg_prefill:
            raise ValueError(
                "prefill_slots/prefill_pages configure the dedicated "
                "prefill worker — set disagg_prefill=True to run one"
            )
        # the kill plan must parse at construction (chaos's fail-at-startup
        # contract) — ONE grammar/validation path with --chaos_spec
        # (round 24 retired the bespoke check this used to carry)
        chaos_lib.validate_fleet_spec(self.kill_spec)
        if self.replica_timeout < 0:
            raise ValueError(
                f"replica_timeout={self.replica_timeout} must be >= 0"
            )
        if self.replica_timeout > 0 and not self.fleet_dir:
            raise ValueError(
                "replica_timeout needs fleet_dir: liveness is declared "
                "from the heartbeat FILES replicas publish there"
            )
        if self.request_retries < 0:
            raise ValueError(
                f"request_retries={self.request_retries} must be >= 0"
            )
        if self.max_queue_depth < 0:
            raise ValueError(
                f"max_queue_depth={self.max_queue_depth} must be >= 0"
            )

    @property
    def max_count(self) -> int:
        return self.max_replicas or self.replicas


class FleetRouter:
    """The fleet: N `ServeEngine` replicas behind one request queue.

    `params_host` is ONE host-side copy of the model params (numpy leaves
    or device arrays — they are np.asarray'd once); the router places it
    per replica (`place_replica_params`), so a checkpoint is read exactly
    once however many replicas serve it. `serve` is the per-replica
    engine shape; `fleet` the topology/control config. `logger`/
    `recorder` flow into every replica (windows tagged `replica=<id>`)
    and carry the router's own fleet records."""

    def __init__(self, params_host, cfg, serve: ServeConfig,
                 fleet: FleetConfig, eos_id: int, *, devices=None,
                 logger=None, recorder=None, tracer=None, metrics=None,
                 slo=None, metrics_dir=None):
        import jax

        if serve.draft and fleet.disagg_prefill:
            # unreachable via ServeConfig (draft requires the ring, disagg
            # the pages) — kept as a named guard for direct construction
            raise ValueError("disagg_prefill and speculative decoding are "
                             "mutually exclusive (ServeConfig enforces "
                             "draft => ring cache)")
        if fleet.disagg_prefill and not serve.paged:
            raise ValueError(
                "disagg_prefill requires the paged cache (page_size > 0): "
                "the prefill->decode handoff rides page granularity — "
                "refcounted read-only pages are the transferable unit"
            )
        if fleet.devices_per_replica and cfg.num_experts > 0:
            raise ValueError(
                "fleet MoE serving uses meshless replicas this round "
                "(devices_per_replica=0): the Megatron grid rules don't "
                "cover expert banks (main-serve.py serves MoE replicated)"
            )
        self.cfg = cfg
        self.serve = serve
        self.fleet = fleet
        self.eos_id = int(eos_id)
        self.logger = logger
        self.recorder = recorder
        # ONE TraceRecorder shared by the router, every replica and the
        # prefill worker (round 20): fleet span trees need a single
        # clock and ring set that survives replica kills, so the router
        # owns it and flushes it once at fleet shutdown.
        self.tracer = tracer
        self._t0: float | None = None  # perf_counter origin of the run clock
        # ONE MetricRegistry shared the same way (round 22): every
        # replica engine observes into it replica-labeled, the router
        # accounts the fleet-level SLOs and owns the snapshot-file
        # publish/merge — per-replica files split out of the shared
        # registry by label, process-0-merges them back by bucket sum
        # (the proof harness for ROADMAP #1's cross-process metrics).
        self.metrics = metrics
        self.slo_accountant = (
            metrics_lib.SloAccountant(slo)
            if (metrics is not None and slo) else None
        )
        self.metrics_dir = metrics_dir
        self._slo_seen_rids: set = set()
        self._metrics_replicas: set = set()  # every replica id ever built
        self._params_host = params_host
        self.placements = 0
        self._placed: dict[int, object] = {}  # subset idx -> placed params

        dpr = fleet.devices_per_replica
        devices = list(devices if devices is not None else jax.devices())
        self._subsets: list = []
        if dpr:
            need = fleet.max_count * dpr
            if need > len(devices):
                raise ValueError(
                    f"max_replicas={fleet.max_count} x devices_per_replica="
                    f"{dpr} needs {need} devices, have {len(devices)}"
                )
            self._subsets = [
                devices[i * dpr: (i + 1) * dpr]
                for i in range(fleet.max_count)
            ]
            # a spare subset beyond the replica budget hosts the prefill
            # worker; otherwise the worker runs meshless
            self._worker_devices = (
                devices[need: need + dpr] if len(devices) >= need + dpr
                else None
            )
        else:
            self._subsets = [None] * fleet.max_count
            self._worker_devices = None

        # counters the fleet summary reports (initialized before the
        # replicas exist — _build_replica updates replicas_peak)
        self.requeued = 0
        self.kills = 0
        self.scale_ups = 0
        self.scale_downs = 0
        self.handoffs = 0
        self.replicas_peak = 0
        # robustness plane (round 24)
        self.rejected = 0
        self.request_failures = 0
        self.replicas_dead = 0
        self.leases_revoked = 0
        self._attempts: dict[int, int] = {}    # rid -> assignments so far
        self._not_before: dict[int, float] = {}  # rid -> backoff gate
        self._stalled_until: dict[int, float] = {}  # replica -> wall time
        self._ledger_marks: dict[int, int] = {}  # id(engine) -> published
        self._replayed = 0
        self._last_beat_t = 0.0                # heartbeat publish throttle
        self._last_live_t = 0.0                # liveness check throttle

        # the serving chaos plan (ONE grammar with --chaos_spec): kills/
        # sigkills/stalls are round-indexed, ledger I/O faults occurrence-
        # indexed through the module hook the router installs during run()
        self._chaos = chaos_lib.ServingChaos(fleet.kill_spec)

        # durable request lifecycle + liveness plane (round 24)
        self.ledger = (
            ledger_lib.RequestLedger(fleet.fleet_dir)
            if fleet.fleet_dir else None
        )
        self._hb_dir = (
            Path(fleet.fleet_dir) / "heartbeats" if fleet.fleet_dir else None
        )
        self._done: list[Completion] = []      # retired/killed replicas'
        self._gen_removed = 0                  # their generated tokens
        self._replica_stats: dict = {}         # id -> final per-replica row
        self._window_idx = 0
        self._win = dict(rounds=0, occ=0.0, tok0=0, t0=0.0, req0=0)

        self._replicas: dict[int, ServeEngine] = {}
        self._draining: set[int] = set()
        for idx in range(fleet.replicas):
            self._build_replica(idx, log=False)

        self.prefill: ServeEngine | None = None
        if fleet.disagg_prefill:
            wcfg = dataclasses.replace(
                serve,
                slots=fleet.prefill_slots or serve.slots,
                num_pages=fleet.prefill_pages or serve.num_pages,
            )
            wmesh = self._make_mesh(self._worker_devices)
            self.prefill = ServeEngine(
                self._place_for(wmesh, subset_idx=-1), cfg, wcfg,
                eos_id=self.eos_id, mesh=wmesh, logger=None, recorder=None,
                replica="prefill", tracer=self.tracer,
            )

        # kill plan (round 19; via ServingChaos since round 24): dispatch
        # round -> target ids (None = highest live). The in-process
        # router fires replica_sigkill as replica_kill — there is no
        # process to kill — and says so in the event; real SIGKILL lives
        # in ledger.ProcessFleet (--fleet_procs).
        self._kill_plan = self._chaos.kills
        self._sigkill_plan = self._chaos.sigkills
        self._stall_plan = self._chaos.stalls

    # ---- replica lifecycle ----------------------------------------------

    def _make_mesh(self, subset):
        if subset is None:
            return None
        from tpukit.mesh import create_mesh

        axes = pick_serve_grid(len(subset), self.cfg.heads, self.serve.slots,
                               paged=self.serve.paged)
        return create_mesh(axes, devices=subset)

    def _place_for(self, mesh, subset_idx: int):
        """Per-replica params placement, cached per subset: N replicas on
        one checkpoint read — placement is pure device_put of the shared
        host copy (the `ckpt_restore` ledger's bytes are paid once;
        `placements` counts the device_put passes). Meshless replicas all
        SHARE one committed copy (params are read-only), so extra
        replicas there place nothing at all."""
        key = -2 if mesh is None else subset_idx
        if key not in self._placed:
            self._placed[key] = place_replica_params(self._params_host, mesh)
            self.placements += 1
        return self._placed[key]

    def _build_replica(self, idx: int, log: bool = True) -> ServeEngine:
        mesh = self._make_mesh(self._subsets[idx])
        eng = ServeEngine(
            self._place_for(mesh, subset_idx=idx), self.cfg, self.serve,
            eos_id=self.eos_id, mesh=mesh, logger=self.logger,
            recorder=self.recorder, replica=idx, tracer=self.tracer,
            metrics=self.metrics,
        )
        eng.stuck_rids = self._chaos.stuck
        if self._t0 is not None:  # scaled up mid-run: on the fleet's clock too
            eng.begin_run(self._t0, joined_s=time.perf_counter() - self._t0)
        self._replicas[idx] = eng
        self._metrics_replicas.add(idx)
        self.replicas_peak = max(self.replicas_peak, len(self._replicas))
        if log:
            self._event("scale_up", replica=idx,
                        devices=len(self._subsets[idx] or []))
        return eng

    def _free_ids(self) -> list[int]:
        return [i for i in range(self.fleet.max_count)
                if i not in self._replicas]

    def _live(self) -> list[ServeEngine]:
        """Admission targets: live, non-draining replicas in id order (so
        max() ties resolve to the lowest id — deterministic routing)."""
        return [e for i, e in sorted(self._replicas.items())
                if i not in self._draining]

    def _event(self, event: str, **kw) -> None:
        if self.logger is not None:
            self.logger.log(kind="fleet_event", event=event, **kw)
        if self.recorder is not None:
            self.recorder.record("fleet_event", event=event, **kw)

    # ---- admission -------------------------------------------------------

    def _admit(self, pending: deque, now: float) -> None:
        """Move arrived requests onto the least-loaded target: most free
        slots, then most free pages, then lowest replica id (`_live`
        ordering + first-maximal `max`). Each engine's batch admits in ONE
        call (the round-14 bucket-grouped batched prefill); paged pool
        pressure returns leftovers, which go back to the queue head in
        arrival order. Round 24: a requeued request additionally waits
        out its jittered backoff gate (`_ready_at` — FIFO is preserved,
        the head simply isn't ready yet), and with a ledger every
        assignment is WRITTEN AHEAD of the engine seeing the request —
        a crash between lease and dispatch replays as a requeue, never a
        lost request. A leftover's assignment is returned (attempt
        un-counted); its stale lease is overwritten at the next assign,
        and replay treats any open lease as in-flight anyway
        (at-least-once assignment, exactly-once completion)."""
        targets = [self.prefill] if self.prefill is not None else self._live()
        if not targets:
            return
        total_free = sum(e.free_slots for e in targets)
        arrived: list[Request] = []
        while (pending and len(arrived) < total_free
               and self._ready_at(pending[0]) <= now):
            arrived.append(pending.popleft())
        if not arrived:
            return
        free = {id(e): e.free_slots for e in targets}
        assign: dict[int, list[Request]] = {id(e): [] for e in targets}
        for req in arrived:
            best = max(targets, key=lambda e: (free[id(e)], e.free_pages))
            assign[id(best)].append(req)
            free[id(best)] -= 1
            if self.tracer is not None:
                self.tracer.emit("route", trace_id(req), rid=req.rid,
                                 t=now, dst=best.replica, replica="router")
        leftovers: list[Request] = []
        for e in targets:
            batch = assign[id(e)]
            if not batch:
                continue
            for req in batch:
                att = self._attempts.get(req.rid, 0) + 1
                self._attempts[req.rid] = att
                if self.ledger is not None:
                    self.ledger.assign(req.rid, e.replica, att, now)
            left = e.admit(batch, now)
            for req in left:
                self._attempts[req.rid] -= 1
            leftovers.extend(left)
        for req in sorted(leftovers, key=lambda r: r.rid, reverse=True):
            pending.appendleft(req)

    def _ready_at(self, req: Request) -> float:
        """When a queued request may admit: its arrival, or its post-
        requeue backoff gate, whichever is later."""
        return max(req.arrival_s, self._not_before.get(req.rid, 0.0))

    def _shed(self, pending: deque, now: float) -> None:
        """Queue-depth backpressure: when more than `max_queue_depth`
        ARRIVED requests are waiting, shed the excess — lowest priority
        first, then latest arrival (highest rid) — each as a NAMED
        `request_rejected` event (and a terminal ledger record, so a
        replayed stream doesn't resurrect it). Shedding at admission
        time, not arrival time, means a queue that drains fast enough
        never rejects."""
        depth = self.fleet.max_queue_depth
        if not depth or len(pending) <= depth:
            return
        arrived = [r for r in pending if r.arrival_s <= now]
        if len(arrived) <= depth:
            return
        shed = sorted(arrived, key=lambda r: (r.priority, -r.rid))
        shed = shed[: len(arrived) - depth]
        drop = {r.rid for r in shed}
        kept = [r for r in pending if r.rid not in drop]
        pending.clear()
        pending.extend(kept)
        for req in sorted(shed, key=lambda r: r.rid):
            self.rejected += 1
            if self.metrics is not None:
                self.metrics.inc("fleet_rejected")
            if self.ledger is not None:
                self.ledger.record_failure(req.rid, "backpressure",
                                           self._attempts.get(req.rid, 0))
            self._event("request_rejected", rid=req.rid,
                        priority=req.priority, reason="backpressure",
                        queue_depth=len(arrived))

    # ---- disaggregated prefill handoff ----------------------------------

    def _handoffs(self, now: float) -> None:
        """Move every prefill-complete worker lane to a decode replica
        with capacity (least-loaded, same rule as admission). A lane with
        no destination WAITS on the worker, holding its pages, until a
        replica frees capacity — prefill work is never discarded."""
        worker = self.prefill
        ready = sorted(
            ((slot, lane) for slot, lane in worker._lanes.items()
             if lane.phase == "decode"),
            key=lambda sl: sl[1].req.rid,
        )
        for slot, lane in ready:
            cands = [e for e in self._live() if e.free_slots > 0]
            if not cands:
                break
            dst = max(cands, key=lambda e: (e.free_slots, e.free_pages))
            if self._adopt(worker, slot, lane, dst, now):
                self.handoffs += 1

    def _adopt(self, worker: ServeEngine, slot: int, lane, dst: ServeEngine,
               now: float) -> bool:
        """One handoff: claim the destination's already-registered prefix
        pages (refcounted — a claimed page can never be reclaimed under
        this reader, however hard the pool is pressed), copy the remaining
        WRITTEN pages device-to-device, and arm the decode lane. Returns
        False (nothing mutated) when the destination pool cannot cover
        the footprint."""
        req, plen = lane.req, lane.prompt_len
        tr = self.tracer
        h0 = tr.now() if tr is not None else 0.0
        p = self.serve.page_size
        written = -(-lane.prefill_end // p)  # pages holding computed K/V
        matched = dst.allocator.lookup_prefix(req.ids, (plen - 1) // p)
        dst.allocator.claim(matched)
        limit = min(plen + req.max_new_tokens, self.serve.width)
        fresh = dst.allocator.alloc(-(-limit // p) - len(matched))
        if fresh is None:
            dst.allocator.release(matched)
            return False
        pages = list(matched) + fresh
        c0 = tr.now() if tr is not None else 0.0
        _copy_pages(worker, dst,
                    lane.pages[len(matched):written],
                    fresh[: written - len(matched)])
        c1 = tr.now() if tr is not None else 0.0
        dst.adopt_prefilled(req, pages, len(matched), lane.admit_s, now,
                            lane.key)
        worker.release_lane(slot)
        if tr is not None:
            tr.emit("handoff", trace_id(req), rid=req.rid, t0=h0,
                    t1=tr.now(), claim_s=c0 - h0, copy_s=c1 - c0,
                    pages=written - len(matched), dst=dst.replica,
                    replica="router")
        return True

    # ---- failure + autoscale --------------------------------------------

    def _maybe_kill(self, rounds: int, now: float) -> None:
        for plan, extra in (
            (self._kill_plan, {}),
            # in-process: a sigkill entry degrades to the simulated kill
            # (there is no process to kill) and SAYS so — real SIGKILL
            # is ledger.ProcessFleet's job (--fleet_procs)
            (self._sigkill_plan, {"signal": "SIGKILL", "simulated": True}),
        ):
            for target in plan.pop(rounds, ()):
                live = sorted(i for i in self._replicas)
                if len(live) <= 1:
                    self._event("kill_skipped", round=rounds,
                                reason="last live replica")
                    continue
                idx = target if target in self._replicas else live[-1]
                self._kill(idx, rounds, now, **extra)

    def _fire_stalls(self, rounds: int) -> None:
        """slow_replica@R:ms — stall the target's HEARTBEAT for ms of
        wall clock without touching the engine: the straggler case the
        liveness check must NOT confuse with death (unless the stall
        outlives replica_timeout, in which case declaring it dead is the
        correct call and the requeue path owns the request)."""
        for stall_s in self._stall_plan.pop(rounds, ()):
            live = sorted(self._replicas)
            if not live:
                continue
            idx = live[-1]
            until = time.time() + stall_s
            self._stalled_until[idx] = max(
                self._stalled_until.get(idx, 0.0), until
            )
            self._chaos.record(dict(fault="slow_replica", round=rounds,
                                    replica=idx, stall_s=stall_s))
            self._event("replica_slow", replica=idx, round=rounds,
                        stall_s=stall_s)

    def _beat(self, rounds: int) -> None:
        """Publish each live replica's heartbeat file (recovery.py's
        one-atomic-file-per-publisher discipline, retry-wrapped like any
        other fleet file I/O). A chaos-stalled replica skips its beat —
        that IS the fault."""
        if self._hb_dir is None:
            return
        wall = time.time()
        # throttle: the loop spins far faster than liveness needs — one
        # beat per ~10 ms keeps heartbeat age resolution well under any
        # sane replica_timeout without an fsync storm
        if wall - self._last_beat_t < 0.01:
            return
        self._last_beat_t = wall
        for idx, eng in sorted(self._replicas.items()):
            if self._stalled_until.get(idx, 0.0) > wall:
                continue
            retry_lib.retry_io(
                recovery_lib.publish_heartbeat, self._hb_dir,
                f"replica-{idx:05d}",
                dict(replica=idx, t=wall, round=rounds,
                     generated=eng.generated_tokens, lanes=eng.live_lanes),
                label="heartbeat",
            )

    def _check_liveness(self, rounds: int, now: float) -> None:
        """Declare heartbeat-silent replicas dead: beat age over
        `replica_timeout` revokes the replica's leases and requeues its
        in-flight requests onto survivors — the round-19 kill path,
        driven by the liveness plane instead of a scripted round."""
        f = self.fleet
        if f.replica_timeout <= 0 or self._hb_dir is None:
            return
        wall = time.time()
        # check at ~4x the timeout's resolution, not every loop spin
        if wall - self._last_live_t < min(f.replica_timeout / 4.0, 0.01):
            return
        self._last_live_t = wall
        beats = recovery_lib.read_heartbeat_dir(self._hb_dir, "replica-")
        for idx in sorted(self._replicas):
            rec = beats.get(f"replica-{idx:05d}")
            if rec is None:
                continue  # not yet published — born this round
            age = wall - float(rec["t"])
            if age <= f.replica_timeout:
                continue
            if len(self._replicas) <= 1:
                self._event("kill_skipped", round=rounds,
                            reason="last live replica")
                continue
            self.replicas_dead += 1
            if self.metrics is not None:
                self.metrics.inc("fleet_replica_dead")
            self._kill(idx, rounds, now, event="replica_dead",
                       reason="heartbeat_timeout", age_s=round(age, 3))

    def _kill(self, idx: int, rounds: int, now: float,
              event: str = "replica_kill", **extra) -> None:
        """Drop replica `idx` mid-flight — the chaos failure model: the
        engine (device state and all) is discarded, its COMPLETED requests
        keep their already-emitted tokens, and its in-flight requests
        re-queue at the queue head with prompt+seed reconstructed from
        the Request (exactly-once output per request: partial tokens were
        never emitted as completions). Round 24 rides liveness deaths
        (`event="replica_dead"`) through the same path, adds the
        `request_retries` budget with jittered-backoff re-admission, and
        publishes the killed engine's completion records to the ledger
        BEFORE the engine is discarded."""
        eng = self._replicas.pop(idx)
        self._draining.discard(idx)
        self._ledger_collect(eng)
        victims = eng.requeue_live()
        self._done.extend(eng.completions)
        # fold the victim's FULL generated count (completed + in-flight
        # partial) into the removed-token tally: the fleet really did
        # generate those partial tokens before discarding them, and the
        # window counter (_fleet_gen - tok0) must stay monotone — folding
        # only the completed tokens would make the post-kill window report
        # NEGATIVE new_tokens. Survivors re-generating the requeued work
        # counts again, honestly: it is work done twice.
        self._gen_removed += eng.generated_tokens
        self._replica_stats[idx] = dict(
            completions=len(eng.completions),
            tokens=sum(c.generated for c in eng.completions),
            occupancy=None, fate="killed" if event == "replica_kill"
            else "dead",
        )
        self.kills += 1
        self.leases_revoked += len(victims)
        kept = self._requeue(victims, idx, now)
        if self.tracer is not None:
            # the requeue event links the killed attempt and the retry
            # under ONE trace id — the same Request object re-queues, so
            # the retry's admit/finish land on the same tree
            for req in kept:
                self.tracer.emit("requeue", trace_id(req), rid=req.rid,
                                 t=now, from_replica=idx, replica="router")
        self._event(event, replica=idx, round=rounds,
                    requeued=len(kept),
                    requeued_rids=[r.rid for r in kept], **extra)
        if self.logger is not None and self.ledger is not None and kept:
            # the durable lease-revocation record: a restarted router can
            # see WHICH leases each death invalidated
            self.logger.log(kind="lease_requeue", from_replica=idx,
                            rids=[r.rid for r in kept],
                            attempts={str(r.rid): self._attempts.get(r.rid, 1)
                                      for r in kept})

    def _requeue(self, victims: list[Request], idx: int,
                 now: float) -> list[Request]:
        """Requeue a dead replica's in-flight requests at the queue head,
        each gated behind a jittered backoff (`retry.backoff_delay` — the
        survivors must not absorb the whole blast in lockstep) and the
        per-request `request_retries` budget: exhaustion is a terminal,
        NAMED failure, not a silent kill->requeue loop."""
        kept: list[Request] = []
        for req in victims:
            n = self._attempts.get(req.rid, 1)
            if n > self.fleet.request_retries:
                self.request_failures += 1
                self._event("request_failed", rid=req.rid, attempts=n,
                            reason="retry_budget")
                if self.metrics is not None:
                    self.metrics.inc("fleet_request_failed")
                if self.ledger is not None:
                    self.ledger.record_failure(req.rid, "retry_budget", n)
                continue
            self._not_before[req.rid] = now + retry_lib.backoff_delay(n)
            kept.append(req)
        self.requeued += len(kept)
        if self.metrics is not None and kept:
            self.metrics.inc("fleet_requeued", len(kept))
        for req in reversed(kept):
            self._pending.appendleft(req)
        return kept

    def _autoscale(self, mean_occ: float, queue_depth: int) -> None:
        f = self.fleet
        live = [i for i in self._replicas if i not in self._draining]
        if (f.scale_up_occupancy and mean_occ >= f.scale_up_occupancy
                and len(live) < f.max_count and self._free_ids()):
            self._build_replica(min(self._free_ids()))
            self.scale_ups += 1
        elif (f.scale_down_occupancy and mean_occ <= f.scale_down_occupancy
                and len(live) > f.min_replicas and queue_depth == 0):
            victim = max(live)
            self._draining.add(victim)
            self.scale_downs += 1
            self._event("scale_down", replica=victim,
                        draining_lanes=self._replicas[victim].live_lanes)

    def _retire_drained(self, now: float) -> None:
        for idx in sorted(self._draining):
            eng = self._replicas[idx]
            if eng.live_lanes:
                continue
            self._retire(idx, eng, now, fate="drained")
            self._event("scale_down_complete", replica=idx)

    def _retire(self, idx: int, eng: ServeEngine, wall: float,
                fate: str) -> None:
        comps = eng.finish(wall)
        self._ledger_collect(eng)
        self._done.extend(comps)
        self._gen_removed += sum(c.generated for c in comps)
        s = eng.last_summary or {}
        self._replica_stats[idx] = dict(
            completions=len(comps),
            tokens=sum(c.generated for c in comps),
            occupancy=s.get("mean_occupancy"), fate=fate,
        )
        del self._replicas[idx]
        self._draining.discard(idx)

    # ---- telemetry -------------------------------------------------------

    def _ledger_collect(self, eng: ServeEngine) -> None:
        """Publish an engine's NEW completions to the durable ledger —
        called after every sync round and before any engine is discarded
        (kill, liveness death, retire), so a crash never loses a finished
        request. The per-engine mark makes this incremental; the ledger's
        check-then-publish makes it exactly-once even when a killed
        replica's work re-completes on a survivor."""
        if self.ledger is None:
            return
        key = id(eng)
        mark = self._ledger_marks.get(key, 0)
        comps = eng.completions
        for c in comps[mark:]:
            self.ledger.complete(c, replica=eng.replica,
                                 attempt=self._attempts.get(c.rid, 1))
        self._ledger_marks[key] = len(comps)

    def _fleet_gen(self) -> int:
        return self._gen_removed + sum(
            e.generated_tokens for e in self._replicas.values()
        )

    def _emit_window(self, now: float, queue_depth: int) -> float:
        """Emit the kind="fleet" window; returns the window's mean
        occupancy (the autoscale signal)."""
        w = self._win
        occ = w["occ"] / max(w["rounds"], 1)
        tok = self._fleet_gen() - w["tok0"]
        wall = now - w["t0"]
        per_replica = {
            str(i): e.generated_tokens
            for i, e in sorted(self._replicas.items())
        }
        rec = dict(
            kind="fleet", window=self._window_idx, rounds=w["rounds"],
            replicas=sorted(self._replicas), draining=sorted(self._draining),
            new_tokens=tok,
            tokens_per_sec=(tok / wall) if wall > 0 else None,
            occupancy=occ, queue_depth=queue_depth,
            requeued=self.requeued - w["req0"],
            per_replica_tokens=per_replica, window_s=wall,
        )
        if self.prefill is not None:
            rec["prefill_lanes"] = self.prefill.live_lanes
            rec["handoffs"] = self.handoffs
        if self.logger is not None:
            self.logger.log(**rec)
        if self.recorder is not None:
            self.recorder.record(
                "fleet", window=self._window_idx, new_tokens=tok,
                occupancy=occ, replicas=len(self._replicas),
            )
        if self.metrics is not None:
            self._metrics_window(rec)
        self._window_idx += 1
        self._win = dict(rounds=0, occ=0.0, tok0=self._fleet_gen(), t0=now,
                         req0=self.requeued)
        return occ

    def _metrics_window(self, rec: dict) -> None:
        """Fleet-level metrics + SLO accounting for one window, derived
        from data the loop already produced (the replica engines observe
        their own per-completion histograms replica-labeled into the
        SAME shared registry)."""
        m = self.metrics
        if rec.get("tokens_per_sec") is not None:
            m.gauge("fleet_tokens_per_sec", rec["tokens_per_sec"])
        m.gauge("fleet_occupancy", rec["occupancy"])
        m.gauge("fleet_queue_depth", rec["queue_depth"])
        m.gauge("fleet_replicas", len(self._replicas))
        if self.slo_accountant is not None:
            # fleet-wide SLO samples: every completion not yet
            # accounted, wherever it lives (live engines or the retired
            # ledger) — exactly-once by rid, the _done dedup invariant
            fresh: list[Completion] = []
            pools = [e.completions for e in self._replicas.values()]
            pools.append(self._done)
            for pool in pools:
                for c in pool:
                    if c.rid not in self._slo_seen_rids:
                        self._slo_seen_rids.add(c.rid)
                        fresh.append(c)
            samples = {
                "e2e": [c.e2e_s for c in fresh],
                "ttft": [max(c.active_s - c.arrival_s, 0.0) for c in fresh],
                "queue_wait": [max(c.admit_s - c.arrival_s, 0.0)
                               for c in fresh],
                "tpot": [c.per_token_s for c in fresh],
            }
            slo_rec = dict(kind="slo", window=self._window_idx,
                           **self.slo_accountant.evaluate(samples))
            if self.logger is not None:
                self.logger.log(**slo_rec)
            if self.recorder is not None:
                self.recorder.record(
                    "slo", window=self._window_idx,
                    overall_compliance=slo_rec["overall_compliance"],
                )
        if self.metrics_dir:
            self._publish_metrics()

    def _publish_metrics(self) -> None:
        """Per-replica snapshot files split from the shared registry by
        label (heartbeat-file discipline: one atomic file per publisher)
        plus the router's process-0 merge beside them. Every touch of the
        shared filesystem rides `retry_io` (round 24) — a transient NFS
        error in a metrics publish must not kill a serving fleet, and
        each failed attempt surfaces as a `kind="retry"` record."""
        wall = time.time()
        count = self.fleet.max_count
        for idx in sorted(self._metrics_replicas):
            retry_lib.retry_io(
                metrics_lib.publish_snapshot, self.metrics_dir, idx,
                self.metrics.filter(replica=idx),
                process_count=count, time_s=wall,
                label="metrics_snapshot",
            )
        merged, meta = retry_lib.retry_io(
            metrics_lib.merge_snapshot_dir, self.metrics_dir,
            process_count=count, label="metrics_merge",
        )
        retry_lib.retry_io(metrics_lib.write_merged, self.metrics_dir,
                           merged, meta=meta, label="metrics_merge")

    def summary(self, wall_s: float) -> dict:
        comps = self._done
        rids = [c.rid for c in comps]
        e2e = sorted(c.e2e_s for c in comps)
        pct = lambda q: (  # noqa: E731
            float(np.percentile(np.asarray(e2e), q)) if e2e else None
        )
        occs = [r["occupancy"] for r in self._replica_stats.values()
                if r.get("occupancy") is not None]
        rec = dict(
            kind="fleet_summary", requests=len(comps),
            generated_tokens=sum(c.generated for c in comps),
            wall_s=wall_s,
            tokens_per_sec=(sum(c.generated for c in comps) / wall_s)
            if wall_s else None,
            replicas_final=len(self._replicas) or sum(
                1 for r in self._replica_stats.values()
                if r["fate"] == "final"
            ),
            replicas_peak=self.replicas_peak,
            scale_ups=self.scale_ups, scale_downs=self.scale_downs,
            kills=self.kills, requeued=self.requeued,
            rejected=self.rejected,
            request_failures=self.request_failures,
            replicas_dead=self.replicas_dead,
            leases_revoked=self.leases_revoked,
            deadline_misses=sum(1 for c in comps if c.reason == "deadline"),
            # the exactly-once invariant, as data: a rid appearing twice
            # means a killed replica's partial work double-emitted
            duplicate_completions=len(rids) - len(set(rids)),
            p50_e2e_s=pct(50), p99_e2e_s=pct(99),
            per_replica=self._replica_stats,
            occupancy_spread=(max(occs) - min(occs)) if len(occs) > 1 else 0.0,
            params_placements=self.placements,
        )
        if self.fleet.disagg_prefill:
            st = self.prefill.allocator.stats
            rec["disagg_prefill"] = dict(
                handoffs=self.handoffs,
                worker_admitted=self.prefill.admitted,
                worker_prefix_hits=st.prefix_hits,
                worker_pages_reused=st.prefix_pages_reused,
            )
        if self.tracer is not None:
            # fleet-wide per-phase latency view over every completed
            # request's span tree (killed-replica work included — the
            # shared tracer outlives its emitters)
            done_rids = {c.rid for c in comps}
            trees = [t for t in trace_lib.build_trees(self.tracer.snapshot())
                     if t["rid"] in done_rids]
            rec["phase_p50"], rec["phase_p99"] = trace_lib.phase_stats(trees)
            rec["trace_complete"] = trace_lib.completeness(trees)
            # per-ring evictions (round 22): a saturated ring silently
            # reads as a complete history otherwise — report.py warns
            # when nonzero
            by_rep = self.tracer.dropped_by_replica
            rec["trace_dropped"] = sum(by_rep.values())
            rec["trace_dropped_by_replica"] = {
                str(k): v for k, v in sorted(by_rep.items(), key=str)
            }
        if self.slo_accountant is not None:
            rec["slo_overall_compliance"] = (
                self.slo_accountant.overall_compliance()
            )
        if self.ledger is not None:
            rec["ledger"] = dict(
                completed=len(self.ledger.completions()),
                replayed=self._replayed,
                duplicates=self.ledger.duplicates(),
            )
        return rec

    # ---- the loop --------------------------------------------------------

    def run(self, requests, max_wall_s: float | None = None) -> list[Completion]:
        """Serve `requests` across the fleet to completion; returns ALL
        completions in finish order. The loop per iteration: fire any
        scheduled kill/stall, check heartbeat liveness, publish beats,
        shed over-depth queue, admit ready requests least-loaded, advance
        prefill (worker chunks + handoffs, or per-replica chunks),
        DISPATCH every replica's decode quantum (async — disjoint subsets
        overlap), then sync each, publish fresh completions to the ledger,
        and retire finished lanes. Fleet windows and the autoscale check
        run every `FleetConfig.window_steps` dispatch rounds. With a
        `fleet_dir`, the request stream is durable: a restarted router
        passed the same stream replays the ledger and serves only the
        not-yet-completed frontier."""
        if self.ledger is not None:
            requests, done_recs = self.ledger.open_stream(requests)
            self._replayed = len(done_recs)
            if self._replayed:
                self._event("ledger_replay", completed=self._replayed,
                            remaining=len(requests))
        self._pending = deque(
            sorted(requests, key=lambda r: (r.arrival_s, r.rid))
        )
        pending = self._pending
        # the serving chaos engine is process-global for the run so the
        # ledger's raw I/O helpers reach it through the same
        # chaos.maybe_io_fault hook the checkpoint sites use
        prev_chaos = chaos_lib.installed()
        chaos_lib.install(self._chaos)
        try:
            return self._run_loop(pending, max_wall_s)
        finally:
            chaos_lib.install(prev_chaos)

    def _run_loop(self, pending: deque,
                  max_wall_s: float | None) -> list[Completion]:
        # start every engine's clock and accounts at the FLEET run start so
        # the construction->run gap lands nowhere (the engine.run discipline)
        t0 = self._t0 = time.perf_counter()
        for eng in self._replicas.values():
            eng.begin_run(t0)
        if self.prefill is not None:
            self.prefill.begin_run(t0)
        if self.tracer is not None:
            self.tracer.set_epoch(t0)
            for r in pending:
                self.tracer.emit("enqueue", trace_id(r), rid=r.rid,
                                 t=r.arrival_s, replica="router")
        self._win["t0"] = 0.0
        rounds = 0
        while pending or self._any_lanes():
            now = time.perf_counter() - t0
            if max_wall_s is not None and now > max_wall_s:
                raise TimeoutError(
                    f"fleet run exceeded max_wall_s={max_wall_s} with "
                    f"{len(pending)} pending and "
                    f"{sum(e.live_lanes for e in self._replicas.values())} "
                    f"live lanes"
                )
            self._maybe_kill(rounds, now)
            self._fire_stalls(rounds)
            self._check_liveness(rounds, now)
            self._beat(rounds)
            self._shed(pending, now)
            self._admit(pending, now)
            if self.prefill is not None:
                self.prefill.poll_prefill(time.perf_counter() - t0)
                self._handoffs(time.perf_counter() - t0)
            else:
                for eng in list(self._replicas.values()):
                    eng.poll_prefill(time.perf_counter() - t0)
            # dispatch ALL replicas' quanta before syncing any: the
            # dispatches are async, so disjoint device subsets decode
            # concurrently while the host walks the list
            dispatched = [e for e in self._replicas.values()
                          if e.dispatch_decode()]
            if not dispatched:
                if not self._any_lanes() and pending:
                    wait = self._ready_at(pending[0]) - now
                    if wait > 0:
                        time.sleep(min(wait, 0.05))
                continue
            rounds += 1
            # occupancy is sampled at DISPATCH time (lanes occupied during
            # the quantum just issued) — post-sync, a lane that finished
            # mid-quantum would read as idle and a saturated replica as
            # half-busy, starving the scale-up signal
            slots = sum(e.serve.slots for e in self._replicas.values())
            decoding = sum(e.decoding_lanes for e in self._replicas.values())
            snow = time.perf_counter() - t0
            for eng in dispatched:
                eng.sync(snow)
                self._ledger_collect(eng)
            self._win["rounds"] += 1
            self._win["occ"] += decoding / max(slots, 1)
            if self._win["rounds"] >= self.fleet.window_steps:
                occ = self._emit_window(snow, len(pending))
                self._autoscale(occ, len(pending))
            self._retire_drained(time.perf_counter() - t0)
        wall = time.perf_counter() - t0
        if self._win["rounds"]:
            self._emit_window(wall, 0)
        for idx, eng in sorted(self._replicas.items()):
            self._retire(idx, eng, wall, fate="final")
        if self.logger is not None:
            for ev in self._chaos.drain_fired():
                self.logger.log(kind="chaos", **ev)
        rec = self.last_summary = self.summary(wall)
        if self.logger is not None:
            self.logger.log(**rec)
        if self.recorder is not None:
            self.recorder.record(
                "fleet_summary", requests=rec["requests"],
                tokens_per_sec=rec["tokens_per_sec"],
                requeued=rec["requeued"], kills=rec["kills"],
            )
        if self.tracer is not None:
            # one flush for the whole fleet: events + span trees into the
            # JSONL (replica engines share this tracer and skip their own
            # flush — see ServeEngine.finish)
            trace_lib.flush_to_logger(
                self.tracer, self.logger,
                trace_lib.build_trees(self.tracer.snapshot()),
            )
        if self.metrics is not None:
            # one metrics epilogue for the whole fleet (replica engines
            # share this registry and skip their own — ServeEngine.finish
            # only emits when replica is None): the kind="metrics"
            # summary row plus the final snapshot publish/merge
            rec_m = dict(kind="metrics", source="fleet",
                         **self.metrics.summary())
            if self.logger is not None:
                self.logger.log(**rec_m)
            if self.recorder is not None:
                self.recorder.record(
                    "metrics", source="fleet",
                    hists=len(rec_m["hists"]),
                    tokens=self.metrics.sum_counter("serve_tokens"),
                )
            if self.metrics_dir:
                self._publish_metrics()
        self._done.sort(key=lambda c: c.done_s)
        return self._done

    def _any_lanes(self) -> bool:
        if any(e.live_lanes for e in self._replicas.values()):
            return True
        return self.prefill is not None and self.prefill.live_lanes > 0


def _copy_pages(src: ServeEngine, dst: ServeEngine, src_ids, dst_ids) -> None:
    """The device-to-device page copy of the disaggregated handoff, spelled
    ONCE: gather the source pool's page rows (`paged.extract_pages`), move
    the block across device subsets with one `jax.device_put` at the
    destination pool's layout, scatter into the destination pool
    (`paged.insert_pages`). Covers K/V pools and (int8) scale sidecars
    alike. Ids pad to the next power of two so the traced-id programs
    compile log-many times: source pads by REPEATING the last id
    (re-extracting a page is idempotent), destination pads with 0 — the
    null page, whose contents are garbage by design (write-safety
    invariant 2)."""
    import jax
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if not src_ids:
        return
    n = 1 << (len(src_ids) - 1).bit_length()
    s = np.asarray(list(src_ids) + [src_ids[-1]] * (n - len(src_ids)),
                   np.int32)
    d = np.asarray(list(dst_ids) + [0] * (n - len(dst_ids)), np.int32)
    for key, spec in (("k", dst._pool_spec), ("v", dst._pool_spec),
                      ("ks", dst._scale_spec), ("vs", dst._scale_spec)):
        if key not in src.cache:
            continue
        block = paged_lib.extract_pages(src.cache[key], src._place(s, P()))
        if dst.mesh is not None:
            block = jax.device_put(block, NamedSharding(dst.mesh, spec))
        else:
            block = jax.device_put(block)
        dst.cache[key] = paged_lib.insert_pages(
            dst.cache[key], dst._place(d, P()), block
        )

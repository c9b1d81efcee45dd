"""CommPlan — the declared collective schedule of one compiled program.

Rounds 10–15 accumulated four closed-form comm predictions, each with its
own shape and its own comparison loop: `Strategy.grad_comm` (quantized
DDP/FSDP grad wire), `ExpertParallel.dispatch_comm` (the MoE a2a
exchange), `serve.decode.decode_step_comm` (the TP decode step) and
`moe_dispatch.expected_a2a` under them. The dryrun, fit()'s xla record
and four test files each re-spelled "fetch the expectation,
index the measured dict, compare count and bytes". A CommPlan is that
expectation normalized once: {op: {count, bytes}} plus, where the
formula knows it, the wire element dtype each op's payload must travel
at — so the rule engine (analysis/rules.py) diffs EVERY audited program
the same way and `wire-upcast` has a declared dtype to check against.

`exhaustive=True` means the plan IS the program's whole collective set
(the decode audit: measured == expected, nothing else tolerated);
False means the plan covers only the hand-placed ops and GSPMD's own
scalar psums etc. ride alongside unchecked.
"""

from __future__ import annotations

from dataclasses import dataclass, field


@dataclass
class CommPlan:
    """Declared per-step collective expectation for one compiled program."""

    label: str
    # op kind -> {"count": int, "bytes": int} (result-payload convention,
    # the numbers obs.xla.collective_bytes reports)
    ops: dict[str, dict] = field(default_factory=dict)
    # op kind -> HLO element type its payload must travel at ("s8", "f32",
    # "bf16"); only ops whose formula fixes the dtype appear here.
    # SCOPE: a wire entry asserts that EVERY collective of that op kind in
    # the program travels at (or under) the declared dtype — declare a
    # kind here only when the plan owns all of its instances (true for
    # the quantized DDP/FSDP/EP programs today: comm-plan's exact count
    # check would flag a surplus same-kind collective anyway, and the
    # wire rule then names the dtype drift rather than leaving it inside
    # an opaque byte mismatch).
    wire: dict[str, str] = field(default_factory=dict)
    # True: measured collectives must equal `ops` exactly, surplus kinds
    # are violations (the decode audit). False: only the kinds in `ops`
    # are checked (train worlds, where GSPMD's loss/count psums coexist).
    exhaustive: bool = False
    # nominal comm dtype the run declared (--comm_dtype), for reporting
    comm_dtype: str = "f32"
    # Overlap declaration (round 18, --grad_buckets): {op: K} — at least
    # K collectives of that kind must each have independent compute the
    # scheduler can hide them behind (Strategy.overlap_comm). None = the
    # serial schedule; the hlolint `overlap` rule stays reporting-only.
    # With a declaration the rule GATES (severity error on shortfall) —
    # a world that claims bucketed overlap must show the structure.
    overlap: dict | None = None

    def expected(self, op: str) -> dict:
        return self.ops.get(op, {"count": 0, "bytes": 0})


def _wire_dtype_of(comm_dtype: str) -> str:
    return {"int8": "s8", "bf16": "bf16"}.get(comm_dtype, "f32")


# expected_a2a's wire marker speaks numpy dtype names for compute dtypes
# ("float32") and its own tag for packed payloads; HLO speaks "f32"/"s8".
_WIRE_TO_HLO = {
    "s8-packed": "s8", "int8": "s8",
    "float32": "f32", "f32": "f32",
    "bfloat16": "bf16", "bf16": "bf16",
    "float16": "f16", "f16": "f16",
    "float64": "f64", "f64": "f64",
}


def train_comm_plan(strategy, cfg, *, param_shapes=None, global_batch=None,
                    seq=None, backend=None, phase="train") -> CommPlan | None:
    """The unified train-step plan for a strategy+config: grad_comm
    (quantized DDP/FSDP) and dispatch_comm (EP a2a/pallas) folded into one
    CommPlan, or None when the strategy hand-places nothing (plain GSPMD
    worlds are measured, not predicted).

    `param_shapes` feeds grad_comm; `global_batch`/`seq` feed
    dispatch_comm — pass what the strategy needs, the other pair may stay
    None. `phase="eval"` builds the forward-only plan (no grad wire, the
    dispatch's eval entry). Byte expectations are backend-aware exactly as
    the underlying formulas are (XLA:CPU's bf16->f32 wire upcast is priced
    in, int8 is upcast-immune)."""
    comm = getattr(cfg, "comm_dtype", "f32")
    ops: dict[str, dict] = {}
    wire: dict[str, str] = {}

    grad_fn = getattr(strategy, "grad_comm", None) if phase == "train" else None
    if grad_fn is not None and param_shapes is not None:
        gexp = grad_fn(cfg, param_shapes, backend=backend)
        if gexp:
            for op, rec in gexp.items():
                ops[op] = {"count": rec["count"], "bytes": rec["bytes"]}
            wdt = _wire_dtype_of(comm)
            if "all-to-all" in gexp:
                wire["all-to-all"] = wdt
            if "all-gather" in gexp:
                # DDP's two-shot gathers the PACKED payload; FSDP's forward
                # param gathers stay full precision by design
                wire["all-gather"] = (
                    wdt if strategy.name == "ddp" else "f32"
                )

    disp_fn = getattr(strategy, "dispatch_comm", None)
    if disp_fn is not None and global_batch is not None and seq is not None:
        dexp = disp_fn(cfg, global_batch=global_batch, seq=seq,
                       backend=backend)
        if dexp:
            train = dexp.get(phase, {"count": 0, "bytes": 0})
            if train.get("count"):
                rec = ops.setdefault("all-to-all", {"count": 0, "bytes": 0})
                rec["count"] += train["count"]
                rec["bytes"] += train["bytes"]
                wname = train.get("wire")
                if wname:
                    # expected_a2a's wire marker names the dtype the payload
                    # actually travels at on this backend
                    wire["all-to-all"] = _WIRE_TO_HLO.get(wname, wname)
                elif comm != "f32":
                    wire["all-to-all"] = _wire_dtype_of(comm)

    pipe_fn = getattr(strategy, "pipe_comm", None)
    if pipe_fn is not None and global_batch is not None and seq is not None:
        # Interleaved pipeline schedules (round 22): the unrolled tick
        # machine's shipping ticks are static, so the strategy states the
        # exact collective-permute count/bytes of the compiled step; MoE
        # worlds also pin all-to-all to ZERO (the pallas dispatch is
        # collective-free — a surplus a2a means the buffer dataflow leaked
        # in). None for the flat V=1 scan, whose hops live inside one scan
        # body instruction.
        pexp = pipe_fn(cfg, global_batch=global_batch, seq=seq, phase=phase)
        if pexp:
            for op, rec in pexp.items():
                dst = ops.setdefault(op, {"count": 0, "bytes": 0})
                dst["count"] += rec["count"]
                dst["bytes"] += rec["bytes"]

    if not ops:
        return None
    # --grad_buckets overlap declaration (train phase only — eval has no
    # backward, hence no grad wire to overlap): the strategy names how
    # many of each op kind must be independently schedulable; the rule
    # engine's `overlap` gate measures the compiled module against it.
    overlap = None
    overlap_fn = getattr(strategy, "overlap_comm", None)
    if phase == "train" and overlap_fn is not None:
        overlap = overlap_fn(cfg, param_shapes)
    return CommPlan(
        label=f"{strategy.name} {phase} step",
        ops=ops, wire=wire, exhaustive=False, comm_dtype=comm,
        overlap=overlap,
    )


def decode_comm_plan(cfg, mesh, slots: int, top_k: int = 0,
                     paged: bool = False, verify_tokens: int = 1) -> CommPlan:
    """The serving decode-step plan: `decode_step_comm`'s closed form as
    an EXHAUSTIVE CommPlan — the compiled step must move these collectives
    and nothing else (the round-14/15 audit bar, unchanged).
    `verify_tokens = spec_k + 1` prices the SPECULATIVE verify step
    instead (round 17, `serve/spec.verify_step`): same collective counts,
    every byte term widened by the verify window — the hlolint
    `spec_verify` world audits it."""
    from tpukit.serve.decode import decode_step_comm

    expected = decode_step_comm(cfg, mesh, slots, top_k=top_k, paged=paged,
                                verify_tokens=verify_tokens)
    label = ("spec verify step" if verify_tokens > 1
             else f"decode step [{'paged' if paged else 'ring'}]")
    return CommPlan(
        label=label,
        ops={op: dict(rec) for op, rec in expected.items()},
        wire={},
        exhaustive=True,
        comm_dtype=getattr(cfg, "comm_dtype", "f32"),
    )


def fleet_decode_comm_plan(cfg, mesh, slots: int, top_k: int = 0,
                           paged: bool = False) -> CommPlan:
    """Per-replica decode plan for the fleet router (round 19,
    tpukit/serve/fleet.py): the router is pure host-side scheduling over
    DISJOINT device subsets — it adds ZERO collectives — so each
    replica's decode program must audit against exactly the standalone
    engine's closed form (`decode_comm_plan`), merely compiled on a
    subset mesh. A fleet whose per-replica HLO drifts from this plan has
    leaked router state into the compiled program (e.g. a cross-replica
    sharding constraint), which is precisely what the hlolint
    `fleet_decode` world exists to catch: it compiles `decode_step` on a
    NON-LEADING device subset of the 8-virtual-device mesh and requires
    plan-exact collectives with 0 involuntary-remat warnings."""
    p = decode_comm_plan(cfg, mesh, slots, top_k=top_k, paged=paged)
    p.label = f"fleet replica {p.label}"
    return p


def ring_wire_bytes(collectives: dict[str, dict], world: int) -> int:
    """Estimated bytes each device actually moves over the interconnect
    for the parsed collectives, from their RESULT payloads (what
    `collective_summary` reports) via the standard ring-algorithm cost
    model. Needed because result bytes are not comparable ACROSS op kinds:
    a reduce-scatter's result is 1/world of the data it moved, an
    all-reduce moves ~2x its result (reduce-scatter + all-gather phases).
    Per-device wire cost for result payload R on a `world`-way ring:

      all-reduce         2 * R * (world-1)/world   (RS + AG phases)
      all-gather             R * (world-1)/world
      all-to-all             R * (world-1)/world
      reduce-scatter         R * (world-1)          (result is 1/world)
      collective-permute     R                      (one hop)

    This is the denominator-normalizer for the quantized-collective
    headline (tests/test_quant_comm.py): "int8 moves <= 30% of
    the f32 wire bytes" compares ring-model wire, not raw result sizes."""
    if world <= 1:
        return 0
    frac = (world - 1) / world
    mult = {
        "all-reduce": 2.0 * frac,
        "all-gather": frac,
        "all-to-all": frac,
        "reduce-scatter": float(world - 1),
        "collective-permute": 1.0,
    }
    total = 0.0
    for op, rec in collectives.items():
        total += rec.get("bytes", 0) * mult.get(op, 1.0)
    return int(total)

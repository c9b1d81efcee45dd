"""Batched KV-cached decode primitives for the serving engine.

Round 14 (ROADMAP #1): the device half of `tpukit/serve`. Three jitted
programs generalize the single-sequence cached decode of
`tpukit/sampling.py` from batch=1 to `[N_slots, W]` with PER-SLOT state —
cursors, EOS/limit flags, rng keys — over one preallocated per-slot KV
ring (the model's `init_kv_cache(cfg, slots, max_len)`):

  - `prefill_slots`: write an admit-batch of (bucket-padded) prompts
    into their slots' token-buffer rows and K/V ring rows in ONE
    dispatch — the "prefill" phase of phase-separated serving, batched
    so a burst of arrivals costs one forward instead of one per
    request. Bucket length and admit size are static (via the rows'
    shape), so the serve path compiles one program per (bucket,
    power-of-two admit size) pair — the declared compile budget
    (`ServeConfig.compile_budget`).
  - `decode_step`: ONE token for every slot — each slot forwards the
    token at its own cursor (a per-row `start` vector through
    the model's `forward_cached`), samples with its own key fold, and appends
    unless it hit EOS or its length limit. One compile total, any slot
    occupancy. The "decode" phase; the host scheduler interleaves
    prefills between steps without ever stalling active slots.
  - `decode_loop`: the fused whole-batch variant (full-width prefill +
    a `lax.while_loop` of the same step body) for callers that know the
    whole batch up front — `sampling.generate_batch` and the per-epoch
    `train.generate_samples` ride this, replacing the retired O(S^2)
    re-forward loop (`_decode_loop_batch`, rounds 4-13).

Why stale cache garbage is harmless (the invariant every program here
leans on): attention masks keys at positions > the query position, and a
slot's decode writes its K/V at `cursor-1` BEFORE attending — so the
attended range `[0, cursor-1]` is always exactly the positions the
CURRENT request has written (prefill covers `[0, bucket)`, decode
rewrites from `prompt_len-1` contiguously). Leftovers from a longer
evicted request above the cursor are never read, which is what lets a
freed slot be reused with nothing but a prefill — no cache clearing,
no masked writes in the hot step.

Token parity: per slot, the math is exactly `sampling._decode_loop_cached`
— same read/write order, same `fold_in(key, cursor)` sampling fold, same
stop-before-EOS append — so the batched decode is token-for-token the
serial cached decode whatever the surrounding slots do
(tests/test_serve.py, incl. mid-stream admit/evict).

Sharded serving (`mesh`): the step runs under the training TP mesh with
params at their training shardings, the KV ring sharded over heads on
the `model` axis and slots on the `data` axis. The one deliberate
sharding constraint pins the step's sampled logits to model-replicated —
one all-gather per step at a known size — so the per-step collectives
have a closed form (`decode_step_comm`) the compiled HLO must match
(the round-10/12 audit discipline, tests/test_serve.py).

The model behind the programs: every call into the model goes through
`tpukit.model.family(cfg)` — the module that implements the config's block
family and offers `forward_cached`, `init_kv_cache`, `select_lanes` and
`merge_lanes` — so the GPT block and the latent family (tpukit/model/
latent.py) run under the same three programs. Nothing here names a model's
function or reads a model's sizes.

Paged KV (round 15, tpukit/serve/paged.py): when the cache pytree
carries block tables (`"bt"`), the same programs run against the page
pool — `decode_step` threads the live-slot mask into the pool
write-back, `prefill_chunk_paged` replaces the per-bucket
`prefill_slots` with chunked whole-page prefill, and
`decode_step_comm(paged=True)` extends the audit (the paged gather adds
ZERO collectives on the model-only paged grid). The ring programs and
their traces are byte-unchanged.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from tpukit.model import family


@jax.named_scope("sample")
def _select_next(last, cursors, keys, temperature: float, top_k: int):
    """Next token per slot from f32 logits `last [N, V]`: exactly
    `sampling._sample_next` — THE one sampling spelling every decode
    loop shares — vmapped over slots. vmap semantics make each row's
    draw identical to the unbatched call, which is what the same-seed
    batched==serial parity tests pin; temperature == 0 is the greedy
    static branch (keys untouched)."""
    from tpukit.sampling import _sample_next

    if temperature > 0.0:
        return jax.vmap(
            partial(_sample_next, temperature=temperature, top_k=top_k)
        )(last, cursors, keys)
    return jnp.argmax(last, axis=-1)


@jax.named_scope("decode")
def _advance(params, cfg, buf, cache, cursors, active, limits, keys,
             eos_id: int, temperature: float, top_k: int, mesh=None):
    """One decode tick for every slot (shared by `decode_step` and
    `decode_loop`'s while body). Inactive slots re-forward their last
    token into the same cache position — a write of identical values —
    and are masked out of every buffer/cursor update."""
    n, total = buf.shape
    read = jnp.clip(cursors - 1, 0, total - 1)
    tok = jnp.take_along_axis(buf, read[:, None], axis=1)
    if "bt" in cache:
        # Paged cache (round 15): the re-forward of an inactive lane must
        # NOT reach the page pool — a freed lane's block-table row may
        # alias pages the allocator has re-issued, and a prefilling lane's
        # cursor-0 write would corrupt its own first page. `write_mask`
        # routes masked rows to the null page; the ring path needs no mask
        # because each slot exclusively owns its full-width ring rows.
        logits, cache = family(cfg).forward_cached(
            params, cfg, tok, read[:, None].astype(jnp.int32), cache, read,
            write_mask=active, mesh=mesh,
        )
    else:
        logits, cache = family(cfg).forward_cached(
            params, cfg, tok, read[:, None].astype(jnp.int32), cache, read
        )
    last = logits[:, -1].astype(jnp.float32)
    if mesh is not None and "model" in mesh.axis_names:
        # Pin the sampled logits model-replicated (slots stay data-sharded):
        # ONE all-gather of the vocab-sharded head output per step, at a
        # size the closed-form audit (`decode_step_comm`) prices exactly.
        # Left to itself GSPMD picks its own (version-dependent) plan for
        # the argmax/categorical over a sharded vocab axis — unauditable.
        batch_axis = "data" if "data" in mesh.axis_names else None
        last = jax.lax.with_sharding_constraint(
            last, NamedSharding(mesh, P(batch_axis, None))
        )
    next_token = _select_next(last, cursors, keys, temperature, top_k).astype(buf.dtype)
    hit_eos = next_token == eos_id
    fits = cursors < limits
    # stop BEFORE appending on EOS (reference utils.py:67-68)
    append = active & fits & ~hit_eos
    write = jnp.clip(cursors, 0, total - 1)
    # One-hot select instead of a scatter: `buf.at[rows, write].set` makes
    # GSPMD partition a batched scatter, which drags its s32 index tensors
    # through collective-permute/all-gather plumbing on the data axis —
    # unauditable noise for a [N, W] buffer a fused elementwise select
    # writes with ZERO comm. Values are identical.
    col = jax.lax.broadcasted_iota(jnp.int32, (n, total), 1)
    hit = (col == write[:, None]) & append[:, None]
    buf = jnp.where(hit, next_token[:, None], buf)
    cursors = jnp.where(append, cursors + 1, cursors)
    active = active & fits & ~hit_eos & (cursors < limits)
    return buf, cache, cursors, active


# NOTE: buffer donation is deliberately OMITTED on the serve programs. An
# earlier jaxlib mis-aliased the inputs of donated executables DESERIALIZED
# from the persistent compilation cache (a fresh process with a warm cache
# decoded garbage while the compiling process was correct). Not re-tested
# on 0.9.0; see ROADMAP S6a — restoring donation moves a metric and is a
# perf PR of its own.
@partial(
    jax.jit,
    static_argnames=("cfg", "eos_id", "temperature", "top_k", "mesh", "steps"),
)
def decode_step(params, cfg, buf, cache, cursors, active,
                limits, keys, eos_id: int, temperature: float = 0.0,
                top_k: int = 0, mesh=None, steps: int = 1):
    """`steps` tokens for every slot (default 1). buf `[N, W]`, cache the
    `init_kv_cache` ring, cursors/active/limits `[N]`, keys `[N, 2]`
    uint32 (per-slot PRNG keys — ignored by the greedy trace). Returns
    the advanced `(buf, cache, cursors, active)`; a slot leaves `active`
    when it samples EOS or its cursor reaches its limit, and a slot that
    finishes mid-quantum stays FROZEN for the remaining ticks — the
    token stream is identical for any `steps`, only the host sync
    cadence changes. ONE compile per quantum size for the whole serve
    path regardless of occupancy or prompt mix.

    `steps > 1` is the decode QUANTUM: one runtime dispatch (and one
    host sync) per `steps` tokens instead of per token. Measured on the
    CPU backend a standalone dispatch costs ~5ms of host/runtime
    overhead per call while a loop-body tick costs ~1ms — per-token
    dispatch is exactly how the serial while_loop decode out-runs a
    naively-scheduled batched engine. The cost is eviction/admission
    latency quantized to `steps` ticks. The comm audit is unaffected:
    the fori_loop body appears ONCE in the compiled HLO, so
    `decode_step_comm` stays the per-step expectation at any quantum
    (tests/test_serve.py pins this)."""
    if steps == 1:
        return _advance(params, cfg, buf, cache, cursors, active, limits,
                        keys, eos_id, temperature, top_k, mesh)

    def tick(_, carry):
        buf, cache, cursors, active = carry
        return _advance(params, cfg, buf, cache, cursors, active, limits,
                        keys, eos_id, temperature, top_k, mesh)

    return jax.lax.fori_loop(0, steps, tick, (buf, cache, cursors, active))


# No donation here either — see the decode_step note.
@partial(
    jax.jit,
    static_argnames=("cfg",),
)
@jax.named_scope("prefill")
def prefill_slots(params, cfg, buf, cache, cursors, active,
                  limits, keys, slots, rows, prompt_lens, new_limits, new_keys):
    """Admit `A` requests in ONE dispatch: write their bucket-padded
    prompts `rows [A, bucket]` into the token buffer at `slots [A]` and
    prefill their K/V for positions `[0, bucket)` as ONE batched forward
    (pad positions write garbage K/V that the decode step's causal window
    never reads — module docstring). The admit-batch size A and the
    bucket are STATIC (rows' shape): compile count == distinct
    (bucket, A) pairs, which the engine bounds by padding A to a power
    of two with REPEATS of the first entry — a repeated admit rewrites
    the same slot with the same values, so dummies are idempotent.
    `slots`/`prompt_lens`/`new_limits`/`new_keys` are traced, so any
    request mix at any lanes reuses the pair's program.

    The prefill forward only materializes a `[A, bucket]`-deep scratch
    cache (the positions it writes); each admitted slot's scratch rows
    land in the big ring at `[slot, :, 0:bucket)`. Only the admitted
    lanes' state changes — active slots pass through untouched, which is
    what lets the scheduler admit mid-decode without stalling anyone."""
    a, bucket = rows.shape
    pos = jnp.broadcast_to(jnp.arange(bucket, dtype=jnp.int32), rows.shape)
    model = family(cfg)
    scratch = model.init_kv_cache(cfg, a, bucket)
    _, scratch = model.forward_cached(params, cfg, rows, pos, scratch, 0)
    for i in range(a):  # A is static and small (<= slots): unrolled writes
        buf = jax.lax.dynamic_update_slice(
            buf, rows[i : i + 1].astype(buf.dtype), (slots[i], 0)
        )
        cache = {
            n: jax.lax.dynamic_update_slice(
                c,
                jax.lax.dynamic_slice_in_dim(scratch[n], i, 1, axis=1),
                (0, slots[i], 0, 0, 0),
            )
            for n, c in cache.items()
        }
        cursors = cursors.at[slots[i]].set(prompt_lens[i])
        active = active.at[slots[i]].set(True)
        limits = limits.at[slots[i]].set(new_limits[i])
        keys = keys.at[slots[i]].set(new_keys[i])
    return buf, cache, cursors, active, limits, keys


# No donation — see the decode_step note.
@partial(jax.jit, static_argnames=("cfg",))
@jax.named_scope("prefill")
def prefill_chunk_paged(params, cfg, buf, cache, cursors,
                        active, limits, keys, slots, rows, starts, is_last,
                        prompt_lens, new_limits, new_keys):
    """One CHUNKED-PREFILL dispatch against the paged cache (round 15):
    forward `rows [A, C]` — each lane's next `C` prompt tokens at logical
    positions `[starts[i], starts[i] + C)` — through the lanes' block
    tables in ONE batched call, writing whole pages (`starts` page-aligned
    and C a page multiple, the engine's chunking contract; C is the
    static `ServeConfig.chunk`). A long prompt is split across scheduler
    iterations — one chunk per lane per iteration, decode quanta running
    in between — so an 8k prompt can never stall admission or active
    slots for more than one chunk's compute.

    A chunk's attention reads everything its lane's block table already
    holds: earlier chunks AND shared-prefix pages another request
    prefilled (skipping the shared compute entirely is the prefix-reuse
    win). Rows on their LAST chunk (`is_last`) arm the lane's decode
    state — cursor to the prompt length, limit, per-request key, active.
    The admit batch pads to a power of two by REPEATING entries (the
    round-14 idempotence trick: a repeated row rewrites the same pages
    and lane state with the same values), so compiles stay bounded by the
    power-of-two admit sizes — one program per (A, C) pair."""
    a, c = rows.shape
    model = family(cfg)
    sub = model.select_lanes(cache, slots, prompt_lens)  # the A lanes' block-table rows
    pos = starts[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]
    _, sub = model.forward_cached(params, cfg, rows, pos, sub, starts)
    cache = model.merge_lanes(cache, sub)  # pools carry the writes; global tables kept
    for i in range(a):  # A is static and small: unrolled lane updates
        buf = jax.lax.dynamic_update_slice(
            buf, rows[i : i + 1].astype(buf.dtype), (slots[i], starts[i])
        )
        arm = is_last[i]
        cursors = jnp.where(arm, cursors.at[slots[i]].set(prompt_lens[i]), cursors)
        active = jnp.where(arm, active.at[slots[i]].set(True), active)
        limits = jnp.where(arm, limits.at[slots[i]].set(new_limits[i]), limits)
        keys = jnp.where(arm, keys.at[slots[i]].set(new_keys[i]), keys)
    return buf, cache, cursors, active, limits, keys


# No donation — see the decode_step note.
@jax.jit
def adopt_slot(buf, cursors, active, limits, keys, slot, row, prompt_len,
               new_limit, new_key):
    """Arm ONE lane whose K/V was prefilled by a DIFFERENT engine (the
    disaggregated-prefill handoff, round 19, tpukit/serve/fleet.py): write
    the prompt row into the token buffer at `slot` and set the lane's
    decode state — cursor to `prompt_len`, limit, per-request key, active.
    Pure dynamic-update-slice/at-set writes, NO model forward: the page
    pool already holds the handed-off K/V (copied by fleet._copy_pages),
    so a decode replica adopting prefixes never compiles a prefill
    program — its serve-path compile budget is one decode program plus
    this trivial arm (one compile per (slots, width) shape)."""
    buf = jax.lax.dynamic_update_slice(
        buf, row[None].astype(buf.dtype), (slot, 0)
    )
    cursors = cursors.at[slot].set(prompt_len)
    active = active.at[slot].set(True)
    limits = limits.at[slot].set(new_limit)
    keys = keys.at[slot].set(new_key)
    return buf, cursors, active, limits, keys


@partial(
    jax.jit,
    static_argnames=("cfg", "max_new_tokens", "eos_id", "temperature", "top_k"),
)
def decode_loop(params, cfg, buf, prompt_lens,
                max_new_tokens: int, eos_id: int, temperature: float = 0.0,
                top_k: int = 0, rng=None):
    """Fused whole-batch cached decode: prefill the full `[N, W]` buffer
    once (per-row prompt lengths are TRACED — one compile per buffer
    shape), then run the decode tick in a `lax.while_loop` until every
    row is done. Zero host round-trips inside the loop — the right shape
    when the whole batch is known up front (`sampling.generate_batch`).
    Returns `(buf, lengths)`.

    All rows share `rng` (each folds its own cursor), matching serial
    `generate(..., seed=)` per prompt. Token-for-token equal to the
    serial cached decode for every row; see the module docstring for why
    the full-width prefill's pad-position K/V garbage is never read."""
    n, total = buf.shape
    model = family(cfg)
    cache = model.init_kv_cache(cfg, n, total)
    pos = jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32), buf.shape)
    _, cache = model.forward_cached(params, cfg, buf, pos, cache, 0)
    cursors = prompt_lens.astype(jnp.int32)
    limits = jnp.minimum(cursors + max_new_tokens, total)
    active = cursors < limits
    keys = (
        jnp.broadcast_to(rng, (n,) + rng.shape)
        if rng is not None
        else jnp.zeros((n, 2), jnp.uint32)
    )

    def cond(carry):
        return jnp.any(carry[3])

    def body(carry):
        buf, cache, cursors, active = carry
        return _advance(params, cfg, buf, cache, cursors, active, limits,
                        keys, eos_id, temperature, top_k)

    buf, _, cursors, _ = jax.lax.while_loop(
        cond, body, (buf, cache, cursors, active)
    )
    return buf, cursors


# No donation — see the decode_step note.
@partial(
    jax.jit,
    static_argnames=("cfg", "eos_id", "temperature", "top_k", "mesh"),
)
def decode_loop_window(params, cfg, buf, cache, cursors,
                       active, limits, keys, pages_held, max_ticks,
                       stop_when_freed, eos_id: int,
                       temperature: float = 0.0, top_k: int = 0, mesh=None):
    """On-device scheduler window (round 21, ROADMAP #3): run the decode
    tick in a `lax.while_loop` for up to `max_ticks` quanta WITHOUT any
    host sync — cursors, EOS/limit flags, and the freed-page account all
    live in the carry, so the whole window costs ONE runtime dispatch.
    PR 16's trace attribution priced the per-quantum host overhead at
    ~0.3ms dispatch against ~0.7ms device work; this loop amortizes that
    dispatch cost across the window instead of paying it every quantum.

    The loop exits early — handing control back to the host scheduler
    before the window is spent — when continuing would waste device time
    or starve admission:

      - every lane is done (`~any(active)`): nothing left to decode;
      - `freed >= stop_when_freed`: lanes that finished mid-window have
        released enough pages (`pages_held [N]` int32, each lane's
        page count, summed as lanes flip inactive) to admit the
        scheduler's head-of-queue request — the host should evict and
        admit NOW rather than let capacity idle for the rest of the
        window. Pass `1 << 30` when the queue is empty.

    `max_ticks` and `stop_when_freed` are TRACED int32 scalars: one
    compile serves every window size and page target. Returns
    `(buf, cache, cursors, active, ticks, freed)` — `ticks` is how many
    ticks actually ran (the engine's step accounting fetches it with the
    window-boundary sync, never mid-window).

    Token parity is free: the body is `_advance` — frozen lanes tick as
    no-ops and each lane's sampling folds its own cursor — so the streams
    are identical for ANY (max_ticks, early-exit) schedule; only the host
    sync cadence changes (tests/test_paged_attention.py pins loop-vs-
    repeated-`decode_step` equality under early exit). The comm audit is
    unaffected for the same reason the quantum was: the while body
    appears ONCE in the compiled HLO, so `decode_step_comm` stays the
    per-step expectation at any window (the `sched_loop` hlolint world).
    """

    def cond(carry):
        _, _, _, active, ticks, freed = carry
        return jnp.any(active) & (ticks < max_ticks) & (freed < stop_when_freed)

    def body(carry):
        buf, cache, cursors, active, ticks, freed = carry
        buf, cache, cursors, new_active = _advance(
            params, cfg, buf, cache, cursors, active, limits, keys,
            eos_id, temperature, top_k, mesh
        )
        just_done = active & ~new_active
        freed = freed + jnp.sum(jnp.where(just_done, pages_held, 0))
        return buf, cache, cursors, new_active, ticks + 1, freed

    zero = jnp.zeros((), jnp.int32)
    return jax.lax.while_loop(
        cond, body, (buf, cache, cursors, active, zero, zero)
    )


def decode_step_comm(cfg, mesh, slots: int, top_k: int = 0,
                     paged: bool = False, verify_tokens: int = 1) -> dict:
    """Closed-form PER-DEVICE collective expectation for one compiled
    `decode_step` under a (data x model) serving mesh — the round-10/12
    audit discipline applied to the decode path: the compiled HLO's
    collectives must match this exactly (tests/test_serve.py).

    With params at their TensorParallel training shardings, slots (and
    the KV ring's batch axis) sharded over `data` and heads over
    `model`, the step's comm is:

      - `all-reduce` x (2*num_layers + 1): the Megatron pair per layer
        (row-parallel attn-out + ffn-down partial sums) on the
        `[N/d, 1, dim]` activations in the compute dtype, plus ONE
        f32 all-reduce for the token-embedding gather from the
        row(vocab)-sharded table (GSPMD's partial-gather lowering:
        local masked take + psum).
      - `all-gather` x 1: the deliberate logits constraint in
        `_advance` — the vocab-sharded head output gathered
        model-replicated before sampling, `[N/d, padded_vocab]` f32.
      - with top-k sampling (`top_k > 0`) and a data axis > 1, ONE more
        all-gather: `lax.top_k` is a sort and GSPMD replicates its batch
        axis over `data` — the full `[N, padded_vocab]` f32 per step, a
        real (measured, priced-in) cost of top-k truncation on a
        data-sharded slot set. Greedy and temperature-only sampling
        don't pay it.

    Precondition: `cfg.heads % model == 0` (the recipe's grid picker
    guarantees it) — with heads undividable the KV ring can't shard over
    `model` and GSPMD inserts extra resharding all-reduces around the
    cache that this formula deliberately refuses to model.

    Byte counts are RESULT payloads, the convention
    `obs.xla.collective_bytes` reports. On XLA:CPU the float wire is
    f32 (the round-12 `wire_itemsize` lesson): audit with a f32
    compute dtype for exact equality on any backend. Round 16:
    `analysis.plan.decode_comm_plan` wraps this closed form as an
    EXHAUSTIVE CommPlan (measured == expected, nothing else tolerated)
    for the hlolint rule engine (DESIGN.md §15).

    `paged=True` (round 15) extends the audit to the paged gather: the
    page pools shard heads over `model` and are REPLICATED across `data`,
    and the block tables are replicated — so the gather (page axis,
    replicated indices) and the pool write-back scatter are comm-free and
    the formula above is UNCHANGED. That only holds with a 1-sized data
    axis: data-sharded slots writing into a data-replicated pool would
    force GSPMD to reconcile the scatter with version-dependent index
    plumbing this formula refuses to model, so paged + data > 1 raises
    here (and at engine construction) instead of drifting from the HLO.

    `verify_tokens=t > 1` (round 17) prices the SPECULATIVE verify step
    (`serve/spec.verify_step`, t = spec_k + 1): the same program shape
    with every activation t positions wide — identical collective COUNTS
    (the speculation win in comm terms: t tokens of progress per
    collective round-trip) with every byte term scaled by t. The
    acceptance math itself (uniform draws, cumprod prefix, residual
    categorical) runs on the model-replicated logits and adds ZERO
    collectives — exactly why the logits pin is the one constraint.
    Speculation runs on the ring only, so `paged` and `verify_tokens>1`
    are mutually exclusive (ServeConfig enforces the same upstream).
    """
    if paged and verify_tokens > 1:
        raise ValueError(
            "speculative verify (verify_tokens > 1) audits the ring cache "
            "only — spec + paged is rejected at ServeConfig"
        )
    d = mesh.shape.get("data", 1)
    m = mesh.shape.get("model", 1)
    if paged and d > 1:
        raise ValueError(
            f"paged KV serving requires a model-only grid (data axis 1, "
            f"got data={d}): the page pool is replicated across `data`, "
            f"and a data-sharded slot set would turn the pool write-back "
            f"into an unauditable cross-shard scatter — shrink the data "
            f"axis or use the ring cache (page_size=0)"
        )
    if slots % d:
        raise ValueError(
            f"slots={slots} must be a multiple of the data axis ({d}) — "
            f"slots shard over it"
        )
    heads = family(cfg).kv_heads(cfg)
    if m > 1 and heads % m:
        raise ValueError(
            f"heads={heads} must divide the model axis ({m}) for the "
            f"closed-form decode audit — undividable heads leave the KV "
            f"ring unsharded and GSPMD inserts resharding this formula "
            f"does not model"
        )
    n_local = slots // d
    t = verify_tokens
    act = n_local * t * cfg.dim * jnp.dtype(cfg.compute_dtype).itemsize
    embed = n_local * t * cfg.dim * jnp.dtype(cfg.param_dtype).itemsize
    out = {}
    if m > 1:
        out["all-reduce"] = {
            "count": 2 * cfg.num_layers + 1,
            "bytes": 2 * cfg.num_layers * act + embed,
        }
        logits = n_local * t * cfg.padded_vocab_size * 4  # f32 sample logits
        out["all-gather"] = {"count": 1, "bytes": logits}
        if top_k > 0 and d > 1:
            out["all-gather"]["count"] += 1
            out["all-gather"]["bytes"] += slots * t * cfg.padded_vocab_size * 4
    return out

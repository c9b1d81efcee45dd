"""Speculative decoding: draft-and-verify on the slot engine (round 17).

ROADMAP open item 3. Vanilla decode is bounded by one target-model forward
per token per slot (the round-14 tick): latency is model depth per token,
whatever the batch. Speculation breaks that bound with two moves:

  - a **draft proposer** guesses k candidate tokens per active slot per
    scheduler quantum — either a small tpukit GPT draft model with its own
    KV ring (`draft_propose`), or **self-speculation** with no second model
    at all (`NGramProposer`: prompt-lookup / n-gram continuation of the
    slot's own history — near-free, and very effective on repetitive
    streams);
  - the target model scores all k+1 positions in ONE batched forward
    (`verify_step`): the k-token window per slot is exactly the
    "mini-prefill" chunk shape the per-row-cursor cached attention
    (`gpt.forward_cached` with a vector `start`) already compiles for
    chunked prefill — one dispatch verifies what vanilla decode needed
    k+1 dispatch-sequential ticks to produce.

**Distribution exactness** (the whole point — speculation must be an
optimization, never a model change):

  - temperature == 0: a draft token is accepted iff it equals the
    target's argmax at its position; the first mismatch is replaced by
    the target argmax. Greedy output is therefore TOKEN-IDENTICAL to
    vanilla decode by construction (asserted engine-vs-engine in
    tests/test_spec.py).
  - temperature > 0: standard rejection sampling (Leviathan et al. /
    Chen et al., PAPERS.md): accept draft token d with probability
    min(1, p(d)/q(d)) where p is the TARGET distribution and q the
    proposal; on the first rejection sample from the residual
    norm(max(p - q, 0)); if every draft survives, sample a bonus token
    from p at the next position. Marginally each emitted token is an
    exact p-sample:  P(x) = q(x)·min(1, p(x)/q(x)) +
    (1 - Σ_y q(y)·min(1, p(y)/q(y)))·residual(x) = p(x).
    Deterministic proposers (n-gram) are the one-hot-q special case:
    accept with probability p(d), residual = p with d zeroed.

  The target distribution p is built with `sampling._adjust_logits` —
  the SAME temperature/top-k transform `_sample_next` draws from — and
  the whole acceptance computation lives in ONE spelling
  (`_accept_prefix`) shared by the engine's batched verify (vmapped over
  slots) and the serial test reference (`reference_spec_decode`), the
  round-14 `_sample_next` discipline applied to speculation: parity is
  the bit-for-bit agreement of this one function across call sites.

**Why KV rollback is free** (ring cache): the verify forward writes K/V
for positions `[cur-1, cur-1+k]` BEFORE attending, and attention reads
only `key_pos <= q_pos` — so rejected positions hold garbage K/V that is
above the advanced cursor, unreachable by the causal window, and
REWRITTEN by the next quantum's verify before anything attends to it:
exactly the round-14 stale-tail invariant (serve/decode.py module
docstring), now load-bearing for rollback. The same argument covers the
draft model's own ring, with one extra care: a quantum can leave the
draft ring missing K/V for up to TWO trailing emitted tokens (the k-th
accepted proposal and the bonus sample — the draft's own ticks stop one
position short of its last proposal), so `draft_propose` opens with a
2-token catch-up window re-forwarding `buf[cur-2], buf[cur-1]` before
proposing, overwriting whatever rejected proposals (or a previous slot
occupant) left behind — "rollback" is a cursor rewind plus that fixed-
width rewrite, no data movement. (A paged draft
cache would be a block-table-row truncate for the same reason, but the
multi-token verify write-back needs position-granular masked pool writes
the paged `write_pages` contract — page-aligned whole pages — does not
cover, so spec requires the ring cache this round; `ServeConfig` rejects
`draft` + `page_size` with a named error. DESIGN.md §16.)

The ring is over-allocated by `spec_k` scratch positions
(`width + spec_k`): a lane whose cursor sits near the buffer end still
writes its full k+1 verify window without `dynamic_update_slice`'s
start-clamping sliding the chunk DOWN over valid history. Scratch
positions sit above every lane's limit, so they are never appended,
never attended by an accepted query, and rewritten like any stale tail.

Per-step comm has the same closed form as the vanilla step widened by
the verify window: `decode.decode_step_comm(..., verify_tokens=k+1)`
prices the compiled `verify_step` under the TP serving grid exactly
(same collective COUNT as one decode tick — the speculation win in comm
terms: k+1 tokens of progress per collective round-trip), audited
through hlolint's comm-plan rule (`tools/hlolint.py --world 8`,
spec_verify world).
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from tpukit.model import gpt
from tpukit.sampling import _adjust_logits, _sample_next

# Salted sub-streams of the per-request PRNG key: the accept uniforms and
# the residual/bonus draw fold a salt on top of the position fold so they
# never collide with `_sample_next`'s unsalted `fold_in(key, pos)` — which
# the DRAFT model's own sampling uses verbatim (it proposes exactly what a
# vanilla decode of the draft would emit at that position).
_SALT_ACCEPT = 0x5AC
_SALT_FIX = 0x5AF

_TINY = 1e-30  # guards p/q ratios and log(0); never changes an accept


def _accept_prefix(logits, draft, q_probs, draft_len, key, cursor,
                   temperature: float, top_k: int):
    """THE acceptance spelling — one slot's rejection-sampling pass over
    one verify window. `logits [k+1, V]` f32 target logits (position j
    predicts the token at `cursor + j`), `draft [k]` proposed tokens,
    `q_probs [k, V]` the proposal distribution per position (one-hot rows
    for deterministic proposers), `draft_len` in `[0, k]` (positions
    `>= draft_len` are padding, never accepted), `key [2]` the request's
    PRNG key, `cursor` the slot's logical position.

    Returns `(accepted, tokens)`: `accepted` is the accepted-prefix
    length (`<= draft_len`), `tokens [k+1]` carries the accepted draft
    tokens in `[0, accepted)` and the corrected / bonus target sample at
    index `accepted` (entries beyond are unspecified). The k=0 / all-
    padding degenerate emits exactly one target sample — a vanilla step.

    The engine vmaps this over slots; the serial test reference calls it
    on one row — bit-for-bit the same math is the parity guarantee
    (module docstring). Draw streams: accept uniforms at
    `fold_in(fold_in(key, cursor+i), _SALT_ACCEPT)`, the correction at
    `fold_in(fold_in(key, cursor+accepted), _SALT_FIX)` — position-keyed,
    so a fixed seed reproduces regardless of quantum boundaries."""
    k = draft.shape[0]
    i = jnp.arange(k, dtype=jnp.int32)
    if temperature > 0.0:
        adj = _adjust_logits(logits, temperature, top_k)  # [k+1, V]
        p = jax.nn.softmax(adj, axis=-1)
        u = jax.vmap(
            lambda pos: jax.random.uniform(
                jax.random.fold_in(jax.random.fold_in(key, pos), _SALT_ACCEPT)
            )
        )(cursor + i)
        p_d = jnp.take_along_axis(p[:k], draft[:, None], axis=1)[:, 0]
        q_d = jnp.take_along_axis(q_probs, draft[:, None], axis=1)[:, 0]
        # accept iff u < min(1, p/q)  <=>  u * q < p (u ~ U[0,1))
        ok = (i < draft_len) & (u * jnp.maximum(q_d, _TINY) < p_d)
        accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
        p_next = p[accepted]  # [V] — target dist at the correction slot
        rejected = accepted < draft_len
        q_row = q_probs[jnp.minimum(accepted, k - 1)]
        resid = jnp.maximum(p_next - q_row, 0.0)
        rsum = jnp.sum(resid)
        # all-accepted -> bonus from p; rejected -> residual correction.
        # A numerically-empty residual (p == q to the ulp) falls back to
        # p itself — still an exact p-sample, since rejection there has
        # probability ~0 anyway.
        dist = jnp.where(rejected & (rsum > 0.0), resid / jnp.maximum(rsum, _TINY), p_next)
        fix = jax.random.categorical(  # lint: allow(sampling-spelling): the rejection-sampling CORRECTION draw — from the residual max(p-q,0), not the model distribution _sample_next owns, on the salted _SALT_FIX stream so it can never collide with _sample_next's unsalted position fold
            jax.random.fold_in(
                jax.random.fold_in(key, cursor + accepted), _SALT_FIX
            ),
            jnp.where(dist > 0.0, jnp.log(jnp.maximum(dist, _TINY)), -jnp.inf),
        )
    else:
        am = jnp.argmax(logits, axis=-1)  # [k+1]
        ok = (i < draft_len) & (draft == am[:k])
        accepted = jnp.sum(jnp.cumprod(ok.astype(jnp.int32)))
        fix = am[accepted]
    dpad = jnp.concatenate([draft, draft[-1:]])
    tokens = jnp.where(jnp.arange(k + 1) < accepted, dpad, fix)
    return accepted, tokens.astype(jnp.int32)


def _verify_body(params, cfg: gpt.GPTConfig, buf, cache, cursors, active,
                 limits, keys, draft, draft_q, draft_len, eos_id: int,
                 temperature: float, top_k: int, k: int,
                 onehot_q: bool, mesh):
    """The verify quantum's traced body — ONE spelling shared by
    `verify_step` (external draft: the draft model, or a host-side test
    proposer) and `spec_ngram_step` (fused on-device self-speculation).
    See `verify_step` for the contract."""
    n, total = buf.shape
    read = jnp.clip(cursors - 1, 0, total - 1)
    last_tok = jnp.take_along_axis(buf, read[:, None], axis=1)
    toks = jnp.concatenate([last_tok, draft.astype(buf.dtype)], axis=1)
    pos = read[:, None] + jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    logits, cache = gpt.forward_cached(params, cfg, toks, pos, cache, read)
    lg = logits.astype(jnp.float32)  # [N, k+1, V]
    if mesh is not None and "model" in mesh.axis_names:
        # The decode step's logits constraint, k+1 wide: ONE all-gather of
        # the vocab-sharded head output per quantum at a size the closed
        # form prices exactly (decode.decode_step_comm, verify_tokens).
        batch_axis = "data" if "data" in mesh.axis_names else None
        lg = jax.lax.with_sharding_constraint(
            lg, NamedSharding(mesh, P(batch_axis, None, None))
        )
    if onehot_q:
        q = jax.nn.one_hot(draft, lg.shape[-1], dtype=jnp.float32)
    else:
        q = draft_q
    accepted, cand = jax.vmap(
        partial(_accept_prefix, temperature=temperature, top_k=top_k)
    )(lg, draft, q, draft_len, keys, cursors)

    # Per-token emission gates, vectorized over the candidate window —
    # tick-for-tick the vanilla `_advance` semantics: a token appends iff
    # the lane is active, it is within the accepted prefix, its position
    # fits below the limit, and no earlier candidate was EOS; the first
    # EOS inside the appendable window freezes the lane WITHOUT being
    # appended (reference stop-before-append), and a lane whose cursor
    # reaches its limit freezes with reason "length" exactly as vanilla.
    j = jnp.arange(k + 1, dtype=jnp.int32)[None, :]
    can = active[:, None] & (j <= accepted[:, None])
    fits = (cursors[:, None] + j) < limits[:, None]
    is_eos = cand == eos_id
    eos_before = jnp.cumsum(is_eos.astype(jnp.int32), axis=1) - is_eos.astype(jnp.int32)
    append = can & fits & (eos_before == 0) & ~is_eos
    eos_hit = jnp.any(can & fits & (eos_before == 0) & is_eos, axis=1)
    n_app = jnp.sum(append.astype(jnp.int32), axis=1)

    # One-hot-select buffer write (the decode-step rule: a batched scatter
    # drags s32 index plumbing through GSPMD; the masked select is
    # comm-free). `append` is a contiguous prefix of the window (every
    # gate is prefix-monotone), so the write range is [cursor, cursor+n).
    col = jax.lax.broadcasted_iota(jnp.int32, (n, total), 1)
    rel = col - cursors[:, None]
    sel = (rel >= 0) & (rel < n_app[:, None])
    vals = jnp.take_along_axis(cand, jnp.clip(rel, 0, k), axis=1)
    buf = jnp.where(sel, vals.astype(buf.dtype), buf)
    new_cursors = cursors + n_app
    new_active = active & ~eos_hit & (new_cursors < limits)
    return buf, cache, new_cursors, new_active, accepted, n_app


# No donation — the serve-path rule (decode.decode_step note: not
# re-tested on 0.9.0; see ROADMAP S6a).
@partial(
    jax.jit,
    static_argnames=("cfg", "k", "eos_id", "temperature", "top_k",
                     "onehot_q", "mesh"),
)
def verify_step(params, cfg: gpt.GPTConfig, buf, cache, cursors, active,
                limits, keys, draft, draft_q, draft_len, eos_id: int,
                temperature: float = 0.0, top_k: int = 0, *, k: int,
                onehot_q: bool = False, mesh=None):
    """One speculative quantum for every slot: score the k+1-token verify
    window `[last emitted, d_0 .. d_{k-1}]` in ONE batched forward against
    the KV ring (per-row vector cursors — the chunked-prefill shape),
    accept a per-slot prefix by `_accept_prefix`, and append the accepted
    tokens plus the corrected/bonus sample under EXACTLY the vanilla
    per-token gates (stop before appending EOS, stop at the limit,
    inactive lanes frozen). Returns
    `(buf, cache, cursors, active, accepted, appended)` — the last two
    `[N]` i32 for telemetry (draft tokens accepted by the test; tokens
    actually appended incl. the correction).

    `draft [N, k]` / `draft_len [N]` come from the proposer;
    `draft_q [N, k, V]` is the proposal distribution (pass None with
    `onehot_q=True` for deterministic proposers — the one-hot rows are
    built on device, saving the H2D). Rejected positions need no cache
    rollback (module docstring); inactive lanes re-forward garbage into
    positions above their frozen cursors, unreachable like any stale
    tail. Under a TP `mesh` the k+1 sample logits are pinned
    model-replicated — the widened twin of the decode step's one
    deliberate constraint — so the compiled collectives match
    `decode.decode_step_comm(..., verify_tokens=k+1)` exactly."""
    return _verify_body(params, cfg, buf, cache, cursors, active, limits,
                        keys, draft, draft_q, draft_len, eos_id,
                        temperature, top_k, k, onehot_q, mesh)


def _ngram_propose_row(h, cur, *, k: int, max_ngram: int):
    """Device twin of `NGramProposer.propose` for ONE slot's buffer row
    `h [W]` at cursor `cur` — bit-for-bit the same proposal (asserted in
    tests/test_spec.py over random and crafted histories): longest suffix
    length first (`max_ngram` down to 1; a static unrolled loop), most
    recent earlier occurrence, then the periodic-wrap continuation
    `h[cur - s + (i mod s)]` where `s` is the implied period. Returns
    `(draft [k] i32, dlen scalar i32)`, dlen == 0 when no n-gram recurs
    (the k=0 degenerate — verify falls back to a vanilla step)."""
    w = h.shape[0]
    pos = jnp.arange(w, dtype=jnp.int32)
    # The whole match is spelled as static shifts + one-hot masked sums —
    # NO dynamic gathers: a gather indexed by the data-sharded cursor
    # drags s32 index-plumbing all-gathers through GSPMD (the round-14
    # decode buf scatter class, now a named hlolint rule), while shifts
    # and selects partition comm-free. shifts[i][j] == h[j + i] (the pad
    # tail is never consulted: matches require j < cur - n <= w - n).
    shifts = [
        h if i == 0
        else jnp.concatenate([h[i:], jnp.zeros((i,), h.dtype)])
        for i in range(max_ngram)
    ]
    found_n = jnp.int32(0)
    found_j = jnp.int32(-1)
    for n in range(max_ngram, 0, -1):  # longest first, static unroll
        # an EARLIER occurrence: j < cur - n (continuation has at least
        # one in-history token), and the suffix itself must fit (n < cur)
        ok = (pos < cur - n) & (n <= cur - 1)
        for i in range(n):
            # suffix token h[cur - n + i] as a one-hot masked sum
            sfx_i = jnp.sum(jnp.where(pos == cur - n + i, h, 0))
            ok = ok & (shifts[i] == sfx_i)
        j_n = jnp.max(jnp.where(ok, pos, -1))
        take = (found_j < 0) & (j_n >= 0)
        found_n = jnp.where(take, n, found_n)
        found_j = jnp.where(take, j_n, found_j)
    s = jnp.maximum((cur - found_n) - found_j, 1)  # implied period, >= 1
    idx = cur - s + (jnp.arange(k, dtype=jnp.int32) % s)  # all < cur
    draft = jnp.sum(
        jnp.where(pos[None, :] == idx[:, None], h[None, :], 0), axis=1
    )
    dlen = jnp.where(found_j >= 0, k, 0).astype(jnp.int32)
    return draft.astype(jnp.int32), dlen


# No donation — serve-path rule (see verify_step).
@partial(
    jax.jit,
    static_argnames=("cfg", "k", "max_ngram", "eos_id", "temperature",
                     "top_k", "mesh"),
)
def spec_ngram_step(params, cfg: gpt.GPTConfig, buf, cache, cursors, active,
                    limits, keys, eos_id: int, temperature: float = 0.0,
                    top_k: int = 0, *, k: int, max_ngram: int = 3,
                    mesh=None):
    """The FUSED self-speculation quantum: on-device n-gram proposal
    (`_ngram_propose_row`, vmapped — pure per-slot tensor ops, ZERO
    collectives and no measurable compute next to the forward) feeding
    the verify body in the SAME compiled program. One dispatch and one
    host sync per quantum — exactly the vanilla decode step's host
    rhythm, which is what makes self-speculation a strict win on
    repetitive streams instead of trading a forward for two host round
    trips (a host-side proposer pays buf D2H + draft H2D + a second
    dispatch every quantum). Returns the `verify_step` tuple plus the
    per-slot proposal length `dlen [N]` for telemetry. This is the
    program the hlolint `spec_verify` world audits — the comm plan is
    `decode_step_comm(verify_tokens=k+1)` unchanged, because the n-gram
    match reads only the data-sharded buf/cursors."""
    draft, dlen = jax.vmap(
        partial(_ngram_propose_row, k=k, max_ngram=max_ngram)
    )(buf, cursors)
    out = _verify_body(params, cfg, buf, cache, cursors, active, limits,
                       keys, draft, None, dlen, eos_id, temperature, top_k,
                       k, True, mesh)
    return out + (dlen,)


# No donation — serve-path rule (see verify_step).
@partial(
    jax.jit,
    static_argnames=("cfg", "k", "temperature", "top_k"),
)
def draft_propose(params, cfg: gpt.GPTConfig, buf, cache, cursors, keys,
                  *, k: int, temperature: float = 0.0, top_k: int = 0):
    """The draft-model proposer: k tokens per slot from the draft's OWN
    KV ring, autoregressively — each tick forwards the previous token at
    position `cursor - 1 + i` and samples the next with `_sample_next`
    under the engine's temperature/top-k and the slot's request key (the
    unsalted `fold_in(key, pos)` — the draft proposes exactly what a
    vanilla decode of the draft model would emit, one spelling).
    Returns `(draft [N, k] i32, q_probs [N, k, V] f32, cache)`; `q_probs`
    rows are `softmax(_adjust_logits(...))` at temperature > 0 and
    one-hot at the argmax for greedy — the distribution the verify
    step's acceptance test corrects against.

    The pass opens with a TWO-token catch-up window (`buf[cur-2],
    buf[cur-1]` at their own positions) rather than re-forwarding just
    the last emitted token: after an all-accept-plus-bonus quantum the
    draft ring is missing K/V for BOTH trailing emitted tokens — the
    k-th proposal (the last position its own ticks forwarded was k-1)
    and the bonus sample — and a 1-token catch-up would leave the
    earlier of the two permanently unwritten, silently attending
    whatever a previous slot occupant left there. Every other quantum
    shape leaves at most those same two trailing positions stale, so
    the 2-wide window restores the invariant exactly; the serial
    reference mirrors the same spelling, which is what makes engine ==
    reference bit-for-bit (tests/test_spec.py)."""
    n, total = buf.shape
    read = jnp.clip(cursors - 1, 0, total - 1)
    prev = jnp.clip(cursors - 2, 0, total - 1)
    t2 = jnp.concatenate(
        [jnp.take_along_axis(buf, prev[:, None], axis=1),
         jnp.take_along_axis(buf, read[:, None], axis=1)], axis=1
    ).astype(jnp.int32)
    pos2 = jnp.stack([prev, read], axis=1).astype(jnp.int32)
    logits2, cache = gpt.forward_cached(params, cfg, t2, pos2, cache, prev)
    v = cfg.padded_vocab_size

    def sample(last, i):
        """Proposal i from its f32 logits row: token + q-distribution."""
        if temperature > 0.0:
            adj = _adjust_logits(last, temperature, top_k)
            qp = jax.nn.softmax(adj, axis=-1)
            nxt = jax.vmap(
                partial(_sample_next, temperature=temperature, top_k=top_k)
            )(last, cursors + i, keys)
        else:
            nxt = jnp.argmax(last, axis=-1)
            qp = jax.nn.one_hot(nxt, v, dtype=jnp.float32)
        return nxt.astype(jnp.int32), qp

    d0, q0 = sample(logits2[:, -1].astype(jnp.float32), 0)
    toks0 = jnp.zeros((n, k), jnp.int32).at[:, 0].set(d0)
    qs0 = jnp.zeros((n, k, v), jnp.float32).at[:, 0].set(q0)

    def tick(i, carry):
        tok, cache, toks, qs = carry
        p = read + i
        logits, cache = gpt.forward_cached(
            params, cfg, tok[:, None], p[:, None].astype(jnp.int32), cache, p
        )
        nxt, qp = sample(logits[:, -1].astype(jnp.float32), i)
        toks = jax.lax.dynamic_update_slice(toks, nxt[:, None], (0, i))
        qs = jax.lax.dynamic_update_slice(qs, qp[:, None, :], (0, i, 0))
        return nxt, cache, toks, qs

    _, cache, toks, qs = jax.lax.fori_loop(1, k, tick, (d0, cache, toks0, qs0))
    return toks, qs, cache


class NGramProposer:
    """Self-speculation: prompt-lookup / n-gram drafting — no second
    model. For a slot with token history `h[:cur]`, find the most recent
    earlier occurrence of the longest current suffix (length
    `max_ngram` down to 1) and propose the `k` tokens that followed it.
    Deterministic (reproducible per stream), near-free on the host, and
    highly effective when generation is repetitive — which both the
    synthetic repetitive stream and small-model greedy loops are.

    The proposal distribution is the one-hot at each proposed token
    (`onehot_q=True` in `verify_step`): acceptance probability collapses
    to p(d) and the residual to p with d zeroed — still an exact
    p-sample marginally (module docstring)."""

    def __init__(self, k: int, max_ngram: int = 3):
        if k < 1 or max_ngram < 1:
            raise ValueError(
                f"NGramProposer needs k >= 1 and max_ngram >= 1 "
                f"(got k={k}, max_ngram={max_ngram})"
            )
        self.k = k
        self.max_ngram = max_ngram

    def propose(self, history) -> list[int]:
        """Up to `k` proposed continuation tokens for one slot's history
        (empty when no n-gram of any length recurs): the most recent
        earlier occurrence of the longest matching suffix (length
        `max_ngram` down to 1) names an implied repetition period
        `s = suffix_start - occurrence_start`, and the proposal walks
        the history forward from the occurrence's continuation, WRAPPING
        back by `s` past the end — so a period-p loop proposes the full
        k tokens however small p is (the most recent occurrence always
        sits one period from the end; without the wrap a proposal could
        never exceed p tokens). For a periodic tail the wrap is exactly
        chained re-lookup, at O(k) instead of O(k·len) after the one
        match; histories are bucket-bounded and the suffix scan is
        numpy-vectorized per candidate length."""
        h = np.asarray(history)
        m = len(h)
        for n in range(min(self.max_ngram, m - 1), 0, -1):
            suffix = h[m - n:]
            # candidate start positions of an EARLIER occurrence (the
            # continuation must have at least one token inside history)
            starts = np.flatnonzero(h[: m - n] == suffix[0])
            for j in starts[::-1]:  # most recent first
                if j + n < m and np.array_equal(h[j : j + n], suffix):
                    s = (m - n) - j  # the implied repetition period
                    out = []
                    for i in range(self.k):
                        pos = j + n + i
                        while pos >= m:
                            pos -= s
                        out.append(int(h[pos]))
                    return out
        return []


def reference_spec_decode(params, cfg: gpt.GPTConfig, ids, max_new: int,
                          eos_id: int, *, k: int, draft: str = "ngram",
                          draft_params=None, draft_cfg=None,
                          temperature: float = 0.0, top_k: int = 0,
                          seed: int = 0, max_ngram: int = 3):
    """Serial ONE-REQUEST speculative decode — the independent spelling
    the engine parity tests pin against (tests/test_spec.py): a plain
    Python loop over scalar-start `gpt.forward_cached` calls (the
    round-14 serial-cached decode layout) with the SAME `_accept_prefix`
    acceptance math, the same proposers, and the same position-keyed
    draw streams. A fixed seed must reproduce the engine's batched
    output token-for-token for the same request. Returns the emitted
    ids (prompt + generated) as an int array."""
    ids = np.asarray(ids, np.int32)
    plen = len(ids)
    total = plen + max_new + k  # + the verify scratch tail (module doc)
    buf = np.zeros((total,), np.int32)
    buf[:plen] = ids
    key = jnp.asarray(np.asarray(jax.random.PRNGKey(seed)))
    cache = gpt.init_kv_cache(cfg, 1, total)
    if plen > 1:
        p = jnp.arange(plen - 1, dtype=jnp.int32)[None, :]
        _, cache = gpt.forward_cached(
            params, cfg, jnp.asarray(buf[None, : plen - 1]), p, cache, 0
        )
    proposer = None
    d_cache = None
    if draft == "ngram":
        proposer = NGramProposer(k, max_ngram=max_ngram)
    elif draft == "model":
        d_cache = gpt.init_kv_cache(draft_cfg, 1, total)
        if plen > 1:
            p = jnp.arange(plen - 1, dtype=jnp.int32)[None, :]
            _, d_cache = gpt.forward_cached(
                draft_params, draft_cfg,
                jnp.asarray(buf[None, : plen - 1]), p, d_cache, 0,
            )
    else:
        raise ValueError(f"draft must be 'ngram' or 'model', got {draft!r}")

    cur = plen
    limit = min(plen + max_new, total - k)  # == plen + max_new
    active = cur < limit
    while active:
        if draft == "ngram":
            prop = proposer.propose(buf[:cur])
            dlen = len(prop)
            d = np.zeros((k,), np.int32)
            d[:dlen] = prop
            d = jnp.asarray(d)
            q = None
        else:
            # the serial twin of draft_propose: the 2-token catch-up
            # window first (closing the all-accept trailing-K/V gap the
            # same way the batched spelling does), then one tick per
            # remaining proposal — same `_sample_next` fold throughout
            d_list, q_list = [], []
            pv = max(cur - 2, 0)
            lg, d_cache = gpt.forward_cached(
                draft_params, draft_cfg,
                jnp.asarray([[int(buf[pv]), int(buf[cur - 1])]],
                            dtype=jnp.int32),
                jnp.asarray([[pv, cur - 1]], dtype=jnp.int32), d_cache, pv,
            )
            for i in range(k):
                if i > 0:
                    p = cur - 1 + i
                    lg, d_cache = gpt.forward_cached(
                        draft_params, draft_cfg,
                        jnp.asarray([[d_list[-1]]], dtype=jnp.int32),
                        jnp.asarray([[p]], dtype=jnp.int32), d_cache, p,
                    )
                last = lg[0, -1].astype(jnp.float32)
                if temperature > 0.0:
                    adj = _adjust_logits(last, temperature, top_k)
                    qp = jax.nn.softmax(adj, axis=-1)
                    nxt = int(_sample_next(last, cur + i, key,
                                           temperature, top_k))
                else:
                    nxt = int(jnp.argmax(last))
                    qp = jax.nn.one_hot(
                        nxt, cfg.padded_vocab_size, dtype=jnp.float32
                    )
                d_list.append(nxt)
                q_list.append(qp)
            dlen = k
            d = jnp.asarray(np.asarray(d_list, np.int32))
            q = jnp.stack(q_list)
        window = np.concatenate([[buf[cur - 1]], np.asarray(d)])
        p_ids = jnp.arange(cur - 1, cur + k, dtype=jnp.int32)[None, :]
        lg, cache = gpt.forward_cached(
            params, cfg, jnp.asarray(window[None, :], dtype=jnp.int32),
            p_ids, cache, cur - 1,
        )
        lg = lg[0].astype(jnp.float32)
        if q is None:
            q = jax.nn.one_hot(d, cfg.padded_vocab_size, dtype=jnp.float32)
        accepted, cand = _accept_prefix(
            lg, d, q, jnp.int32(dlen), key, jnp.int32(cur),
            temperature, top_k,
        )
        accepted, cand = int(accepted), np.asarray(cand)
        for j in range(accepted + 1):  # the vanilla per-token gates
            if cur >= limit:  # doesn't fit: freeze, reason "length"
                active = False
                break
            t = int(cand[j])
            if t == eos_id:  # stop BEFORE appending (reference rule)
                active = False
                break
            buf[cur] = t
            cur += 1
        if cur >= limit:
            active = False
    return buf[:cur]

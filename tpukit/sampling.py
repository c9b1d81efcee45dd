"""Greedy (argmax) text generation.

Twin of `generate` (reference utils.py:42-91): greedy decoding, at most
`max_new_tokens` (default 20) new tokens, stop *before* appending when the
model emits EOS (utils.py:67-68), decode with special tokens skipped
(utils.py:91). The reference's prompt handling — tokenize with truncation to
max_length=256 (utils.py:57) — is kept.

TPU-native redesign of the loop itself: the reference re-forwards a *growing*
sequence each step via `torch.cat` (utils.py:63-87), which under jit would
recompile at every length. Here the sequence lives in a fixed
`[1, prompt + max_new_tokens]` buffer and the whole decode loop is a single
jitted `lax.while_loop`: one compile per prompt length, zero host round-trips
inside the loop. Because attention is causal and the model is called without
a padding mask (as in the reference, utils.py:64), the trailing unwritten
buffer positions cannot influence the logits at the current position, so the
fixed-buffer decode is token-for-token equivalent to the growing-buffer one.

Unlike the reference (which re-runs the full forward per token,
utils.py:63-64), decoding defaults to a KV-cached path: prefill the prompt
once, then one-token steps against per-layer K/V buffers. The naive loop is
kept (`use_cache=False`) and the two are equivalence-tested token-for-token.
Both loops support temperature/top-k sampling (round 11 — the cached loop
previously raised on temperature>0, VERDICT r5 #5): the per-position key
fold is identical in the two loops, so a fixed seed samples the same tokens
cached and uncached.

Round 14: `generate_batch` rides the serving engine's batched KV-cached
decode (`tpukit/serve/decode.decode_loop` — per-row cursors over a
preallocated per-slot cache) instead of the retired `_decode_loop_batch`,
which re-forwarded the whole growing buffer per token: O(S) attention per
generated token now, same token-for-token parity with the serial decode,
plus temperature/top-k sampling per row.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from tpukit.model import family


@partial(
    jax.jit,
    static_argnames=("cfg", "prompt_len", "max_new_tokens", "eos_id", "temperature", "top_k"),
)
def _decode_loop(
    params, cfg, buf, prompt_len: int, max_new_tokens: int,
    eos_id: int, temperature: float = 0.0, top_k: int = 0, rng=None,
):
    """Returns (buf, final_length). buf: [1, prompt_len + max_new_tokens].

    temperature == 0 (default) is the reference's greedy argmax; > 0
    samples from softmax(logits / temperature), optionally truncated to
    the top_k candidates (beyond-parity — the reference decodes greedily
    only, utils.py:65). The step key folds the cursor into `rng`, so a
    fixed seed reproduces exactly."""
    total = buf.shape[1]
    position_ids = jnp.broadcast_to(jnp.arange(total, dtype=jnp.int32), buf.shape)

    def cond(carry):
        _, cur, done = carry
        return jnp.logical_and(~done, cur < total)

    def body(carry):
        buf, cur, _ = carry
        logits = family(cfg).forward(params, cfg, buf, position_ids)
        last = logits[0, cur - 1].astype(jnp.float32)
        next_token = _sample_next(last, cur, rng, temperature, top_k).astype(buf.dtype)
        done = next_token == eos_id
        # Only append when not EOS — the reference breaks before appending
        # (utils.py:67-68), so EOS never enters the sequence.
        new_buf = jnp.where(done, buf, buf.at[0, cur].set(next_token))
        new_cur = jnp.where(done, cur, cur + 1)
        return (new_buf, new_cur, done)

    buf, cur, _ = jax.lax.while_loop(cond, body, (buf, jnp.int32(prompt_len), jnp.bool_(False)))
    return buf, cur


@partial(
    jax.jit,
    static_argnames=("cfg", "prompt_len", "max_new_tokens", "eos_id", "temperature", "top_k"),
)
def _decode_loop_cached(
    params, cfg, buf, prompt_len: int, max_new_tokens: int,
    eos_id: int, temperature: float = 0.0, top_k: int = 0, rng=None,
):
    """KV-cached twin of `_decode_loop`: the prompt is prefilled once, then
    each step forwards ONE token against the cache — O(S) attention per
    token instead of the naive loop's O(S^2) full re-forward (the
    reference's known wart, utils.py:63-64). Token-for-token equivalent to
    the naive loop (tests/test_sampling.py).

    temperature/top_k mirror `_decode_loop` exactly (round 11, the first
    rung of the serving ladder — VERDICT r5 #5 flagged the cached path
    raising on temperature>0): the SAME per-position key fold
    (`fold_in(rng, cur)`) and the same truncate-then-categorical math, so
    a fixed seed samples the same tokens cached and uncached — the
    same-seed equivalence tests/test_sampling.py asserts. The static
    temperature==0 branch keeps the greedy decode trace byte-unchanged."""
    total = buf.shape[1]
    model = family(cfg)
    cache = model.init_kv_cache(cfg, 1, total)
    if prompt_len > 1:
        ids = buf[:, : prompt_len - 1]
        pos = jnp.arange(prompt_len - 1, dtype=jnp.int32)[None, :]
        _, cache = model.forward_cached(params, cfg, ids, pos, cache, 0)

    def cond(carry):
        _, _, cur, done = carry
        return jnp.logical_and(~done, cur < total)

    def body(carry):
        buf, cache, cur, _ = carry
        tok = jax.lax.dynamic_slice(buf, (0, cur - 1), (1, 1))
        pos = jnp.reshape(cur - 1, (1, 1)).astype(jnp.int32)
        logits, cache = model.forward_cached(params, cfg, tok, pos, cache, cur - 1)
        last = logits[0, -1].astype(jnp.float32)
        next_token = _sample_next(last, cur, rng, temperature, top_k).astype(buf.dtype)
        done = next_token == eos_id
        new_buf = jnp.where(done, buf, buf.at[0, cur].set(next_token))
        new_cur = jnp.where(done, cur, cur + 1)
        return (new_buf, cache, new_cur, done)

    buf, _, cur, _ = jax.lax.while_loop(
        cond, body, (buf, cache, jnp.int32(prompt_len), jnp.bool_(False))
    )
    return buf, cur


def _adjust_logits(last, temperature: float, top_k: int):
    """The temperature/top-k logits transform every sampler draws from:
    scale by 1/temperature, then mask everything below the k-th largest
    to -inf. Factored out of `_sample_next` (round 17) so the speculative
    verify step (tpukit/serve/spec.py) builds its target distribution
    from the SAME math — the rejection-sampling correction is only exact
    against the distribution vanilla sampling actually draws from.
    `last` is `[..., V]` f32; only `temperature > 0` callers may use it."""
    scaled = last / temperature
    if top_k > 0:
        kth = jax.lax.top_k(scaled, top_k)[0][..., -1:]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    return scaled


def _sample_next(last, cur, rng, temperature: float = 0.0, top_k: int = 0):
    """THE sampling spelling — one token from one f32 logits vector
    `last [V]` at cursor `cur`: temperature == 0 is greedy argmax (static
    branch, `rng` untouched); > 0 scales, optionally top-k-truncates
    (`_adjust_logits`), and draws `categorical(fold_in(rng, cur), ...)`.
    Every decode loop — serial naive, serial cached, and the serving
    engine's batched step (which vmaps this over slots) — calls this ONE
    function, because the cached==uncached and batched==serial parity
    guarantees are exactly the bit-for-bit agreement of this math across
    loops."""
    if temperature > 0.0:  # static branch: greedy decode trace unchanged
        scaled = _adjust_logits(last, temperature, top_k)
        return jax.random.categorical(jax.random.fold_in(rng, cur), scaled)
    return jnp.argmax(last, axis=-1)


def _cached_decode_exact(cfg) -> bool:
    """True when the KV-cached decode is token-for-token the full-reforward
    decode: the model's own statement (`cached_decode_exact` of its family —
    dense GPT models and dropless expert layers are, capacity'd buffer
    dispatches are not; rationale at gpt.cached_decode_exact)."""
    return family(cfg).cached_decode_exact(cfg)


def _replicate_like(params, buf):
    """Place the decode buffer replicated on the params' mesh. Plain
    `jnp.asarray` would commit it to a single device, which is invalid for
    a multi-host SPMD decode (every process must hold the same global,
    fully-addressable-per-host value)."""
    from jax.sharding import NamedSharding, PartitionSpec

    from tpukit.mesh import place_host_array

    sh = next(
        (
            leaf.sharding
            for leaf in jax.tree_util.tree_leaves(params)
            if isinstance(getattr(leaf, "sharding", None), NamedSharding)
        ),
        None,
    )
    if sh is None:
        return jnp.asarray(buf)
    return place_host_array(buf, NamedSharding(sh.mesh, PartitionSpec()))


def generate(
    params,
    cfg,
    prompt: str,
    tokenizer,
    max_new_tokens: int = 20,
    use_cache: bool | None = None,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
) -> str:
    """Decode a continuation of `prompt`. Default is the reference's greedy
    argmax; `temperature > 0` switches to softmax sampling (optionally
    `top_k`-truncated), reproducible under `seed`. See module docstring."""
    # The reference truncates prompts at a hard 256 (utils.py:57). Also cap
    # at the position-embedding table so the whole buffer (prompt + new
    # tokens) stays in-range — beyond it, position lookups would silently
    # clamp to the last learned position instead of erroring.
    max_prompt = min(256, family(cfg).max_context(cfg) - max_new_tokens)
    if max_prompt < 1:
        raise ValueError(
            f"max_new_tokens={max_new_tokens} leaves no room for a prompt "
            f"within the model's longest context "
            f"({family(cfg).max_context(cfg)})"
        )
    encoded = tokenizer([prompt], truncation=True, max_length=max_prompt)
    ids = np.asarray(encoded["input_ids"][0], dtype=np.int32)
    prompt_len = int(ids.shape[0])

    buf = np.zeros((1, prompt_len + max_new_tokens), dtype=np.int32)
    buf[0, :prompt_len] = ids

    eos = tokenizer.eos_token_id
    if use_cache is None:
        # Measured on v5e: the cached path wins on long buffers (O(S) vs
        # O(S^2) per token) but its per-step cache updates cost more than
        # the naive re-forward saves on short ones. MoE models with a
        # capacity'd buffer dispatch default to the exact full-reforward
        # path — the cached decode routes each chunk with its own
        # expert-capacity window (gpt._apply_moe_ffn docstring); dropless
        # "pallas" MoE is chunk-composition-independent, so its cached
        # decode is exact and auto-resolves like a dense model (round 14).
        use_cache = buf.shape[1] >= 512 and _cached_decode_exact(cfg)
    if use_cache:
        # Round 11 (first rung of the serving ladder, ROADMAP #1): the
        # cached loop samples too — same key fold, same truncation math as
        # the naive loop, so a fixed seed decodes the same tokens either
        # way (the r5 #5 raise is gone; same-seed equivalence is tested).
        buf, length = _decode_loop_cached(
            params, cfg, _replicate_like(params, buf), prompt_len,
            max_new_tokens, int(eos), temperature=float(temperature),
            top_k=min(int(top_k), cfg.padded_vocab_size),
            rng=_replicate_like(params, np.asarray(jax.random.PRNGKey(seed)))
            if temperature > 0.0
            else None,
        )
    else:
        buf, length = _decode_loop(
            params, cfg, _replicate_like(params, buf), prompt_len,
            max_new_tokens, int(eos), temperature=float(temperature),
            # lax.top_k rejects k beyond the logits width — clamp
            top_k=min(int(top_k), cfg.padded_vocab_size),
            rng=_replicate_like(params, np.asarray(jax.random.PRNGKey(seed)))
            if temperature > 0.0
            else None,
        )
    out_ids = np.asarray(buf)[0, : int(length)]
    return tokenizer.decode(out_ids, skip_special_tokens=True)


def generate_batch(
    params,
    cfg,
    prompts: list[str],
    tokenizer,
    max_new_tokens: int = 20,
    temperature: float = 0.0,
    top_k: int = 0,
    seed: int = 0,
) -> list[str]:
    """Decode continuations of every prompt in ONE jitted call — the
    KV-cached batched decode (`tpukit/serve/decode.decode_loop`, round 14):
    one full-width prefill, then one-token-per-row cached steps in a single
    `lax.while_loop`. This retired the round-4 `_decode_loop_batch`, which
    re-forwarded the whole growing buffer every token — O(S^2) attention
    per generated token vs O(S) here.

    Prompts are right-padded into a common `[N, max_prompt + new]` buffer
    with per-row (traced) lengths, so any prompt set of the same max length
    reuses one compiled program. Greedy output is token-for-token identical
    to `generate` called per prompt (tests/test_sampling.py), and
    `temperature`/`top_k`/`seed` sample per row with the same
    `fold_in(key, cursor)` fold as the serial loops — a fixed seed decodes
    each row exactly as `generate(..., seed=seed)` would. For MoE configs
    the batched decode equals the serial CACHED decode always; it equals
    the full-reforward decode exactly when `_cached_decode_exact(cfg)`
    (dense, or dropless-pallas MoE — gpt._apply_moe_ffn docstring)."""
    if not prompts:
        return []
    max_prompt = min(256, family(cfg).max_context(cfg) - max_new_tokens)
    if max_prompt < 1:
        raise ValueError(
            f"max_new_tokens={max_new_tokens} leaves no room for a prompt "
            f"within the model's longest context "
            f"({family(cfg).max_context(cfg)})"
        )
    encoded = tokenizer(list(prompts), truncation=True, max_length=max_prompt)
    ids = [np.asarray(row, dtype=np.int32) for row in encoded["input_ids"]]
    lens = np.asarray([r.shape[0] for r in ids], dtype=np.int32)

    buf = np.zeros((len(ids), int(lens.max()) + max_new_tokens), dtype=np.int32)
    for r, row in enumerate(ids):
        buf[r, : row.shape[0]] = row

    from tpukit.serve.decode import decode_loop

    buf, lengths = decode_loop(
        params, cfg, _replicate_like(params, buf),
        _replicate_like(params, lens), max_new_tokens,
        int(tokenizer.eos_token_id), temperature=float(temperature),
        top_k=min(int(top_k), cfg.padded_vocab_size),
        rng=_replicate_like(params, np.asarray(jax.random.PRNGKey(seed)))
        if temperature > 0.0
        else None,
    )
    buf, lengths = np.asarray(buf), np.asarray(lengths)
    return [
        tokenizer.decode(buf[r, : int(lengths[r])], skip_special_tokens=True)
        for r in range(buf.shape[0])
    ]

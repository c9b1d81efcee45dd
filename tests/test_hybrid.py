"""The hybrid state-space family (tpukit/model/hybrid.py) at a tiny size on
the CPU, against the plain float32 reference the benchmark keeps
(benchmark/reference/jamba_block.py, which imports nothing from tpukit): the
whole forward, chunked prefill then decode through the paged cache and the
recurrent state, the state's invariants (padding, masked lanes, a reused
slot, information over a thousand tokens), the model under the same
ServeEngine as the other two families, the byte counts, and the named
refusals."""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tpukit.model import GPTConfig, HybridConfig, ServedOnlyError, family, gpt, hybrid, latent
from tpukit.serve import paged
from tpukit.serve.engine import Request, ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 4, 8
M, A = hybrid.MAMBA, hybrid.ATTENTION


@pytest.fixture(scope="module")
def ref():
    return common.load_by_name("reference", "jamba_block", ROOT)


def hf_keys(cfg: HybridConfig, period: int = 3, offset: int = 1) -> dict:
    """The published-key view of a HybridConfig, as a configuration file
    holds it (the layer pattern as offset and period)."""
    assert cfg.layer_types == tuple(A if i % period == offset else M for i in range(cfg.num_layers))
    return dict(num_hidden_layers=cfg.num_layers, rms_norm_eps=cfg.norm_eps, attn_layer_period=period, attn_layer_offset=offset,
                hidden_size=cfg.dim, mamba_expand=cfg.expand, mamba_d_state=cfg.d_state,
                mamba_dt_rank=cfg.dt_rank, mamba_d_conv=cfg.d_conv)


@pytest.fixture(scope="module")
def model():
    cfg = hybrid.tiny_config()
    return cfg, hybrid.init_params(jax.random.PRNGKey(3), cfg)


def _ids(n: int, seed: int = 1, vocab: int = 97) -> np.ndarray:
    return np.random.default_rng(seed).integers(0, vocab, size=n).astype(np.int32)


def test_forward_logits_match_the_reference(model, ref):
    cfg, params = model
    ids = _ids(37)
    got = hybrid.forward(params, cfg, jnp.asarray(ids)[None], page_size=PAGE)[0]
    want = ref.logits(params, jnp.asarray(ids), hf=hf_keys(cfg))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5, rtol=1e-5)


_forward_cached = jax.jit(hybrid.forward_cached, static_argnums=1)


def _cache(cfg, slots=3, per=16):
    cache = hybrid.init_paged_cache(cfg, slots * per + 1, PAGE, per, slots, "f32")
    return dict(cache, bt=jnp.asarray(1 + np.arange(slots * per, dtype=np.int32).reshape(slots, per)))


def _prefill(params, cfg, cache, lanes, seqs, prompt_len, chunk=CHUNK):
    """The lanes' prompts through the cache as `decode.prefill_chunk_paged`
    runs them (one admit batch, page-aligned chunks, the last one ragged).
    Returns `(cache, the first lane's logits of every prompt position)`."""
    out = []
    for start in range(0, prompt_len, chunk):
        n = min(chunk, prompt_len - start)
        rows = np.zeros((len(lanes), chunk), np.int32)
        rows[:, :n] = np.stack(seqs)[:, start:start + n]
        sub = hybrid.select_lanes(cache, jnp.asarray(lanes), jnp.full((len(lanes),), prompt_len, jnp.int32))
        pos = start + jnp.arange(chunk, dtype=jnp.int32)[None].repeat(len(lanes), 0)
        lg, sub = _forward_cached(params, cfg, jnp.asarray(rows), pos, sub, jnp.full((len(lanes),), start, jnp.int32))
        cache = hybrid.merge_lanes(cache, sub)
        out.append(np.asarray(lg[0, :n]))
    return cache, np.concatenate(out)


def _tick(params, cfg, cache, lane_tokens: dict, slots=3):
    """One decode tick: `{lane: (token, position)}` live, every other lane
    masked. Returns `(cache, {lane: logits})`."""
    tok, pos, live = np.zeros((slots,), np.int32), np.zeros((slots,), np.int32), np.zeros((slots,), bool)
    for lane, (t, p) in lane_tokens.items():
        tok[lane], pos[lane], live[lane] = t, p, True
    lg, cache = _forward_cached(params, cfg, jnp.asarray(tok)[:, None], jnp.asarray(pos)[:, None], cache,
                                jnp.asarray(pos), jnp.asarray(live))
    return cache, {lane: np.asarray(lg[lane, 0]) for lane in lane_tokens}


def test_chunked_prefill_with_a_ragged_last_chunk_then_decode_match_the_full_forward(model, ref):
    """Two lanes in one admit batch, a 29-token prompt (chunks of 8, the last
    of 5), then decode ticks from the prompt's last token (the engine's first
    tick forwards it again; the state takes it then) with a third lane
    masked: every position's logits are the full forward's."""
    cfg, params = model
    seq, other = _ids(37, 1), _ids(37, 2)
    want = np.asarray(ref.logits(params, jnp.asarray(seq), hf=hf_keys(cfg)))
    cache, prompt = _prefill(params, cfg, _cache(cfg), [1, 2], [seq, other], 29)
    np.testing.assert_allclose(prompt, want[:29], atol=2e-5, rtol=1e-5)
    for t in range(28, 37):
        cache, lg = _tick(params, cfg, cache, {1: (seq[t], t), 2: (other[t], t)})
        np.testing.assert_allclose(lg[1], want[t], atol=2e-5, rtol=1e-5)


def test_padding_and_masked_lanes_leave_the_state_bit_equal(model):
    cfg, params = model
    cache = _cache(cfg)
    seq = _ids(20)
    a, _ = _prefill(params, cfg, cache, [0], [seq], 13)
    noisy = seq.copy()
    noisy[13:] = _ids(7, 9)  # different padding after the prompt
    b, _ = _prefill(params, cfg, cache, [0], [noisy], 13)
    for key in ("ssm", "conv"):
        assert np.array_equal(np.asarray(a[key]), np.asarray(b[key])), key
    # the state stopped before the prompt's last token: a lane with a 12-token prompt padded by token 13 holds the same
    c, _ = _prefill(params, cfg, cache, [0], [seq], 12 + 1)
    d, _ = _prefill(params, cfg, cache, [0], [np.concatenate([seq[:12], _ids(8, 4)])], 12 + 1)
    assert np.array_equal(np.asarray(c["ssm"]), np.asarray(d["ssm"]))
    # a decode tick leaves a masked lane's state as it was
    e, _ = _tick(params, cfg, a, {1: (5, 0)})
    for key in ("ssm", "conv"):
        assert np.array_equal(np.asarray(e[key])[:, 0] if key == "ssm" else np.asarray(e[key])[:, :, 0],
                              np.asarray(a[key])[:, 0] if key == "ssm" else np.asarray(a[key])[:, :, 0])


def test_a_chunk_forwarded_a_group_of_lanes_at_a_time_is_bit_equal(model, monkeypatch):
    """A prefill chunk of more lanes than `PREFILL_TOKENS` allows at once runs
    its lanes a group at a time (the branch the engine's 32- and 128-lane
    admit batches take at the published size): two chunks, the last ragged,
    four lanes of their own lengths in scattered slots, forwarded in groups
    of one and of two give the logits, the state, the convolution's inputs
    and the pages of one pass over all four, bit for bit."""
    cfg, params = model
    lanes, seqs, lens = [3, 0, 2, 1], [_ids(16, 30 + i) for i in range(4)], [13, 16, 9, 11]

    def through(prefill_tokens):
        monkeypatch.setattr(hybrid, "PREFILL_TOKENS", prefill_tokens)
        fwd = jax.jit(hybrid.forward_cached, static_argnums=1)  # traced afresh: the constant is read at trace time
        cache, logits = _cache(cfg, slots=4), []
        for start in (0, CHUNK):
            sub = hybrid.select_lanes(cache, jnp.asarray(lanes), jnp.asarray(lens, jnp.int32))
            rows = jnp.asarray(np.stack([s[start:start + CHUNK] for s in seqs]))
            pos = start + jnp.arange(CHUNK, dtype=jnp.int32)[None].repeat(4, 0)
            lg, sub = fwd(params, cfg, rows, pos, sub, jnp.full((4,), start, jnp.int32))
            cache = hybrid.merge_lanes(cache, sub)
            logits.append(np.asarray(lg))
        return np.stack(logits), {k: np.asarray(cache[k]) for k in ("ssm", "conv", "kv")}

    whole = through(4 * CHUNK)
    for tokens in (CHUNK, 2 * CHUNK):  # groups of one lane and of two
        logits, cache = through(tokens)
        assert np.array_equal(logits, whole[0]), tokens
        for key in ("ssm", "conv", "kv"):
            assert np.array_equal(cache[key], whole[1][key]), (tokens, key)


def test_a_reused_slot_gives_the_logits_a_fresh_cache_gives(model):
    """A slot that served one request and then a new one from position 0: the
    new request's logits are bit-equal to a fresh cache's (its state starts
    from zeros, not from the last request's)."""
    cfg, params = model
    first, second = _ids(30, 5), _ids(24, 6)
    used, _ = _prefill(params, cfg, _cache(cfg), [1], [first], 30)
    used, _ = _tick(params, cfg, used, {1: (first[29], 29)})
    used = dict(used, bt=_cache(cfg)["bt"])
    reused, lg_reused = _prefill(params, cfg, used, [1], [second], 17)
    fresh, lg_fresh = _prefill(params, cfg, _cache(cfg), [1], [second], 17)
    assert np.array_equal(lg_reused, lg_fresh)
    _, a = _tick(params, cfg, reused, {1: (second[16], 16)})
    _, b = _tick(params, cfg, fresh, {1: (second[16], 16)})
    assert np.array_equal(a[1], b[1])


def test_a_token_a_thousand_positions_back_moves_the_last_logits():
    """On a preset of Mamba layers only (no attention to carry it), a token
    perturbed 1,000 positions before the last moves the last position's
    logits: the state carries information that far, as the seeded decay
    (`A_log = log(n + 1)`, `dt` down to 1e-3) says it should."""
    cfg = hybrid.tiny_config(layer_types=(M, M, M))
    params = hybrid.init_params(jax.random.PRNGKey(7), cfg)
    ids = _ids(1100, 8)
    other = ids.copy()
    other[99] = (ids[99] + 1) % cfg.vocab_size
    a = hybrid.forward(params, cfg, jnp.asarray(np.stack([ids, other, ids])), page_size=PAGE)[:, -1]
    assert np.array_equal(np.asarray(a[0]), np.asarray(a[2]))  # the same sequence twice: no noise to mistake
    assert float(jnp.max(jnp.abs(a[0] - a[1]))) > 1e-5  # 2.4e-4 of logits of 5: a few slow channels carry it


@pytest.fixture(scope="module")
def served(model):
    cfg, params = model
    sv = ServeConfig(slots=3, buckets=(16, 32), max_len=48, max_new_tokens=10, page_size=PAGE, kv_dtype="f32",
                     prefill_chunk=CHUNK, decode_quantum=2)
    from tpukit.obs.trace import TraceRecorder

    tracer = TraceRecorder(1 << 14)
    engine = ServeEngine(params, cfg, sv, eos_id=96, tracer=tracer)
    reqs = [Request(rid=i, ids=tuple(int(t) for t in _ids(9 + 3 * i, 20 + i)), max_new_tokens=6 + i % 4, seed=0,
                    arrival_s=0.0) for i in range(7)]
    comps = engine.run(reqs)
    return engine, comps, tracer


def test_the_engine_serves_the_reference_argmax_tokens(served, model, ref):
    cfg, params = model
    _, comps, _ = served
    assert len(comps) == 7
    for c in comps:
        ids = np.asarray(c.ids)
        want = np.asarray(ref.logits(params, jnp.asarray(ids), hf=hf_keys(cfg))).argmax(-1)
        assert ids[c.prompt_len:].tolist() == want[c.prompt_len - 1: len(ids) - 1].tolist(), c.rid


def test_state_bytes_and_kv_bytes_by_hand(served, model):
    cfg, _ = model
    engine, _, tracer = served
    # 3 Mamba layers x (4 x 128 float32 scan state + 3 x 128 float32 convolution inputs)
    assert hybrid.slot_state_bytes(cfg) == 3 * (4 * 128 * 4 + 3 * 128 * 4) == 10752
    page_bytes = PAGE * 2 * 16 * 4  # k || v of one head of 16, float32
    assert hybrid.page_kinds(cfg, PAGE, "f32")[0].page_bytes == page_bytes
    assert engine.kv_bytes == paged.pool_bytes(cfg, engine.num_pages, PAGE, "f32", slots=3) == (
        2 * engine.num_pages * page_bytes + 3 * 10752)
    quanta = [ev for ev in tracer.snapshot() if ev["ev"] == "quantum"]
    assert quanta
    for q in quanta:  # pages held, both attention layers, and the state of every live lane
        live = q["decoding"] + q["prefilling"]
        assert (q["kv_bytes"] - live * 10752) % (2 * page_bytes) == 0 and q["kv_bytes"] >= live * 10752
        # the gauges are the last tick's live lanes': none live, nothing held and no state to read
        assert q["state_bytes"] % 10752 == 0 and 0 <= q["ssm_state_absmax"] < 1e3
        assert (q["state_bytes"] > 0) == (q["ssm_state_absmax"] > 0)
    assert any(q["state_bytes"] > 0 for q in quanta)


def test_the_named_refusals():
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer

    cfg = hybrid.tiny_config()
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    with pytest.raises(ServedOnlyError, match="served only"):  # training
        create_train_state(jax.random.PRNGKey(0), cfg, make_optimizer(1e-4), SingleDevice())
    with pytest.raises(ServedOnlyError):
        hybrid.forward(params, cfg, jnp.zeros((1, 8), jnp.int32), deterministic=False)
    with pytest.raises(ValueError, match="speculation"):
        ServeEngine(params, cfg, ServeConfig(slots=2, buckets=(16,), draft="ngram"), eos_id=96)
    with pytest.raises(ValueError, match="ring"):
        ServeEngine(params, cfg, ServeConfig(slots=2, buckets=(16,)), eos_id=96)
    with pytest.raises(ServedOnlyError, match="paged cache only"):
        hybrid.init_kv_cache(cfg, 2, 16)
    # prefix sharing: identical prompts share no page, and the page handoff refuses the state
    sv = ServeConfig(slots=2, buckets=(16, 32), max_len=48, max_new_tokens=4, page_size=PAGE, kv_dtype="f32",
                     prefill_chunk=CHUNK)
    engine = ServeEngine(params, cfg, sv, eos_id=96)
    same = tuple(range(1, 25))
    comps = engine.run([Request(rid=i, ids=same, max_new_tokens=4, seed=0, arrival_s=0.0) for i in range(3)])
    assert engine.allocator.stats.prefix_hits == 0 and engine.allocator.registered_pages() == 0
    assert len({tuple(int(t) for t in c.ids) for c in comps}) == 1
    with pytest.raises(NotImplementedError, match="prefix"):
        engine.adopt_prefilled(Request(rid=9, ids=same, max_new_tokens=4, seed=0, arrival_s=0.0), [1, 2], 0,
                               0.0, 0.0, np.zeros((2,), np.uint32))


def test_family_dispatch_and_the_published_config():
    cfg = hybrid.tiny_config()
    assert family(cfg) is hybrid and family(GPTConfig()) is gpt and family(latent.tiny_config()) is latent
    config = json.loads((ROOT / "benchmark" / "configs" / "jamba2-3b.json").read_text())
    real = hybrid.config_from_hf(config)
    assert [i for i, t in enumerate(real.layer_types) if t == A] == [7, 21] and real.layers_of(M) == 26
    assert (real.inner, real.head_dim, hybrid.kv_heads(real), real.d_state, real.dt_rank) == (5120, 128, 1, 16, 160)
    shapes = jax.eval_shape(lambda: hybrid.init_params(jax.random.PRNGKey(0), real))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == config["held"]["parameters"]
    assert sum(int(np.prod(x.shape)) * x.dtype.itemsize for x in jax.tree.leaves(shapes)) == config["held"]["bytes"]
    assert hybrid.slot_state_bytes(real) == 9_318_400
    with pytest.raises(NotImplementedError, match="num_experts"):
        hybrid.config_from_hf(dict(config, num_experts=16))
    with pytest.raises(NotImplementedError, match="K/V head"):
        hybrid.config_from_hf(dict(config, num_key_value_heads=8))
    with pytest.raises(ValueError, match="Jamba"):
        hybrid.config_from_hf(dict(config, model_type="mamba"))


# The family's serve programs at `tiny_config()`. The decode ticks' were
# recorded on the tree at commit adc8f13, before the key-block walk it shares
# with the latent family (`latent.walk_key_blocks`) took its selection
# operand: a walk with no selection lowers to the text it lowered to then.
# The prefill chunks' were recorded anew when the chunk's scan became the
# `selective_scan` kernel (interpret mode here); a tick's one-token step does
# not run it.
HYBRID_PROGRAM_HASHES = {
    ("f32", "decode_step", 1): "979e5ef23f3d0c74", ("f32", "decode_step", 4): "6f8a5c235d1b5d86",
    ("f32", "prefill_chunk_paged", 0): "edc4fa5728349f16",
    ("bf16", "decode_step", 1): "18d3065435b86a90", ("bf16", "decode_step", 4): "67c8640feef06255",
    ("bf16", "prefill_chunk_paged", 0): "2e9744580f09afe5",
}


@pytest.mark.parametrize("kv,program,steps", sorted(HYBRID_PROGRAM_HASHES))
def test_hybrid_serve_programs_are_byte_equal_to_the_parents(kv, program, steps):
    import hashlib

    from tpukit.serve import decode as serve_decode

    cfg = hybrid.tiny_config()
    params = hybrid.init_params(jax.random.PRNGKey(0), cfg)
    n, per = 4, 6
    z = lambda shape, dt: jnp.zeros(shape, dt)  # noqa: E731
    cache = hybrid.init_paged_cache(cfg, n * per + 1, PAGE, per, n, kv)
    state = (z((n, per * PAGE), jnp.int32), cache, z((n,), jnp.int32), z((n,), bool), z((n,), jnp.int32),
             z((n, 2), jnp.uint32))
    if program == "decode_step":
        lowered = serve_decode.decode_step.lower(params, cfg, *state, 96, 0.0, 0, None, steps=steps)
    else:
        lowered = serve_decode.prefill_chunk_paged.lower(
            params, cfg, *state, z((2,), jnp.int32), z((2, CHUNK), jnp.int32), z((2,), jnp.int32), z((2,), bool),
            z((2,), jnp.int32), z((2,), jnp.int32), z((2, 2), jnp.uint32))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] == HYBRID_PROGRAM_HASHES[kv, program, steps]

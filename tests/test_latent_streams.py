"""The latent family's second published form (grouped differential heads on
the latent, no key selection, a multi-stream residual under hyper-connections,
PolyNorm experts) at a tiny size on the CPU, against the plain float32
reference the benchmark keeps (benchmark/reference/motif3_block.py, which
imports nothing from tpukit): forward logits, chunked prefill then decode
through the paged cache, the expert layer's shares, the Sinkhorn maps, the
differential combine, the key-blocked attention, and the model under the same
ServeEngine as the other two."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tpukit.model import LatentConfig, family, latent
from tpukit.ops import moe_dispatch
from tpukit.serve import paged
from tpukit.serve.engine import Request, ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 4, 8
HELD_LAYERS = [0, 4, 5, 6, 7]  # published indices: one dense window layer, then window, window, window, full


@pytest.fixture(scope="module")
def ref():
    return common.load_by_name("reference", "motif3_block", ROOT)


def hf_keys(cfg: LatentConfig) -> dict:
    """The published-key view of a LatentConfig, as a configuration file holds it."""
    s = cfg.full
    return dict(
        rms_norm_eps=cfg.norm_eps, sliding_window=cfg.window_size, sliding_window_period=4,
        held_layers=HELD_LAYERS, num_hidden_layers=cfg.num_layers, n_dense_first_layers=2,
        num_attention_heads=s.heads, num_key_value_heads=s.kv_heads, num_noise_heads=s.noise_heads,
        head_dim=s.nope + s.rope, qk_rope_head_dim=s.rope, v_head_dim=s.v, kv_lora_rank=s.kv_rank,
        rope_theta=s.theta, swa_rope_theta=cfg.window.theta, experts_top_k=cfg.experts_per_token,
        route_scale=cfg.route_scale, polynorm_output_scale=cfg.poly_scale,
        polynorm_bias_clamp=cfg.poly_bias_clamp, mhc_expansion_rate=cfg.streams,
        mhc_sinkhorn_iters=cfg.sinkhorn_iters, hidden_clamp=cfg.hidden_clamp)


@pytest.fixture(scope="module", params=[0, 6], ids=["experts0-1", "experts6-7"])
def model(request):
    cfg = latent.tiny_diff_config(expert_lo=request.param)
    return cfg, latent.init_params(jax.random.PRNGKey(3 + request.param), cfg)


def test_the_preset_is_the_published_pattern_at_toy_widths(ref):
    cfg = latent.tiny_diff_config()
    assert cfg.layer_types == tuple(latent.FULL if ref.layer_is_full(hf_keys(cfg), i) else latent.WINDOW
                                    for i in HELD_LAYERS)
    assert (cfg.full.groups, cfg.full.heads // cfg.full.groups, cfg.full.out_heads) == (2, 5, 8)
    assert (cfg.streams, cfg.window_size, cfg.n_experts, cfg.experts_held, cfg.index_topk) == (4, 9, 16, 2, 0)
    assert family(cfg) is latent


def test_forward_logits_match_the_reference(model, ref):
    cfg, params = model
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 0, cfg.vocab_size)
    served = jax.jit(lambda p, i: latent.forward(p, cfg, i, page_size=PAGE))(params, ids)
    for b in range(2):
        exact = ref.logits(params, ids[b], hf=hf_keys(cfg), expert_lo=cfg.expert_lo)
        np.testing.assert_allclose(np.asarray(served[b]), np.asarray(exact), atol=2e-5)


def one_lane_cache(cfg, pages: int):
    ring = latent.page_kinds(cfg, PAGE, "f32")[1].ring_pages
    cache = latent.init_paged_cache(cfg, {"bt": pages + 1, "bt_w": ring + 1}, PAGE, pages, 1, "f32")
    return dict(cache, bt=1 + jnp.arange(pages, dtype=jnp.int32)[None],
                bt_w=1 + jnp.arange(ring, dtype=jnp.int32)[None])


def test_chunked_prefill_then_decode_through_the_cache_matches_the_reference(model, ref, monkeypatch):
    """Logits, not tokens: a prompt in page-aligned chunks (the last one
    padded), then ticks, through one lane's paged cache whose window ring
    wraps many times and whose full layer is walked in key blocks of 8 (two
    pages: the context passes six of them)."""
    monkeypatch.setattr(latent, "KEY_BLOCK", 8)
    cfg, params = model
    prompt_len, total, pages = 21, 52, 14
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (total,), 0, cfg.vocab_size))
    exact = np.asarray(ref.logits(params, jnp.asarray(ids), hf=hf_keys(cfg), expert_lo=cfg.expert_lo))
    step = jax.jit(lambda p, c, i, pos, s: latent.forward_cached_tapped(p, cfg, i, pos, c, s))
    cache = one_lane_cache(cfg, pages)
    valid = jnp.asarray([prompt_len], jnp.int32)
    got = []
    for start in range(0, prompt_len, CHUNK):
        seg = ids[start:min(start + CHUNK, prompt_len)]
        row = np.zeros((1, CHUNK), np.int32)
        row[0, :len(seg)] = seg
        pos = start + jnp.arange(CHUNK, dtype=jnp.int32)[None]
        logits, cache, sel = step(params, dict(cache, valid=valid), jnp.asarray(row), pos,
                                  jnp.asarray([start], jnp.int32))
        cache.pop("valid")
        assert sel == []  # no layer selects keys
        got.append(np.asarray(logits[0, :len(seg)]))
    assert float(cache["health"][0]) == 0.0  # a prefill chunk is no tick: the gauges are the ticks'
    for t in range(prompt_len, total):
        logits, cache, _ = step(params, cache, jnp.asarray(ids[None, t:t + 1]), jnp.asarray([[t]], jnp.int32),
                                jnp.asarray([t], jnp.int32))
        got.append(np.asarray(logits[0]))
    np.testing.assert_allclose(np.concatenate(got), exact, atol=3e-5)
    assert "idx" not in cache and int(cache["moe_rows"][0]) > 0
    err, lam = (float(x) for x in cache["health"])
    assert 0 < err < 1e-3 and 0 < lam < 1


def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer_with_polynorm_experts(ref):
    """Eight chips of two experts each: their routed parts, plus the shared
    expert counted once, add up to what the uncut layer (all 16 experts on
    one chip) gives; and the reference's layer agrees with each share. Each
    expert's PolyNorm has weights of its own, so a share that normalised with
    another expert's would not add up."""
    whole = latent.tiny_diff_config(experts_held=16)
    params = latent.init_params(jax.random.PRNGKey(5), whole)
    moe = params["layers"][2]["moe"]
    assert "select_bias" not in moe and moe["experts"]["poly_w"].shape == (16, 3)
    assert float(jnp.abs(moe["experts"]["poly_b"]).max()) > whole.poly_bias_clamp  # the clamp is live
    x = jax.random.normal(jax.random.PRNGKey(6), (37, whole.dim), jnp.float32)
    uncut, _ = latent._expert_layer(moe, whole, x, None)
    idx, gates = moe_dispatch.sigmoid_topk_route(x, moe["router"], None, whole.experts_per_token, whole.route_scale)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), whole.route_scale, atol=1e-6)  # over ALL the chosen, x2
    shared = latent._gated_ffn(moe["shared"], x, jnp.float32, latent._activation(moe["shared"], whole))
    total, rows_seen = shared, 0
    for lo in range(0, 16, 2):
        share_cfg = whole.replace(experts_held=2, expert_lo=lo)
        share = dict(moe, experts=jax.tree.map(lambda w: w[lo:lo + 2], moe["experts"]))
        got, rows = latent._expert_layer(share, share_cfg, x, None)
        exact = ref.expert_layer(x, share, hf=hf_keys(whole), expert_lo=lo)
        np.testing.assert_allclose(np.asarray(got), np.asarray(exact), atol=2e-5)
        total, rows_seen = total + (got - shared), rows_seen + int(rows.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert rows_seen == 37 * whole.experts_per_token  # dropless: every (token, choice) pair computed exactly once


def test_the_stream_mixing_matrix_is_doubly_stochastic_and_the_gauge_reports_it():
    cfg = latent.tiny_diff_config()
    p = latent.init_params(jax.random.PRNGKey(0), cfg)["layers"][1]["mhc1"]
    x = jax.random.normal(jax.random.PRNGKey(4), (3, 5, cfg.streams, cfg.dim), jnp.float32)
    health = {"row_err": [], "lambda": []}
    u, (res, post) = latent._read_streams(p, cfg, x, health)
    assert u.shape == (3, 5, cfg.dim) and res.shape == (4, 4, 3, 5) and post.shape == (3, 5, 4)
    np.testing.assert_allclose(np.asarray(res.sum(axis=0)), 1.0, atol=1e-3)  # columns
    np.testing.assert_allclose(np.asarray(res.sum(axis=1)), 1.0, atol=1e-3)  # rows
    assert float(jnp.abs(res - 0.25).max()) > 0.05 and float(res.min()) > 0  # live, not uniform, not an identity
    np.testing.assert_allclose(np.asarray(health["row_err"][0]), np.abs(np.asarray(res.sum(axis=1)) - 1.0).max(axis=0))
    # one iteration is not enough: the gauge would say so
    once = latent.sinkhorn(jnp.exp(jax.random.normal(jax.random.PRNGKey(5), (4, 4, 7))), 1)
    assert float(jnp.abs(once.sum(axis=1) - 1.0).max()) > 1e-2
    # a write-back with H_res = I, H_post = 1 is the plain residual
    eye = jnp.broadcast_to(jnp.eye(4)[:, :, None, None], (4, 4, 3, 5))
    y = jax.random.normal(jax.random.PRNGKey(6), (3, 5, cfg.dim))
    np.testing.assert_allclose(np.asarray(latent._write_streams(cfg, x, (eye, jnp.ones((3, 5, 4))), y)),
                               np.asarray(x + y[:, :, None, :]), atol=1e-6)


def test_lambda_zero_is_plain_grouped_latent_attention_and_a_live_lambda_differs():
    spec = latent.tiny_diff_config().full
    kv_b = jax.random.normal(jax.random.PRNGKey(1), (spec.kv_rank, spec.groups, spec.nope + spec.v))
    o_lat = jax.random.normal(jax.random.PRNGKey(2), (6, spec.heads, spec.kv_rank))
    lam = jax.nn.sigmoid(jax.random.normal(jax.random.PRNGKey(3), (6, spec.out_heads)))
    plain = latent._diff_values(o_lat, jnp.zeros_like(lam), kv_b, spec, jnp.float32)
    # every signal head's own weighted latent through its group's value projection, the noise heads dropped
    per = spec.heads // spec.groups
    signal = [h for h in range(spec.heads) if h % per < per - 1]
    want = jnp.einsum("qhc,chv->qhv", o_lat[:, signal], jnp.repeat(kv_b[..., spec.nope:], per - 1, axis=1))
    np.testing.assert_allclose(np.asarray(plain), np.asarray(want), atol=1e-5)
    live = latent._diff_values(o_lat, lam, kv_b, spec, jnp.float32)
    noise = jnp.einsum("qgc,cgv->qgv", o_lat[:, per - 1::per], kv_b[..., spec.nope:])
    want = want - lam[..., None] * jnp.repeat(noise, per - 1, axis=1)  # outputs subtracted = latents subtracted
    np.testing.assert_allclose(np.asarray(live), np.asarray(want), atol=1e-5)
    assert float(jnp.abs(live - plain).max()) > 0.1


@pytest.mark.parametrize("key_block", [4, 8, 512])
def test_key_blocked_attention_equals_the_unblocked_one(monkeypatch, key_block):
    """`_attend_paged` over a lane's scattered pages, in blocks of one page,
    of two, and of the whole table, against one softmax over every key."""
    monkeypatch.setattr(latent, "KEY_BLOCK", key_block)
    spec = latent.tiny_diff_config().full
    lanes, pages, nq = 3, 6, 2
    rng = np.random.default_rng(0)
    pool = jnp.asarray(rng.normal(size=(2, lanes * pages + 1, PAGE, spec.row)), jnp.float32)
    bt = jnp.asarray(1 + rng.permutation(lanes * pages).reshape(lanes, pages), jnp.int32)
    q_cat = jnp.asarray(rng.normal(size=(lanes, nq, spec.heads, spec.row)), jnp.float32)
    q_pos = jnp.asarray([[0, 1], [9, 10], [22, 23]], jnp.int32)
    got = latent._attend_paged(q_cat, pool, 1, bt, q_pos, spec, jnp.float32)
    keys = pool[1][bt].reshape(lanes, pages * PAGE, spec.row)
    scores = jnp.einsum("bqhr,bkr->bqhk", q_cat, keys) / np.sqrt(spec.nope + spec.rope)
    seen = jnp.arange(pages * PAGE)[None, None, :] <= q_pos[:, :, None]
    probs = jax.nn.softmax(jnp.where(seen[:, :, None, :], scores, -jnp.inf), axis=-1)
    want = jnp.einsum("bqhk,bkc->bqhc", probs, keys[..., : spec.kv_rank])
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    assert bool(jnp.isfinite(got).all())


def _requests(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, ids=tuple(rng.integers(0, cfg.vocab_size - 1, size=n).tolist()), max_new_tokens=m,
                    seed=0, arrival_s=0.0) for i, (n, m) in enumerate(sizes)]


@pytest.fixture(scope="module")
def served():
    """Seven requests of mixed lengths through a four-slot engine, traced."""
    from tpukit.obs.trace import TraceRecorder

    cfg = latent.tiny_diff_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    sv = ServeConfig(slots=4, buckets=(8, 16, 24, 32), max_len=64, max_new_tokens=24, decode_quantum=2,
                     page_size=PAGE, kv_dtype="f32", prefill_chunk=CHUNK)
    tracer = TraceRecorder(1 << 14)
    engine = ServeEngine(params, cfg, sv, eos_id=cfg.vocab_size - 1, tracer=tracer)
    sizes = [(5, 20), (21, 24), (30, 10), (13, 24), (32, 24), (9, 3), (17, 17)]
    comps = engine.run(_requests(cfg, sizes))
    return cfg, params, engine, comps, tracer.snapshot()


def test_the_engine_serves_the_reference_argmax_tokens(served, ref):
    cfg, params, engine, comps, _ = served
    assert len(comps) == 7 and all(c.reason == "length" for c in comps)
    for c in comps:
        best = np.asarray(jnp.argmax(ref.logits(params, jnp.asarray(c.ids), hf=hf_keys(cfg)), -1))
        assert [int(best[t - 1]) for t in range(c.prompt_len, len(c.ids))] == [int(x) for x in c.ids[c.prompt_len:]]
    assert all(a.live_pages == 0 for a in engine.allocators.values())


def test_quantum_events_carry_the_counters_and_the_two_gauges(served):
    _, _, engine, _, events = served
    quanta = [e for e in events if e["ev"] == "quantum"]
    assert quanta and all({"ctx_tokens", "kv_bytes", "expert_rows", "expert_rows_max", "mhc_row_err_max",
                           "diff_lambda_mean"} <= set(q) for q in quanta)
    assert all(isinstance(q["expert_rows"], int) and 0 <= q["expert_rows_max"] <= q["expert_rows"] for q in quanta)
    assert any(q["expert_rows"] > 0 for q in quanta)
    ticking = [q for q in quanta if q["decoding"]]  # a quantum in which every lane prefills has no live tick to gauge
    assert ticking and all(0 < q["mhc_row_err_max"] < 1e-2 and 0 < q["diff_lambda_mean"] < 1 for q in ticking)
    errs = [q["mhc_row_err_max"] for q in quanta]
    assert errs == sorted(errs)  # the largest so far: a gauge is reported as fetched, never as a difference
    assert sum(e["tokens"] for e in events if e["ev"] == "prefill") == 5 + 21 + 30 + 13 + 32 + 9 + 17


def test_config_from_the_published_keys_and_the_cache_it_keeps():
    config = common.load_json(ROOT / "benchmark" / "configs" / "motif-3-beta.json")
    cfg = latent.config_from_hf(config)
    assert cfg.layer_types == (latent.WINDOW,) * 4 + (latent.FULL,) and cfg.first_dense == 1
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.vocab_size) == (384, 48, 8, 27520)
    spec = cfg.full
    assert cfg.window == spec and (spec.heads, spec.kv_heads, spec.noise_heads, spec.out_heads) == (80, 16, 16, 64)
    assert (spec.nope, spec.rope, spec.v, spec.q_rank, spec.kv_rank, spec.gate) == (128, 64, 128, 1024, 512, "elementwise")
    assert (cfg.streams, cfg.sinkhorn_iters, cfg.activation, cfg.route_scale, cfg.poly_scale) == (4, 20, "poly_norm", 2.0, 0.5)
    assert (cfg.index_topk, cfg.select_bias, cfg.rescale_latents, cfg.hidden_clamp) == (0, False, False, 1e6)
    assert latent.max_context(cfg) == 262144 and cfg.window_size == 128
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == config["held"]["parameters"]
    assert shapes["layers"][0]["attn"]["gate"].shape == (4096, 8192) and "ffn" in shapes["layers"][0]
    assert shapes["layers"][4]["moe"]["experts"]["gate"].shape == (48, 4096, 1280)
    # a full layer's 576-value latent row and no indexer key; a ring of 9 pages for window 128
    full, window = latent.page_kinds(cfg, 16, "bf16")
    assert (full.layers, full.page_bytes, full.ring_pages) == (1, 16 * 1152, 0)
    assert (window.layers, window.page_bytes, window.ring_pages) == (4, 16 * 1152, 9)
    assert window.pages_for(16384, 16) == 9 and 9 * 16 * 1152 == 165_888  # 166 kB a slot a window layer
    pages = {"bt": 64 * 1024 + 1, "bt_w": 64 * 9 + 1}
    assert paged.pool_bytes(cfg, pages, 16, "bf16") == (65537 + 4 * 577) * 16 * 1152
    with pytest.raises(ValueError, match="only 'interleave'"):
        latent.config_from_hf(dict(config, sliding_window_pattern="every_other"))
    with pytest.raises(ValueError, match="held_layers"):
        latent.config_from_hf(dict(config, held_layers=[0, 4, 5]))


def test_the_configs_refuse_what_the_heads_cannot_be():
    with pytest.raises(ValueError, match="one noise head each"):
        latent.AttnSpec(heads=10, nope=16, rope=8, v=16, q_rank=32, kv_rank=24, theta=1e4, kv_heads=2, noise_heads=4)
    with pytest.raises(ValueError, match="kv_heads must be given"):
        latent.AttnSpec(heads=10, nope=16, rope=8, v=16, q_rank=32, kv_rank=24, theta=1e4, noise_heads=2)
    with pytest.raises(ValueError, match="activation"):
        latent.tiny_diff_config(activation="gelu")


def test_a_ticks_rows_through_every_held_expert_give_the_grouped_sum():
    """`every_row`: each held expert computes every row and the gates keep
    what was routed: the grouped form's sum and row counts, masked lanes too."""
    cfg = latent.tiny_diff_config(experts_held=6, expert_lo=3)
    moe = latent.init_params(jax.random.PRNGKey(5), cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(8), (7, cfg.dim), jnp.float32)
    mask = jnp.asarray([True, True, False, True, True, False, True])
    grouped, rows = latent._expert_layer(moe, cfg.replace(tick_experts_every_row=False), x, mask, tick=True)
    dense, rows_d = latent._expert_layer(moe, cfg, x, mask, tick=True)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(grouped), atol=2e-5)
    assert rows.tolist() == rows_d.tolist() and int(rows.sum()) > 0
    # a prefill chunk's rows stay grouped whatever the switch says
    a, _ = latent._expert_layer(moe, cfg, x, None)
    b, _ = latent._expert_layer(moe, cfg.replace(tick_experts_every_row=False), x, None)
    assert np.array_equal(np.asarray(a), np.asarray(b))

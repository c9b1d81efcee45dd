"""The latent family's all-full form (DeepSeek-V3's: every layer full, a KV
head a query head, YaRN on interleaved rotary pairs, node-limited routing, one
page kind) at a tiny size on the CPU, against the plain float32 reference the
benchmark keeps (benchmark/reference/deepseek_v3_block.py, which imports
nothing from tpukit): forward logits, a prompt prefilled in chunks then
decode ticks, a second prompt whose shared prefix the page registry served,
the router against a direct numpy version, the YaRN table against the
formula, the expert shares, and the one-kind page layout the engine's prefix
sharing needs."""

import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tpukit.model import latent
from tpukit.ops import moe_dispatch
from tpukit.serve import paged
from tpukit.serve.engine import Request, ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 4, 8


@pytest.fixture(scope="module")
def ref():
    return common.load_by_name("reference", "deepseek_v3_block", ROOT)


def hf_keys(cfg) -> dict:
    """The published-key view of an all-full LatentConfig."""
    spec = cfg.full
    factor, original, fast, slow, mscale = spec.yarn
    return dict(num_attention_heads=spec.heads, qk_nope_head_dim=spec.nope, qk_rope_head_dim=spec.rope,
                kv_lora_rank=spec.kv_rank, rms_norm_eps=cfg.norm_eps, rope_theta=spec.theta, hidden_size=cfg.dim,
                max_position_embeddings=cfg.max_position_embeddings, num_experts_per_tok=cfg.experts_per_token,
                n_group=cfg.route_groups, topk_group=cfg.route_topk_groups, routed_scaling_factor=cfg.route_scale,
                rope_scaling=dict(type="yarn", factor=factor, original_max_position_embeddings=original,
                                  beta_fast=fast, beta_slow=slow, mscale=mscale, mscale_all_dim=mscale))


@pytest.fixture(scope="module")
def model():
    cfg = latent.tiny_full_config(expert_lo=6)
    return cfg, latent.init_params(jax.random.PRNGKey(11), cfg)


def published_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """DeepSeek-V3's `precompute_freqs_cis`, line for line, in numpy."""
    def find_correction_dim(num_rotations):
        return dim * math.log(original / (num_rotations * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(find_correction_dim(beta_fast)), 0)
    high = min(math.ceil(find_correction_dim(beta_slow)), dim - 1)
    freqs = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low) / (high - low if high != low else 0.001), 0, 1)
    smooth = 1 - ramp
    return freqs / factor * (1 - smooth) + freqs * smooth


@pytest.mark.parametrize("sizes", [(64, 10000.0, 40.0, 4096, 32.0, 1.0), (8, 100.0, 4.0, 16, 4.0, 1.0)],
                         ids=["published", "tiny"])
def test_yarn_frequencies_and_mscale_follow_the_published_formula(sizes, ref):
    got = latent.yarn_inv_freq(*sizes)
    np.testing.assert_allclose(got, published_yarn(*sizes), rtol=1e-6)
    dim, base, factor, original, fast, slow = sizes
    hf = dict(qk_rope_head_dim=dim, rope_theta=base, max_position_embeddings=original + 1, qk_nope_head_dim=128,
              rope_scaling=dict(factor=factor, original_max_position_embeddings=original, beta_fast=fast,
                                beta_slow=slow, mscale_all_dim=1.0))
    np.testing.assert_array_equal(ref.rope_freqs(hf), got)
    keep, scaled = got[:1], got[-1:]  # the fastest dimension keeps its frequency, the slowest is divided
    assert keep[0] == pytest.approx(1.0) and scaled[0] == pytest.approx(published_yarn(*sizes)[-1])
    assert latent.yarn_mscale(40.0, 1.0) == pytest.approx(1.36889, abs=1e-5)
    spec = latent.AttnSpec(128, 128, 64, 128, 1536, 512, 1e4, gate="none", yarn=(40.0, 4096, 32.0, 1.0, 1.0))
    assert spec.scale == pytest.approx(192 ** -0.5 * 1.36888794 ** 2) and spec.scale * 192 ** 0.5 == pytest.approx(1.8739, abs=1e-4)
    assert ref.softmax_scale(dict(hf, qk_nope_head_dim=128, qk_rope_head_dim=64, max_position_embeddings=163840,
                                  rope_scaling=dict(hf["rope_scaling"], factor=40.0, original_max_position_embeddings=4096))) \
        == pytest.approx(spec.scale)


def numpy_gate(scores, bias, top_k, groups, topk_groups, scale):
    """DeepSeek-V3's `Gate.forward` for sigmoid scores, one token at a time."""
    out_idx, out_w = [], []
    for s in scores:
        biased = (s + bias).reshape(groups, -1)
        group_score = np.sort(biased, axis=-1)[:, -2:].sum(-1)
        keep = np.argsort(-group_score, kind="stable")[:topk_groups]
        masked = np.full_like(biased, -np.inf)
        masked[keep] = biased[keep]
        idx = np.argsort(-masked.reshape(-1), kind="stable")[:top_k]
        w = s[idx] / s[idx].sum() * scale
        out_idx.append(idx)
        out_w.append(w)
    return np.stack(out_idx), np.stack(out_w)


def test_node_limited_routing_against_numpy():
    """Sixteen experts in four groups, the best two kept. Token 0 is built so
    that the group holding its single highest score is dropped (its second
    score is low, so its pair sum loses), and that expert is not chosen."""
    e, d, t = 16, 8, 33
    rng = np.random.default_rng(0)
    x = rng.normal(size=(t, d)).astype(np.float32)
    kernel = rng.normal(size=(d, e)).astype(np.float32)
    bias = (0.02 * rng.normal(size=(e,))).astype(np.float32)
    # token 0: logits by hand through a kernel that reads x[0]'s first coordinate only
    x[0] = 0.0
    x[0, 0] = 1.0
    row = np.array([6.0, -6, -6, -6, 1.5, 1.4, -6, -6, 1.3, 1.3, -6, -6, -1, -1, -1, -1], np.float32)
    kernel[0] = row
    idx, gates = moe_dispatch.sigmoid_topk_route(jnp.asarray(x), jnp.asarray(kernel), jnp.asarray(bias), 4, 2.5,
                                                 groups=4, topk_groups=2)
    scores = 1 / (1 + np.exp(-(x.astype(np.float64) @ kernel.astype(np.float64))))
    want_idx, want_w = numpy_gate(scores, bias, 4, 4, 2, 2.5)
    assert np.argmax(scores[0]) == 0 and 0 not in np.asarray(idx[0]).tolist()  # the highest score's group lost
    for a, b in zip(np.asarray(idx), want_idx):
        assert set(a.tolist()) == set(b.tolist())
    order = np.argsort(np.asarray(idx), axis=1)
    np.testing.assert_allclose(np.take_along_axis(np.asarray(gates), order, 1),
                               np.take_along_axis(want_w, np.argsort(want_idx, axis=1), 1), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(gates).sum(-1), 2.5, rtol=1e-6)
    # every choice lies in the two kept groups
    assert all(len({int(i) // 4 for i in r}) <= 2 for r in np.asarray(idx))


def test_forward_logits_match_the_reference(model, ref):
    cfg, params = model
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 37), 0, cfg.vocab_size)
    served = jax.jit(lambda p, i: latent.forward(p, cfg, i, page_size=PAGE))(params, ids)
    for b in range(2):
        exact = ref.logits(params, ids[b], hf=hf_keys(cfg), expert_lo=cfg.expert_lo)
        np.testing.assert_allclose(np.asarray(served[b]), np.asarray(exact), atol=3e-5)


def _through(params, cfg, cache, ids, lane, start, prompt_len, step):
    """Prefill `ids[start:prompt_len]` into `lane` in chunks, then one tick
    a token for the rest; returns the logits of positions `start..`."""
    got = []
    for s in range(start, prompt_len, CHUNK):
        seg = ids[s:min(s + CHUNK, prompt_len)]
        row = np.zeros((1, CHUNK), np.int32)
        row[0, :len(seg)] = seg
        sub = latent.select_lanes(cache, jnp.asarray([lane]), jnp.asarray([prompt_len], jnp.int32))
        pos = s + jnp.arange(CHUNK, dtype=jnp.int32)[None]
        logits, sub = step(params, sub, jnp.asarray(row), pos, jnp.asarray([s], jnp.int32), None)
        cache = latent.merge_lanes(cache, sub)
        got.append(np.asarray(logits[0, :len(seg)]))
    slots = cache["bt"].shape[0]
    for t in range(prompt_len, len(ids)):
        tok, live = np.zeros((slots, 1), np.int32), np.zeros((slots,), bool)
        tok[lane], live[lane] = ids[t], True
        read = np.zeros((slots,), np.int32)
        read[lane] = t
        logits, cache = step(params, cache, jnp.asarray(tok), jnp.asarray(read)[:, None], jnp.asarray(read),
                             jnp.asarray(live))
        got.append(np.asarray(logits[lane]))
    return np.concatenate(got), cache


def test_prefill_decode_and_a_registry_hit_match_the_reference(model, ref):
    """A document and a first question prefilled cold in chunks into lane 0,
    then decode ticks; the lane's pages registered as the engine registers
    them; a second question on the same document admitted into lane 1
    through `lookup_prefix` / `claim`, its block table the shared pages and
    its own, prefilling only its suffix from the first unshared chunk. Both
    lanes' logits against the reference's full forward of their own
    sequence; the document is longer than YaRN's original context."""
    cfg, params = model
    rng = np.random.default_rng(4)
    doc = rng.integers(0, cfg.vocab_size, size=24).astype(np.int32)
    first = np.concatenate([doc, rng.integers(0, cfg.vocab_size, size=13)]).astype(np.int32)
    second = np.concatenate([doc, rng.integers(0, cfg.vocab_size, size=18)]).astype(np.int32)
    plen1, plen2 = 30, 35  # the rest of each sequence is decoded
    per_slot, slots = 16, 2
    (kind,) = latent.page_kinds(cfg, PAGE, "f32")
    alloc = paged.PageAllocator(slots * per_slot + 1, PAGE)
    cache = latent.init_paged_cache(cfg, {"bt": slots * per_slot + 1}, PAGE, per_slot, slots, "f32")
    step = jax.jit(lambda p, c, i, pos, s, live: latent.forward_cached(p, cfg, i, pos, c, s, write_mask=live))
    bt = np.zeros((slots, per_slot), np.int32)
    pages1 = alloc.alloc(kind.pages_for(len(first), PAGE))
    bt[0, :len(pages1)] = pages1
    cache["bt"] = jnp.asarray(bt)
    got1, cache = _through(params, cfg, cache, first, 0, 0, plen1, step)
    alloc.register(tuple(first[:plen1].tolist()), pages1[:(plen1 - 1) // PAGE])
    matched = alloc.lookup_prefix(tuple(second[:plen2].tolist()), (plen2 - 1) // PAGE)
    shared_tokens = (len(matched) * PAGE // CHUNK) * CHUNK
    shared = matched[: shared_tokens // PAGE]
    assert shared_tokens == len(doc) and shared == pages1[: len(doc) // PAGE]  # the whole document, chunk-aligned
    alloc.claim(shared)
    own = alloc.alloc(kind.pages_for(len(second), PAGE) - len(shared))
    bt[1, : len(shared) + len(own)] = shared + own
    cache["bt"] = jnp.asarray(bt)
    got2, cache = _through(params, cfg, cache, second, 1, shared_tokens, plen2, step)
    hf = hf_keys(cfg)
    exact1 = np.asarray(ref.logits(params, jnp.asarray(first), hf=hf, expert_lo=cfg.expert_lo))
    exact2 = np.asarray(ref.logits(params, jnp.asarray(second), hf=hf, expert_lo=cfg.expert_lo))
    np.testing.assert_allclose(got1, exact1, atol=5e-5)
    np.testing.assert_allclose(got2, exact2[shared_tokens:], atol=5e-5)
    assert int(cache["moe_rows"][0]) > 0


def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer(ref):
    """Eight chips of two experts each under node-limited routing: their
    routed parts, plus the shared expert counted once, add up to what the
    uncut layer gives; the reference's layer agrees with each share."""
    whole = latent.tiny_full_config(experts_held=16)
    params = latent.init_params(jax.random.PRNGKey(5), whole)
    moe = params["layers"][2]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (29, whole.dim), jnp.float32)
    uncut, _ = latent._expert_layer(moe, whole, x, None)
    idx, gates = moe_dispatch.sigmoid_topk_route(x, moe["router"], moe["select_bias"], whole.experts_per_token,
                                                 whole.route_scale, whole.route_groups, whole.route_topk_groups)
    shared = latent._gated_ffn(moe["shared"], x, jnp.float32)
    total, rows_seen = shared, 0
    for lo in range(0, 16, 2):
        held = jax.tree.map(lambda w: w[lo:lo + 2], moe["experts"])
        part, rows = moe_dispatch.held_experts_ffn(x, idx, gates, held, lo, jnp.float32)
        exact = ref.expert_layer(x, dict(moe, experts=held), hf=hf_keys(whole), expert_lo=lo)
        np.testing.assert_allclose(np.asarray(part + shared), np.asarray(exact), atol=2e-5)
        total, rows_seen = total + part, rows_seen + int(rows.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert rows_seen == 29 * whole.experts_per_token


def test_one_page_kind_without_window_layers():
    cfg = latent.tiny_full_config()
    kinds = latent.page_kinds(cfg, PAGE, "bf16")
    assert len(kinds) == 1 and kinds[0].table == "bt" and kinds[0].ring_pages == 0
    assert kinds[0].layers == 5 and kinds[0].page_bytes == PAGE * (24 + 8) * 2
    cache = latent.init_paged_cache(cfg, {"bt": 9}, PAGE, 4, 2, "f32")
    assert set(cache) == {"lat", "bt", "moe_rows"}
    assert paged.pool_bytes(cfg, 9, PAGE, "f32") == int(np.prod(cache["lat"].shape)) * 4
    sub = latent.select_lanes(cache, jnp.asarray([1]), jnp.asarray([5]))
    assert set(sub) == {"lat", "bt", "moe_rows", "valid"} and sub["bt"].shape == (1, 4)
    assert set(latent.merge_lanes(cache, sub)) == set(cache)
    assert "gate" not in latent.init_params(jax.random.PRNGKey(0), cfg)["layers"][0]["attn"]


def test_the_engine_shares_a_window_less_latent_prefix_and_serves_the_reference_argmax(model, ref):
    cfg, params = model
    sv = ServeConfig(slots=2, buckets=(16, 32), max_len=48, max_new_tokens=6, page_size=PAGE, kv_dtype="f32",
                     prefill_chunk=CHUNK)
    engine = ServeEngine(params, cfg, sv, eos_id=cfg.vocab_size - 1)
    rng = np.random.default_rng(7)
    doc = tuple(rng.integers(0, cfg.vocab_size - 1, size=16).tolist())
    first = [Request(rid=0, ids=doc + (5, 6, 7), max_new_tokens=6, seed=0, arrival_s=0.0)]
    engine.run(first)
    later = [Request(rid=i, ids=doc + tuple(rng.integers(0, 90, size=3 + i).tolist()), max_new_tokens=6, seed=0,
                     arrival_s=0.0) for i in (1, 2)]
    comps = engine.run(later)
    hits = [c for c in comps if c.rid in (1, 2)]
    assert engine.allocator.stats.prefix_hits >= 2 and all(c.prefix_pages * PAGE == 16 for c in hits)
    for c in hits:
        best = np.asarray(jnp.argmax(ref.logits(params, jnp.asarray(c.ids), hf=hf_keys(cfg),
                                                expert_lo=cfg.expert_lo), -1))
        assert [int(best[t - 1]) for t in range(c.prompt_len, len(c.ids))] == [int(x) for x in c.ids[c.prompt_len:]]


def test_the_all_full_form_is_read_by_its_model_type_alone():
    """A config is read as DeepSeek-V3's by its `model_type`; one that only
    lacks `layer_types` is read as the dots3 key set (which asks for it), and
    a DeepSeek-V3 config that states `layer_types` is refused."""
    config = common.load_json(ROOT / "benchmark" / "configs" / "deepseek-v3.json")
    with pytest.raises(KeyError, match="layer_types"):
        latent.config_from_hf(dict(config, model_type="another"))
    with pytest.raises(ValueError, match="states no layer_types"):
        latent.config_from_hf(dict(config, layer_types=["full_attention"] * 5))


def test_config_from_the_published_keys():
    config = common.load_json(ROOT / "benchmark" / "configs" / "deepseek-v3.json")
    cfg = latent.config_from_hf(config)
    spec = cfg.full
    assert cfg.layer_types == (latent.FULL,) * 5 and cfg.first_dense == 1 and cfg.index_topk == 0
    assert (spec.heads, spec.nope, spec.rope, spec.v, spec.q_rank, spec.kv_rank, spec.gate) == (128, 128, 64, 128, 1536, 512, "none")
    assert spec.rope_interleaved and spec.yarn == (40.0, 4096, 32.0, 1.0, 1.0) and spec.kv_heads == 0
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.route_groups, cfg.route_topk_groups) == (256, 16, 8, 8, 4)
    assert (cfg.route_scale, cfg.select_bias, cfg.rescale_latents, cfg.vocab_size) == (2.5, True, False, 16160)
    assert cfg.tick_experts_every_row  # a tick's rows through every held expert
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == config["held"]["parameters"]
    (kind,) = latent.page_kinds(cfg, 16, "bf16")
    assert kind.layers * kind.page_bytes // 16 == 5760  # bytes a token over the five layers

"""The latent family (tpukit/model/latent.py) at a tiny size on the CPU,
against the plain float32 reference the benchmark keeps
(benchmark/reference/dots3_block.py, which imports nothing from tpukit):
forward logits, chunked prefill then decode through the paged latent cache,
the selected key sets, the expert layer's shares, the window ring's bound,
and the model under the same ServeEngine as the GPT twin."""

from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import common
from tpukit.model import GPTConfig, LatentConfig, ServedOnlyError, family, gpt, latent
from tpukit.ops import moe_dispatch
from tpukit.serve import decode as serve_decode
from tpukit.serve import paged
from tpukit.serve.engine import Request, ServeConfig, ServeEngine

ROOT = Path(__file__).resolve().parents[1]
PAGE, CHUNK = 4, 8


@pytest.fixture(scope="module")
def ref():
    return common.load_by_name("reference", "dots3_block", ROOT)


def hf_keys(cfg: LatentConfig) -> dict:
    """The published-key view of a LatentConfig, as a configuration file holds it."""
    out = dict(rms_norm_eps=cfg.norm_eps, sliding_window_size=cfg.window_size, layer_types=list(cfg.layer_types),
               index_topk=cfg.index_topk, num_experts_per_tok=cfg.experts_per_token,
               apply_mla_qkv_lora_rescale=cfg.rescale_latents)
    for pre, spec in (("", cfg.full), ("swa_", cfg.window)):
        out.update({pre + "num_attention_heads": spec.heads, pre + "qk_nope_head_dim": spec.nope,
                    pre + "qk_rope_head_dim": spec.rope, pre + "q_lora_rank": spec.q_rank,
                    pre + "kv_lora_rank": spec.kv_rank, pre + "rope_theta": spec.theta})
    return out


@pytest.fixture(scope="module", params=[0, 6], ids=["experts0-1", "experts6-7"])
def model(request):
    cfg = latent.tiny_config(expert_lo=request.param)
    return cfg, latent.init_params(jax.random.PRNGKey(3 + request.param), cfg)


def one_lane_cache(cfg, pages: int):
    ring = latent.page_kinds(cfg, PAGE, "f32")[1].ring_pages
    cache = latent.init_paged_cache(cfg, {"bt": pages + 1, "bt_w": ring + 1}, PAGE, pages, 1, "f32")
    return dict(cache, bt=1 + jnp.arange(pages, dtype=jnp.int32)[None],
                bt_w=1 + jnp.arange(ring, dtype=jnp.int32)[None])


def test_forward_logits_match_the_reference(model, ref):
    cfg, params = model
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 41), 0, cfg.vocab_size)
    served = jax.jit(lambda p, i: latent.forward(p, cfg, i, page_size=PAGE))(params, ids)
    for b in range(2):
        exact = ref.logits(params, ids[b], hf=hf_keys(cfg), expert_lo=cfg.expert_lo)
        np.testing.assert_allclose(np.asarray(served[b]), np.asarray(exact), atol=2e-5)


def test_chunked_prefill_then_decode_through_the_cache_matches_the_reference(model, ref):
    """Logits, not tokens: a prompt in page-aligned chunks (the last one
    padded), then ticks, through one lane's paged cache whose window ring
    wraps many times; the context passes the top-k (16) and the window (9)."""
    cfg, params = model
    prompt_len, total, pages = 21, 52, 14
    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(2), (total,), 0, cfg.vocab_size))
    exact_sel: list = []
    exact = np.asarray(ref.logits(params, jnp.asarray(ids), hf=hf_keys(cfg), expert_lo=cfg.expert_lo,
                                  selected=exact_sel))
    step = jax.jit(lambda p, c, i, pos, s: latent.forward_cached_tapped(p, cfg, i, pos, c, s))
    cache = one_lane_cache(cfg, pages)
    valid = jnp.asarray([prompt_len], jnp.int32)
    got, sels = [], [[], []]
    for start in range(0, prompt_len, CHUNK):
        seg = ids[start:min(start + CHUNK, prompt_len)]
        row = np.zeros((1, CHUNK), np.int32)
        row[0, :len(seg)] = seg
        pos = start + jnp.arange(CHUNK, dtype=jnp.int32)[None]
        logits, cache, sel = step(params, dict(cache, valid=valid), jnp.asarray(row), pos,
                                  jnp.asarray([start], jnp.int32))
        cache.pop("valid")
        got.append(np.asarray(logits[0, :len(seg)]))
        for layer, s in enumerate(sel):
            sels[layer].append(np.asarray(s[0, :len(seg)]))
    for t in range(prompt_len, total):
        logits, cache, sel = step(params, cache, jnp.asarray(ids[None, t:t + 1]), jnp.asarray([[t]], jnp.int32),
                                  jnp.asarray([t], jnp.int32))
        got.append(np.asarray(logits[0]))
        for layer, s in enumerate(sel):
            sels[layer].append(np.asarray(s[0]))
    np.testing.assert_allclose(np.concatenate(got), exact, atol=3e-5)
    # the selection as a SET, every query of both full layers
    for layer in range(2):
        served = np.concatenate(sels[layer])
        for t in range(total):
            assert set(served[t][served[t] >= 0]) == set(np.asarray(exact_sel[layer][t][exact_sel[layer][t] >= 0])), (layer, t)
            assert len(set(served[t][served[t] >= 0])) == min(t + 1, cfg.index_topk)
    # the decode ticks counted the rows the held experts computed
    assert int(cache["moe_rows"][0]) > 0 and int(cache["moe_rows"][1]) <= int(cache["moe_rows"][0])


def stable_sort_mask(score, k: int):
    """The keys a stable sort of `-score` keeps in its first `k` places, as a
    mask (a `-inf` key is no key)."""
    pos = jnp.broadcast_to(jnp.arange(score.shape[-1], dtype=jnp.int32), score.shape)
    _, order = jax.lax.sort((-score, pos), dimension=score.ndim - 1, num_keys=1, is_stable=True)
    return (jnp.argsort(order, axis=-1) < k) & (score > -jnp.inf)


def _score_rows(case: str, rows: int = 6, width: int = 96):
    """`(score [rows, width] float32, k)` for one case of the selection test."""
    rng = np.random.default_rng(sum(map(ord, case)))
    x = rng.standard_normal((rows, width)).astype(np.float32)
    k = 24
    if case == "ties_straddle_the_kth":  # few distinct values: every row's k-th place falls inside a run of ties
        x = np.round(x * 2.0) / 2.0
        x[:, ::7] = np.sort(x[0])[-k]
    elif case == "fewer_visible_than_k":
        x[:, 40:] = -np.inf
        x[1, 10:] = -np.inf
    elif case == "all_but_one_minus_inf":
        x[:] = -np.inf
        x[np.arange(rows), np.arange(rows) * 11] = rng.standard_normal(rows)
    elif case == "k_is_the_width":
        k = width
        x[2, 50:] = -np.inf
    elif case == "signed_zeros":
        x = rng.choice(np.asarray([0.0, -0.0, 0.5, -0.5, np.inf, -np.inf], np.float32), size=(rows, width))
        x[x == np.inf] = 1.0
        k = 40  # past the ones and halves (~32 a row): the k-th place falls among the zeros of both signs
    return jnp.asarray(x), k


@pytest.mark.parametrize("case", ["random", "ties_straddle_the_kth", "fewer_visible_than_k", "all_but_one_minus_inf",
                                  "k_is_the_width", "signed_zeros"])
def test_the_threshold_keeps_the_stable_sorts_set(case):
    """`select_top_keys` (the k-th largest score on its bits, the ties by
    position) keeps exactly what the stable sort it replaced kept."""
    score, k = _score_rows(case)
    got = np.asarray(jax.jit(latent.select_top_keys, static_argnums=1)(score, k))
    want = np.asarray(stable_sort_mask(score, k))
    np.testing.assert_array_equal(got, want)
    visible = np.asarray(score > -jnp.inf).sum(-1)
    np.testing.assert_array_equal(got.sum(-1), np.minimum(visible, k))
    if case in ("ties_straddle_the_kth", "signed_zeros"):  # the tie-break by position was needed
        kth = np.sort(np.asarray(score), axis=-1)[:, -k]
        assert (np.asarray(score) >= kth[:, None]).sum(-1).max() > k


@pytest.mark.parametrize("t", [CHUNK, 1], ids=["prefill_chunk", "decode_tick"])
def test_a_full_layer_hands_out_the_stable_sorts_positions(t, monkeypatch):
    """`_full_attention` at `tiny_config()` (top-16) over two lanes of 30 and
    45 keys: the positions `taps` hands out are the set the stable sort
    keeps, and the layer's output is what it gives under the sort's mask."""
    cfg = latent.tiny_config()
    a = latent.init_params(jax.random.PRNGKey(4), cfg)["layers"][0]["attn"]
    cache = latent.init_paged_cache(cfg, {"bt": 33, "bt_w": 9}, PAGE, 16, 2, "f32")
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(5), 3)
    lat = jax.random.normal(k1, cache["lat"].shape, jnp.float32)
    idx = jax.random.normal(k2, cache["idx"].shape, jnp.float32)
    bt = 1 + jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    start = jnp.asarray([24, 40] if t > 1 else [29, 44], jnp.int32)
    pos = start[:, None] + jnp.arange(t, dtype=jnp.int32)[None]
    h = jax.random.normal(k3, (2, t, cfg.dim), jnp.float32)

    def layer():
        taps: list = []
        out, *_ = latent._full_attention(a, cfg, h, pos, lat, idx, 0, bt, start, jnp.ones((2,), bool), taps)
        return np.asarray(out), np.asarray(taps[0])

    out, sel = layer()
    monkeypatch.setattr(latent, "select_top_keys", stable_sort_mask)
    sorted_out, sorted_sel = layer()
    for lane in range(2):
        for q in range(t):
            kept = set(sel[lane, q][sel[lane, q] >= 0].tolist())
            assert kept == set(sorted_sel[lane, q][sorted_sel[lane, q] >= 0].tolist()), (lane, q)
            assert len(kept) == min(cfg.index_topk, int(pos[lane, q]) + 1) and max(kept) <= int(pos[lane, q])
    np.testing.assert_allclose(out, sorted_out, atol=1e-6)


def test_the_decode_ticks_count_the_keys_selected_and_seen():
    """`select_kept` / `select_visible` grow by `min(top-k, ctx)` and `ctx`
    a full layer a live lane a tick (a masked lane adds nothing; prefill
    chunks add nothing), and the form without selection keeps its tuple."""
    cfg = latent.tiny_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    slots, per = 3, 16
    ring = latent.page_kinds(cfg, PAGE, "f32")[1].ring_pages
    cache = latent.init_paged_cache(cfg, {"bt": slots * per + 1, "bt_w": slots * ring + 1}, PAGE, per, slots, "f32")
    cache = dict(cache, bt=1 + jnp.arange(slots * per, dtype=jnp.int32).reshape(slots, per),
                 bt_w=1 + jnp.arange(slots * ring, dtype=jnp.int32).reshape(slots, ring))
    assert latent.counters(cache)[0] == ("expert_rows", "expert_rows_max", "select_kept", "select_visible")
    step = jax.jit(lambda c, i, p, s, m: latent.forward_cached(params, cfg, i, p, c, s, m))
    ids = jnp.asarray(np.random.default_rng(1).integers(0, cfg.vocab_size, (slots, CHUNK)), jnp.int32)
    prompt = jnp.broadcast_to(jnp.arange(CHUNK, dtype=jnp.int32), (slots, CHUNK))
    _, cache = step(dict(cache, valid=jnp.full((slots,), CHUNK, jnp.int32)), ids, prompt,
                    jnp.zeros((slots,), jnp.int32), jnp.ones((slots,), bool))
    cache.pop("valid")
    assert np.asarray(cache["sel_keys"]).tolist() == [0, 0]  # a prefill chunk is no tick
    kept = visible = 0
    ctx = np.asarray([3, 14, 20])
    live = np.asarray([True, False, True])
    for tick in range(4):
        read = jnp.asarray(ctx - 1 + tick, jnp.int32)
        _, cache = step(cache, ids[:, :1], read[:, None], read, jnp.asarray(live))
        seen = ctx + tick
        kept += 2 * int(np.minimum(seen, cfg.index_topk)[live].sum())
        visible += 2 * int(seen[live].sum())
        assert np.asarray(cache["sel_keys"]).tolist() == [kept, visible], tick
    diff = latent.tiny_diff_config()
    names, arrays = latent.counters(latent.init_paged_cache(diff, {"bt": 9, "bt_w": 9}, PAGE, 4, 2, "f32"))
    assert names == ("expert_rows", "expert_rows_max", "mhc_row_err_max", "diff_lambda_mean") and len(arrays) == 2


def test_the_shares_routed_parts_and_the_shared_expert_once_equal_the_uncut_layer(ref):
    """Eight chips of two experts each: their routed parts, plus the shared
    expert counted once, add up to what the uncut layer (all 16 experts on
    one chip) gives; and the reference's layer agrees with each share."""
    whole = latent.tiny_config(experts_held=16)
    params = latent.init_params(jax.random.PRNGKey(5), whole)
    moe = params["layers"][2]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(6), (37, whole.dim), jnp.float32)
    uncut, _ = latent._expert_layer(moe, whole, x, None)
    idx, gates = moe_dispatch.sigmoid_topk_route(x, moe["router"], moe["select_bias"], whole.experts_per_token)
    np.testing.assert_allclose(np.asarray(gates.sum(-1)), 1.0, atol=1e-6)  # normalised over ALL the chosen
    shared = latent._gated_ffn(moe["shared"], x, jnp.float32)
    total, rows_seen = shared, 0
    for lo in range(0, 16, 2):
        held = jax.tree.map(lambda w: w[lo:lo + 2], moe["experts"])
        part, rows = moe_dispatch.held_experts_ffn(x, idx, gates, held, lo, jnp.float32)
        share = dict(moe, experts=held)
        exact = ref.expert_layer(x, share, top_k=whole.experts_per_token, expert_lo=lo)
        np.testing.assert_allclose(np.asarray(part + shared), np.asarray(exact), atol=2e-5)
        total, rows_seen = total + part, rows_seen + int(rows.sum())
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut), atol=2e-5)
    assert rows_seen == 37 * whole.experts_per_token  # dropless: every (token, choice) pair computed exactly once


def test_held_experts_skip_masked_rows():
    cfg = latent.tiny_config(experts_held=16)
    moe = latent.init_params(jax.random.PRNGKey(5), cfg)["layers"][1]["moe"]
    x = jax.random.normal(jax.random.PRNGKey(7), (6, cfg.dim), jnp.float32)
    idx, gates = moe_dispatch.sigmoid_topk_route(x, moe["router"], moe["select_bias"], cfg.experts_per_token)
    mask = jnp.asarray([True, False, True, True, False, True])
    y, rows = moe_dispatch.held_experts_ffn(x, idx, gates, moe["experts"], 0, jnp.float32, mask)
    full, _ = moe_dispatch.held_experts_ffn(x, idx, gates, moe["experts"], 0, jnp.float32)
    assert int(rows.sum()) == 4 * cfg.experts_per_token
    np.testing.assert_allclose(np.asarray(y[mask]), np.asarray(full[mask]), atol=1e-6)
    assert float(jnp.abs(y[~mask]).max()) == 0.0


def _requests(cfg, sizes, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(rid=i, ids=tuple(rng.integers(0, cfg.vocab_size - 1, size=n).tolist()), max_new_tokens=m,
                    seed=0, arrival_s=0.0) for i, (n, m) in enumerate(sizes)]


@pytest.fixture(scope="module")
def served():
    """Seven requests of mixed lengths through a four-slot engine, traced."""
    from tpukit.obs.trace import TraceRecorder

    cfg = latent.tiny_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    sv = ServeConfig(slots=4, buckets=(8, 16, 24, 32), max_len=64, max_new_tokens=24, decode_quantum=2,
                     page_size=PAGE, kv_dtype="f32", prefill_chunk=CHUNK)
    tracer = TraceRecorder(1 << 14)
    engine = ServeEngine(params, cfg, sv, eos_id=cfg.vocab_size - 1, tracer=tracer)
    ring_held = []
    retire = engine._retire

    def watched(*a, **kw):  # what each live lane holds of the window kind, at every sync
        ring_held.extend(len(l.more_pages["bt_w"]) for l in engine._lanes.values())
        return retire(*a, **kw)

    engine._retire = watched
    sizes = [(5, 20), (21, 24), (30, 10), (13, 24), (32, 24), (9, 3), (17, 17)]
    comps = engine.run(_requests(cfg, sizes))
    return cfg, params, engine, comps, tracer.snapshot(), ring_held


def test_the_engine_serves_the_reference_argmax_tokens(served, ref):
    cfg, params, _, comps, _, _ = served
    assert len(comps) == 7 and all(c.reason == "length" for c in comps)
    for c in comps:
        best = np.asarray(jnp.argmax(ref.logits(params, jnp.asarray(c.ids), hf=hf_keys(cfg)), -1))
        assert [int(best[t - 1]) for t in range(c.prompt_len, len(c.ids))] == [int(x) for x in c.ids[c.prompt_len:]]


def test_window_pages_never_exceed_the_ring_and_every_page_comes_back(served):
    cfg, _, engine, comps, _, ring_held = served
    bound = -(-cfg.window_size // PAGE) + 1  # ceil(window / P) + 1
    assert ring_held and max(ring_held) <= bound
    assert max(len(c.ids) for c in comps) > bound * PAGE  # contexts outgrew the ring: its pages were reused
    assert all(a.live_pages == 0 for a in engine.allocators.values())
    assert not any(host.any() for host in engine._tables.values())


def test_quantum_and_prefill_events_carry_the_new_counters(served):
    _, _, engine, _, events, _ = served
    quanta = [e for e in events if e["ev"] == "quantum"]
    assert quanta and all({"ctx_tokens", "kv_bytes", "expert_rows", "expert_rows_max"} <= set(q) for q in quanta)
    assert any(q["expert_rows"] > 0 for q in quanta)
    assert all(0 <= q["expert_rows_max"] <= q["expert_rows"] for q in quanta)
    assert all(q["ctx_tokens"] > 0 and 0 < q["kv_bytes"] <= engine.kv_bytes for q in quanta)
    prefills = [e for e in events if e["ev"] == "prefill"]
    assert sum(e["tokens"] for e in prefills) == 5 + 21 + 30 + 13 + 32 + 9 + 17
    assert all(0 < e["tokens"] <= CHUNK for e in prefills)


def test_the_quanta_carry_the_selection_counters_and_traceview_shows_them(served):
    import importlib

    cfg, _, _, _, events, _ = served
    quanta = [e for e in events if e["ev"] == "quantum"]
    assert all(0 <= q["select_kept"] <= q["select_visible"] for q in quanta)
    kept, visible = sum(q["select_kept"] for q in quanta), sum(q["select_visible"] for q in quanta)
    assert 0 < kept < visible  # contexts passed the top-k (16): the selection dropped keys
    line = importlib.import_module("tools.traceview").selection_line(events)
    assert line.startswith(f"key selection: {kept} of the {visible} keys") and f"({kept / visible:.3f})" in line


def test_pool_bytes_counts_every_page_kind():
    cfg = latent.tiny_config()
    pages = {"bt": 11, "bt_w": 5}
    tree = latent.init_paged_cache(cfg, pages, PAGE, 6, 2, "f32")
    pools = sum(int(np.prod(tree[k].shape)) * tree[k].dtype.itemsize for k in ("lat", "idx", "win"))
    assert paged.pool_bytes(cfg, pages, PAGE, "f32") == pools
    with pytest.raises(ValueError, match="page kinds"):
        paged.pool_bytes(cfg, 11, PAGE, "f32")
    full, window = latent.page_kinds(cfg, PAGE, "bf16")
    assert full.page_bytes == PAGE * (cfg.full.row + cfg.index_dim) * 2
    assert window.ring_pages == 4 and window.pages_for(1000, PAGE) == 4 and window.pages_for(5, PAGE) == 2
    # the GPT block keeps one kind: the closed form is what it was
    g = GPTConfig(dim=64, head_dim=16, heads=4, num_layers=2, vocab_size=97, max_position_embeddings=64)
    assert paged.pool_bytes(g, 7, 8, "bf16") == 2 * 2 * 7 * 4 * 8 * 16 * 2


def test_the_engine_refuses_what_the_family_cannot_serve():
    cfg = latent.tiny_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    ring = ServeConfig(slots=2, buckets=(16,), max_len=32, max_new_tokens=8)
    with pytest.raises(ServedOnlyError, match="paged cache only"):
        ServeEngine(params, cfg, ring, eos_id=96)
    big_chunk = ServeConfig(slots=2, buckets=(32,), max_len=64, max_new_tokens=8, page_size=PAGE, prefill_chunk=32)
    with pytest.raises(ValueError, match="ring"):
        ServeEngine(params, cfg, big_chunk, eos_id=96)
    long = ServeConfig(slots=2, buckets=(8192,), max_len=8192, max_new_tokens=8, page_size=PAGE)
    with pytest.raises(ValueError, match="longest context"):
        ServeEngine(params, cfg, long, eos_id=96)


def test_no_prefix_is_shared_where_a_window_layer_would_miss_it():
    cfg = latent.tiny_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    sv = ServeConfig(slots=2, buckets=(16, 32), max_len=48, max_new_tokens=4, page_size=PAGE, kv_dtype="f32",
                     prefill_chunk=CHUNK)
    engine = ServeEngine(params, cfg, sv, eos_id=96)
    same = tuple(range(1, 25))
    comps = engine.run([Request(rid=i, ids=same, max_new_tokens=4, seed=0, arrival_s=0.0) for i in range(3)])
    assert engine.allocator.stats.prefix_hits == 0 and all(c.prefix_pages == 0 for c in comps)
    assert len({tuple(int(t) for t in c.ids) for c in comps}) == 1


def test_training_the_family_is_a_named_error():
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer

    cfg = latent.tiny_config()
    with pytest.raises(ServedOnlyError, match="served only"):
        create_train_state(jax.random.PRNGKey(0), cfg, make_optimizer(1e-4), SingleDevice())
    with pytest.raises(ServedOnlyError):
        latent.forward(latent.init_params(jax.random.PRNGKey(0), cfg), cfg, jnp.zeros((1, 8), jnp.int32),
                       deterministic=False)


def test_family_gives_each_config_its_module():
    assert family(GPTConfig()) is gpt and family(latent.tiny_config()) is latent
    with pytest.raises(TypeError, match="no block family"):
        family(object())
    for module in (gpt, latent):
        for name in ("init_params", "forward", "forward_cached", "init_kv_cache", "init_paged_cache", "page_kinds",
                     "select_lanes", "merge_lanes", "max_context", "cached_decode_exact", "kv_heads"):
            assert callable(getattr(module, name)), (module.__name__, name)


def test_the_serve_programs_name_no_model():
    """decode.py, engine.py and sampling.py reach the model through its
    family: none of them imports gpt or reads a GPT size."""
    import re

    for rel in ("tpukit/serve/decode.py", "tpukit/serve/engine.py", "tpukit/sampling.py"):
        text = (ROOT / rel).read_text()
        code = "\n".join(line.split("#")[0] for line in text.split('"""')[::2] for line in line.splitlines())
        assert not re.search(r"\bgpt\.", code), rel
        assert not re.search(r"cfg\.(heads|head_dim|max_position_embeddings)\b", code), rel
        assert "moe_rows" not in code and "expert_rows" not in code, rel  # a family's counters come through `counters`


def test_config_from_published_keys():
    config = common.load_json(ROOT / "benchmark" / "configs" / "dots3-note-prev.json")
    cfg = latent.config_from_hf(config)
    assert cfg.num_layers == 5 and cfg.layer_types[:3] == (latent.FULL, latent.FULL, latent.WINDOW)
    assert (cfg.n_experts, cfg.experts_held, cfg.experts_per_token, cfg.vocab_size) == (256, 32, 8, 19008)
    assert (cfg.full.heads, cfg.full.kv_rank, cfg.window.heads, cfg.window.kv_rank, cfg.window_size) == (128, 512, 64, 1024, 513)
    assert latent.max_context(cfg) == 524288
    shapes = jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg))
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) == config["held"]["parameters"]
    full, window = latent.page_kinds(cfg, 16, "bf16")
    assert full.page_bytes == 16 * 1408 and window.page_bytes == 16 * 2176 and window.ring_pages == 34


def test_gpt_prefill_lanes_seam_is_the_block_table_cut():
    cache = {"k": jnp.zeros((1, 3, 1, 2, 2)), "bt": jnp.arange(8).reshape(4, 2)}
    sub = gpt.select_lanes(cache, jnp.asarray([2, 0]), jnp.asarray([5, 5]))
    assert sub["bt"].tolist() == [[4, 5], [0, 1]] and sub["k"] is cache["k"]
    assert gpt.merge_lanes(cache, dict(sub, k="written"))["bt"] is cache["bt"]
    assert serve_decode.prefill_chunk_paged is not None


# The GPT twin goes through the same seam and its serve programs are what
# they were: sha256 (first 16 hex) of the lowered module text of each program
# at the parent of PR 27 (commit 897bbb4), taken with the script below on the
# parent's tree. A PR that means to change a GPT serve program re-records them.
GPT_PROGRAM_HASHES = {
    ("f32", "decode_step", 1): "0cca0c1af62815a7", ("f32", "decode_step", 4): "93e578de675c5dad",
    ("f32", "prefill_chunk_paged", 0): "382ba71ca55a2167",
    ("bf16", "decode_step", 1): "ae027ea485edd30f", ("bf16", "decode_step", 4): "b6e0e4033305c50f",
    ("bf16", "prefill_chunk_paged", 0): "b762680629f38b04",
    ("int8", "decode_step", 1): "7a71e94da1c59199", ("int8", "decode_step", 4): "ef527ae747b08f51",
    ("int8", "prefill_chunk_paged", 0): "1a96bf525a38181e",
    ("ring", "decode_step", 4): "71261d76166b9533", ("ring", "prefill_slots", 0): "5d3a8c298f0b2507",
    ("ring", "decode_loop", 0): "a35b6475474b3a20",
}


@pytest.mark.parametrize("kv,program,steps", sorted(GPT_PROGRAM_HASHES))
def test_gpt_serve_programs_are_byte_equal_to_the_parents(kv, program, steps):
    import hashlib

    cfg = GPTConfig(dim=64, head_dim=16, heads=4, num_layers=2, vocab_size=97, max_position_embeddings=64)
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    n, per = 4, 6
    z = lambda shape, dt: jnp.zeros(shape, dt)  # noqa: E731
    if kv == "ring":
        cache, width = gpt.init_kv_cache(cfg, n, 48), 48
    else:
        page = 16 if kv == "int8" else 8
        cache, width = paged.init_paged_cache(cfg, n * per + 1, page, per, n, kv), per * page
    state = (z((n, width), jnp.int32), cache, z((n,), jnp.int32), z((n,), bool), z((n,), jnp.int32),
             z((n, 2), jnp.uint32))
    if program == "decode_step":
        lowered = serve_decode.decode_step.lower(params, cfg, *state, 96, 0.0, 0, None, steps=steps)
    elif program == "prefill_chunk_paged":
        lowered = serve_decode.prefill_chunk_paged.lower(
            params, cfg, *state, z((2,), jnp.int32), z((2, 16), jnp.int32), z((2,), jnp.int32), z((2,), bool),
            z((2,), jnp.int32), z((2,), jnp.int32), z((2, 2), jnp.uint32))
    elif program == "prefill_slots":
        lowered = serve_decode.prefill_slots.lower(
            params, cfg, *state, z((2,), jnp.int32), z((2, 16), jnp.int32), z((2,), jnp.int32), z((2,), jnp.int32),
            z((2, 2), jnp.uint32))
    else:
        lowered = serve_decode.decode_loop.lower(params, cfg, state[0], z((n,), jnp.int32), 8, 96)
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] == GPT_PROGRAM_HASHES[kv, program, steps]


# The latent family's own serve programs at `tiny_config()`, re-recorded when
# the full layers' key selection became a mask over the lane's keys attended
# by the key-block walk (the change on top of commit adc8f13; first recorded on
# commit 81c4036): the second published form of the family (grouped
# differential heads, streams, PolyNorm) is switched by the config alone and
# leaves this one's programs byte-equal.
LATENT_PROGRAM_HASHES = {
    ("f32", "decode_step", 1): "e32408e8350e4512", ("f32", "decode_step", 4): "ef2270706ccb6c80",
    ("f32", "prefill_chunk_paged", 0): "d789a1c78ef010af",
    ("bf16", "decode_step", 1): "787a2dcebe157198", ("bf16", "decode_step", 4): "521b4e7b648e7961",
    ("bf16", "prefill_chunk_paged", 0): "824daa940b1573e8",
}


@pytest.mark.parametrize("kv,program,steps", sorted(LATENT_PROGRAM_HASHES))
def test_latent_serve_programs_are_byte_equal_to_the_parents(kv, program, steps):
    import hashlib

    cfg = latent.tiny_config()
    params = latent.init_params(jax.random.PRNGKey(0), cfg)
    n, per = 4, 6
    z = lambda shape, dt: jnp.zeros(shape, dt)  # noqa: E731
    ring = latent.page_kinds(cfg, PAGE, kv)[1].ring_pages
    cache = latent.init_paged_cache(cfg, {"bt": n * per + 1, "bt_w": n * ring + 1}, PAGE, per, n, kv)
    state = (z((n, per * PAGE), jnp.int32), cache, z((n,), jnp.int32), z((n,), bool), z((n,), jnp.int32),
             z((n, 2), jnp.uint32))
    if program == "decode_step":
        lowered = serve_decode.decode_step.lower(params, cfg, *state, 96, 0.0, 0, None, steps=steps)
    else:
        lowered = serve_decode.prefill_chunk_paged.lower(
            params, cfg, *state, z((2,), jnp.int32), z((2, CHUNK), jnp.int32), z((2,), jnp.int32), z((2,), bool),
            z((2,), jnp.int32), z((2,), jnp.int32), z((2, 2), jnp.uint32))
    assert hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16] == LATENT_PROGRAM_HASHES[kv, program, steps]

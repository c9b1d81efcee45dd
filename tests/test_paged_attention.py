"""Fused paged decode (round 21, ROADMAP #2/#4): the Pallas
paged-attention kernel (tpukit/ops/paged_attention.py) and the on-device
scheduler window (decode.decode_loop_window), both behind
`--fused_decode`.

Contracts pinned here:
  - the kernel is the gathered-view reference (`paged.gather_view` +
    `_attend_over_cache` math) op-for-op: logits agree to the ~1-ULP dot
    reassociation of the backend (interpret mode *scans* the grid, so
    kernel dots compile inside a loop body and XLA:CPU picks a different
    accumulation order than the eager einsum — measured max ~5e-7 f32 at
    test shapes, and NOT reducible by barriers), while TOKEN streams are
    exactly identical — greedy and fixed-seed sampled, at the forward,
    decode_step, and full-engine levels;
  - a one-position window degenerates to the fresh token exactly, and
    positions beyond the cursor never contribute: null/garbage/recycled
    page ids behind the cursor are annihilated bit-for-bit (the ragged
    block-table story);
  - int8 pages dequantize in-kernel on the quant_comm block layout to
    the same values the gather path dequantizes — token agreement >= 90%
    is the gate (in practice 100% at test scale; int8 is lossy vs f32,
    never vs the unfused int8 path);
  - decode_loop_window == repeated decode_step for ANY window schedule,
    including early exit on the freed-page account — ticks/freed report
    what actually ran, and resuming after an early exit lands on the
    same stream;
  - under the model-only TP mesh the fused step and the whole while-loop
    window move EXACTLY `decode_step_comm(paged=True)` — the kernel adds
    no comm (shard_map, zero body collectives) and the loop body's
    collectives appear ONCE regardless of window size — with zero
    involuntary-remat warnings;
  - bad layouts fail with NAMED errors (VMEM budget, int8 quant-block
    tiling, fused without the paged cache), never Mosaic/XLA shape
    errors;
  - the fused engine's traces stay complete (1.0) with window-granular
    quantum spans whose `steps` is the device-reported tick count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.data import WordTokenizer, synthetic_stories
from tpukit.model import GPTConfig, init_params
from tpukit.model import gpt
from tpukit.ops import quant_comm
from tpukit.ops import paged_attention as pa
from tpukit.ops.pallas_attention import online_softmax_update
from tpukit.sampling import _decode_loop_cached
from tpukit.serve import ServeConfig, ServeEngine, synthetic_request_stream
from tpukit.serve import decode as sd
from tpukit.serve import paged as paged_lib

MAX_NEW = 10


@pytest.fixture(scope="module")
def tok():
    return WordTokenizer(synthetic_stories(64))


@pytest.fixture(scope="module")
def cfg(tok):
    return GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=tok.vocab_size,
        max_position_embeddings=96, compute_dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def params(cfg):
    return init_params(jax.random.PRNGKey(1), cfg)


def _serial(params, cfg, ids, max_new=MAX_NEW, eos_id=None, temperature=0.0,
            top_k=0, seed=0):
    ids = np.asarray(ids, np.int32)
    buf = np.zeros((1, len(ids) + max_new), np.int32)
    buf[0, : len(ids)] = ids
    out, length = _decode_loop_cached(
        params, cfg, jnp.asarray(buf), len(ids), max_new, int(eos_id),
        temperature=float(temperature),
        top_k=min(int(top_k), cfg.padded_vocab_size),
        rng=jnp.asarray(np.asarray(jax.random.PRNGKey(seed)))
        if temperature > 0.0
        else None,
    )
    return np.asarray(out)[0, : int(length)]


_ref_attend = pa.paged_attend_reference  # the kernel's contract, unfused


def _rand_kernel_operands(dtype=jnp.float32, h=4, p=8, d=8, mp=3, n=4,
                          seed=0):
    np_pages = n * mp + 1
    rng = np.random.RandomState(seed)
    pool_k = jnp.asarray(rng.randn(np_pages, h, p, d), dtype)
    pool_v = jnp.asarray(rng.randn(np_pages, h, p, d), dtype)
    bt = jnp.asarray(np.arange(1, n * mp + 1).reshape(n, mp), jnp.int32)
    start = jnp.asarray([5, 0, 17, 23], jnp.int32)[:n]
    q = jnp.asarray(rng.randn(n, h, d), dtype)
    kn = jnp.asarray(rng.randn(n, h, d), dtype)
    vn = jnp.asarray(rng.randn(n, h, d), dtype)
    return pool_k, pool_v, bt, start, q, kn, vn


# ---------------------------------------------------------------------------
# The owner helper's exactness argument: one call == plain softmax, bit
# for bit. This degeneracy is what lets the one-block kernel claim the
# reference's math rather than "a flash approximation of it".
# ---------------------------------------------------------------------------


def test_online_softmax_single_call_is_plain_softmax():
    s = jnp.asarray(np.random.RandomState(0).randn(4, 24) * 3, jnp.float32)
    m0 = jnp.full((4, 1), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((4, 1), jnp.float32)
    m, l, corr, p = online_softmax_update(m0, l0, s)
    ref = jax.nn.softmax(s, axis=-1)
    np.testing.assert_array_equal(np.asarray(p / l), np.asarray(ref))
    np.testing.assert_array_equal(np.asarray(m), np.asarray(jnp.max(s, -1, keepdims=True)))


# ---------------------------------------------------------------------------
# Kernel vs the gathered reference.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 1e-5),
                                        (jnp.bfloat16, 5e-2)],
                         ids=["f32", "bf16"])
def test_paged_attend_matches_gathered_reference(dtype, atol):
    ops = _rand_kernel_operands(dtype)
    out = pa.paged_attend(ops[0], ops[1], None, None, *ops[2:])
    ref = _ref_attend(ops[0], ops[1], None, None, *ops[2:])
    assert out.dtype == ref.dtype == dtype
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), atol=atol, rtol=0)


def test_paged_attend_ragged_block_tables():
    """The block-table edge cases the engine actually produces: a cursor
    at 0 (fresh token only — the softmax over ONE position must return
    v_new exactly), a partially filled last page, page ids recycled
    across rows, and garbage pages behind the cursor (a freed page
    re-issued full of another request's K/V must be annihilated — the
    output may not depend on what the masked tail points at)."""
    pool_k, pool_v, bt, start, q, kn, vn = _rand_kernel_operands()
    # cursor 0: only the fresh token is in-window -> exact passthrough
    out = pa.paged_attend(pool_k, pool_v, None, None, bt, start, q, kn, vn)
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(vn[1]))
    # start=17 (row 2) is a partially filled last page; all rows match
    # the gathered reference
    ref = _ref_attend(pool_k, pool_v, None, None, bt, start, q, kn, vn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=0)
    # masked-tail independence: rows 0/1 sit early in their windows, so
    # repoint their tail pages at garbage (large finite values, and page
    # ids RECYCLED from other rows' tables) — output must not move a bit
    poison_k = pool_k.at[5].set(1e3).at[9].set(-1e3)
    poison_v = pool_v.at[5].set(1e3).at[9].set(-1e3)
    bt2 = np.asarray(bt).copy()
    bt2[0, 1:] = (5, 9)   # row 0 tail -> poisoned pages
    bt2[1, :] = (9, 5, 9)  # row 1 (cursor 0): EVERY page garbage + repeated
    out2 = pa.paged_attend(poison_k, poison_v, None, None,
                           jnp.asarray(bt2), start, q, kn, vn)
    np.testing.assert_array_equal(np.asarray(out2[:2]), np.asarray(out[:2]))


def test_paged_attend_int8_matches_gather_dequant():
    """int8 pools dequantize INSIDE the kernel tile-by-tile on the
    quant_comm block layout; the gather path dequantizes after the
    gather. Same blocks, same scales — the values must agree to the same
    ~1-ULP reassociation bar as f32."""
    h, p, d, mp, n = 4, 8, 32, 3, 4  # page*head_dim == 256 == quant block
    np_pages = n * mp + 1
    rng = np.random.RandomState(3)
    raw_k = jnp.asarray(rng.randn(np_pages, h, p * d), jnp.float32) * 0.3
    raw_v = jnp.asarray(rng.randn(np_pages, h, p * d), jnp.float32) * 0.3
    qk, sk = quant_comm.quantize_blocks(raw_k)
    qv, sv = quant_comm.quantize_blocks(raw_v)
    pool_k = qk.reshape(np_pages, h, p, d)
    pool_v = qv.reshape(np_pages, h, p, d)
    bt = jnp.asarray(np.arange(1, n * mp + 1).reshape(n, mp), jnp.int32)
    start = jnp.asarray([5, 0, 17, 23], jnp.int32)
    q = jnp.asarray(rng.randn(n, h, d), jnp.float32)
    kn = jnp.asarray(rng.randn(n, h, d), jnp.float32)
    vn = jnp.asarray(rng.randn(n, h, d), jnp.float32)
    out = pa.paged_attend(pool_k, pool_v, sk, sv, bt, start, q, kn, vn)
    ref = _ref_attend(pool_k, pool_v, sk, sv, bt, start, q, kn, vn)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=0)


def test_paged_attend_named_errors(monkeypatch):
    ops = _rand_kernel_operands()
    monkeypatch.setattr(pa, "_PAGED_VMEM_BYTES", 1024)
    with pytest.raises(ValueError, match="VMEM"):
        pa.paged_attend(ops[0], ops[1], None, None, *ops[2:])
    monkeypatch.undo()
    # int8 with page*head_dim == 64: does not tile into 256-elem blocks
    pool8 = jnp.zeros(ops[0].shape, jnp.int8)
    scales = jnp.ones(ops[0].shape[:2] + (1,), jnp.float32)
    with pytest.raises(ValueError, match="quant blocks"):
        pa.paged_attend(pool8, pool8, scales, scales, *ops[2:])


# ---------------------------------------------------------------------------
# forward_cached with fused_decode: same logits (~1 ULP), same tokens
# (exactly), same write-back (bit-for-bit — the pool write is the SHARED
# path, only the read is fused).
# ---------------------------------------------------------------------------


def _fresh_cache(cfg, slots=4, page=8, mp=3, kv="f32", fill_seed=None):
    num_pages = slots * mp + 1
    cache = paged_lib.init_paged_cache(cfg, num_pages, page, mp, slots, kv)
    cache["bt"] = jnp.asarray(
        np.arange(1, slots * mp + 1).reshape(slots, mp), jnp.int32)
    if fill_seed is not None:
        cache = dict(
            cache,
            k=jax.random.normal(jax.random.PRNGKey(fill_seed),
                                cache["k"].shape, jnp.float32) * 0.3,
            v=jax.random.normal(jax.random.PRNGKey(fill_seed + 1),
                                cache["v"].shape, jnp.float32) * 0.3,
        )
    return cache


def test_fused_forward_cached_parity(cfg, params):
    slots = 4
    cache = _fresh_cache(cfg, slots, fill_seed=1)
    tok_ids = jnp.asarray(
        np.random.RandomState(0).randint(0, cfg.vocab_size, (slots, 1)),
        jnp.int32)
    start = jnp.asarray([5, 1, 17, 23], jnp.int32)
    wm = jnp.asarray([True, True, True, False])  # one frozen lane
    lu, cu = gpt.forward_cached(params, cfg, tok_ids, start[:, None],
                                dict(cache), start, write_mask=wm)
    lf, cf = gpt.forward_cached(params, cfg.replace(fused_decode=True),
                                tok_ids, start[:, None], dict(cache), start,
                                write_mask=wm)
    assert float(jnp.max(jnp.abs(lu - lf))) < 1e-5
    np.testing.assert_array_equal(
        np.asarray(jnp.argmax(lu[:, -1], -1)),
        np.asarray(jnp.argmax(lf[:, -1], -1)))
    # write-back is the SHARED path: layer 0 (same activations in) lands
    # bit-identically; deeper layers' K/V projections see the previous
    # layer's ~1-ULP attention wobble, so they agree to the same bar as
    # the logits
    for key in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(cu[key][0]),
                                      np.asarray(cf[key][0]),
                                      err_msg=f"cache[{key}] layer 0")
        np.testing.assert_allclose(np.asarray(cu[key]), np.asarray(cf[key]),
                                   atol=1e-5, rtol=0,
                                   err_msg=f"cache[{key}]")
    np.testing.assert_array_equal(np.asarray(cu["bt"]), np.asarray(cf["bt"]))


def test_fused_forward_int8_token_agreement(cfg, params):
    """The issue's int8 gate: >= 90% greedy token agreement between the
    fused kernel (in-kernel dequant) and the unfused gather-then-dequant
    path, over the SAME quantized pools."""
    cfg8 = cfg.replace(head_dim=32)  # page*head_dim == 256
    params8 = init_params(jax.random.PRNGKey(1), cfg8)
    slots, page, mp = 4, 8, 3
    cache = _fresh_cache(cfg8, slots, page, mp, kv="int8")
    rng = np.random.RandomState(3)
    for nm, snm in (("k", "ks"), ("v", "vs")):
        raw = jnp.asarray(
            rng.randn(cfg8.num_layers, slots * mp + 1, cfg8.heads,
                      page * cfg8.head_dim), jnp.float32) * 0.3
        q8, s8 = quant_comm.quantize_blocks(raw)
        cache[nm] = q8.reshape(cfg8.num_layers, slots * mp + 1, cfg8.heads,
                               page, cfg8.head_dim)
        cache[snm] = s8
    tok_ids = jnp.asarray(rng.randint(0, cfg8.vocab_size, (slots, 1)),
                          jnp.int32)
    start = jnp.asarray([5, 1, 17, 23], jnp.int32)
    wm = jnp.ones((slots,), bool)
    lu, _ = gpt.forward_cached(params8, cfg8, tok_ids, start[:, None],
                               dict(cache), start, write_mask=wm)
    lf, _ = gpt.forward_cached(params8, cfg8.replace(fused_decode=True),
                               tok_ids, start[:, None], dict(cache), start,
                               write_mask=wm)
    agree = jnp.mean(jnp.argmax(lu[:, -1], -1) == jnp.argmax(lf[:, -1], -1))
    assert float(agree) >= 0.9


@pytest.mark.parametrize("temperature,top_k", [(0.0, 0), (0.8, 5)],
                         ids=["greedy", "sampled_topk"])
def test_fused_decode_steps_token_parity(cfg, params, temperature, top_k):
    """12 decode ticks from a shared prompt state: the fused and unfused
    buffers (and cursors) must be IDENTICAL — greedy and fixed-seed
    sampled. Sampling folds each lane's own cursor, so ~1-ULP logit
    wobble may only flip a token if it flips the argmax/top-k order —
    pinning exact equality here is the real parity bar."""
    slots, page, mp = 4, 8, 3
    tok_ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (slots,))
    buf = jnp.zeros((slots, mp * page), jnp.int32).at[:, 0].set(tok_ids)
    cursors = jnp.ones((slots,), jnp.int32)
    active = jnp.ones((slots,), bool)
    limits = jnp.full((slots,), 20, jnp.int32)
    keys = jnp.stack([jax.random.PRNGKey(100 + i)
                      for i in range(slots)]).astype(jnp.uint32)
    outs = {}
    for fused in (False, True):
        c = cfg.replace(fused_decode=fused)
        st = (buf, _fresh_cache(cfg, slots, page, mp), cursors, active)
        for _ in range(12):
            st = sd.decode_step(params, c, st[0], st[1], st[2], st[3],
                                limits, keys, 3, temperature, top_k, None,
                                steps=1)
        outs[fused] = (np.asarray(st[0]), np.asarray(st[2]))
    np.testing.assert_array_equal(outs[False][0], outs[True][0])
    np.testing.assert_array_equal(outs[False][1], outs[True][1])


# ---------------------------------------------------------------------------
# The on-device scheduler window.
# ---------------------------------------------------------------------------


def _loop_state(cfg, slots=4, page=8, mp=3):
    tok_ids = np.random.RandomState(0).randint(0, cfg.vocab_size, (slots,))
    buf = jnp.zeros((slots, mp * page), jnp.int32).at[:, 0].set(tok_ids)
    keys = jnp.stack([jax.random.PRNGKey(100 + i)
                      for i in range(slots)]).astype(jnp.uint32)
    return (buf, _fresh_cache(cfg, slots, page, mp),
            jnp.ones((slots,), jnp.int32), jnp.ones((slots,), bool), keys)


def test_decode_loop_window_equals_repeated_steps(cfg, params):
    cfgf = cfg.replace(fused_decode=True)
    buf, cache, cursors, active, keys = _loop_state(cfg)
    limits = jnp.full((4,), 10, jnp.int32)
    ph = jnp.full((4,), 3, jnp.int32)
    st = (buf, dict(cache), cursors, active)
    for _ in range(8):
        st = sd.decode_step(params, cfgf, st[0], st[1], st[2], st[3],
                            limits, keys, 3, 0.0, 0, None, steps=1)
    b2, c2, cur2, act2, ticks, freed = sd.decode_loop_window(
        params, cfgf, buf, dict(cache), cursors, active, limits, keys,
        ph, jnp.asarray(8, jnp.int32), jnp.asarray(1 << 30, jnp.int32),
        3, 0.0, 0, None)
    assert int(ticks) == 8 and int(freed) == 0
    np.testing.assert_array_equal(np.asarray(b2), np.asarray(st[0]))
    np.testing.assert_array_equal(np.asarray(cur2), np.asarray(st[2]))
    np.testing.assert_array_equal(np.asarray(act2), np.asarray(st[3]))
    for key in c2:
        np.testing.assert_array_equal(np.asarray(c2[key]),
                                      np.asarray(st[1][key]))


def test_decode_loop_window_early_exit_resumes_on_stream(cfg, params):
    """Lane 0's limit trips on tick 2, releasing its 3 pages >= the
    stop_when_freed target: the loop must hand control back EARLY
    (ticks=2, freed=3) — and resuming for the remaining ticks must land
    bit-for-bit on the same stream as the uninterrupted window (the
    schedule-invariance that makes early exit free)."""
    cfgf = cfg.replace(fused_decode=True)
    buf, cache, cursors, active, keys = _loop_state(cfg)
    limits = jnp.asarray([3, 10, 10, 10], jnp.int32)
    ph = jnp.full((4,), 3, jnp.int32)
    full = sd.decode_loop_window(
        params, cfgf, buf, dict(cache), cursors, active, limits, keys,
        ph, jnp.asarray(8, jnp.int32), jnp.asarray(1 << 30, jnp.int32),
        3, 0.0, 0, None)
    b1, c1, cur1, act1, t1, f1 = sd.decode_loop_window(
        params, cfgf, buf, dict(cache), cursors, active, limits, keys,
        ph, jnp.asarray(8, jnp.int32), jnp.asarray(3, jnp.int32),
        3, 0.0, 0, None)
    assert int(t1) == 2 and int(f1) == 3
    assert not bool(act1[0]) and bool(act1[1])
    b2, c2, cur2, act2, t2, _ = sd.decode_loop_window(
        params, cfgf, b1, c1, cur1, act1, limits, keys,
        ph, jnp.asarray(8 - int(t1), jnp.int32),
        jnp.asarray(1 << 30, jnp.int32), 3, 0.0, 0, None)
    assert int(t1) + int(t2) == int(full[4]) == 8
    np.testing.assert_array_equal(np.asarray(b2), np.asarray(full[0]))
    np.testing.assert_array_equal(np.asarray(cur2), np.asarray(full[2]))
    np.testing.assert_array_equal(np.asarray(act2), np.asarray(full[3]))


# ---------------------------------------------------------------------------
# TP comm audits: the fused step and the whole window both move exactly
# decode_step_comm(paged=True) — the kernel adds no collectives and the
# while body is compiled (and counted) once at any window size.
# ---------------------------------------------------------------------------


def _tp_paged_state(cfg, mesh, slots, kv_dtype="f32", page=8, mp=3):
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from tpukit.shardings import TensorParallel

    strat = TensorParallel(mesh)
    params = init_params(jax.random.PRNGKey(0), cfg)
    psh = strat.state_sharding(jax.eval_shape(lambda: params))
    params = jax.tree.map(jax.device_put, params, psh)
    sh = lambda spec: NamedSharding(mesh, spec)
    num_pages = slots * mp + 1
    tree = paged_lib.init_paged_cache(cfg, num_pages, page, mp, slots, kv_dtype)
    specs = {"k": P(None, None, "model", None, None),
             "v": P(None, None, "model", None, None),
             "ks": P(None, None, "model", None),
             "vs": P(None, None, "model", None), "bt": P()}
    cache = {k: jax.device_put(np.asarray(v), sh(specs[k]))
             for k, v in tree.items()}
    bt = np.arange(1, slots * mp + 1, dtype=np.int32).reshape(slots, mp)
    cache["bt"] = jax.device_put(bt, sh(P()))
    w = mp * page
    buf = jax.device_put(np.zeros((slots, w), np.int32), sh(P(None, None)))
    cursors = jax.device_put(np.full((slots,), 5, np.int32), sh(P(None)))
    active = jax.device_put(np.ones((slots,), bool), sh(P(None)))
    limits = jax.device_put(np.full((slots,), 12, np.int32), sh(P(None)))
    keys = jax.device_put(np.zeros((slots, 2), np.uint32), sh(P(None, None)))
    return params, buf, cache, cursors, active, limits, keys


@pytest.mark.parametrize(
    "kv_dtype,temperature,top_k",
    [("f32", 0.0, 0), ("f32", 0.9, 5), ("int8", 0.0, 0)],
    ids=["f32_greedy", "f32_topk", "int8_greedy"],
)
def test_tp_fused_decode_step_hlo_comm_audit(kv_dtype, temperature, top_k):
    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import capture_compiler_stderr, collective_bytes

    head_dim = 32 if kv_dtype == "int8" else 8
    cfg = GPTConfig(
        dim=32, head_dim=head_dim, heads=4, num_layers=2, vocab_size=160,
        max_position_embeddings=64, compute_dtype=jnp.float32,
        fused_decode=True,
    )
    mesh = create_mesh({"model": 4})
    slots = 4
    state = _tp_paged_state(cfg, mesh, slots, kv_dtype)
    params, buf, cache, cursors, active, limits, keys = state
    with capture_compiler_stderr(check=True):
        compiled = sd.decode_step.lower(
            params, cfg, buf, cache, cursors, active, limits, keys,
            1, temperature, top_k, mesh,
        ).compile()
    measured = collective_bytes(compiled.as_text())
    expected = sd.decode_step_comm(cfg, mesh, slots, top_k=top_k, paged=True)
    assert measured == expected, (measured, expected)


def test_tp_sched_loop_hlo_comm_audit():
    """The whole fused window lowered as one program: collective_bytes
    over the compiled HLO must STILL equal the per-step closed form —
    the while body's collectives appear once, so the audit is window-
    size-invariant (max_ticks/stop_when_freed are traced scalars; the
    same executable serves every window)."""
    from jax.sharding import PartitionSpec as P
    from jax.sharding import NamedSharding

    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import capture_compiler_stderr, collective_bytes

    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=160,
        max_position_embeddings=64, compute_dtype=jnp.float32,
        fused_decode=True,
    )
    mesh = create_mesh({"model": 4})
    slots = 4
    state = _tp_paged_state(cfg, mesh, slots, "f32")
    params, buf, cache, cursors, active, limits, keys = state
    ph = jax.device_put(np.full((slots,), 3, np.int32),
                        NamedSharding(mesh, P(None)))
    with capture_compiler_stderr(check=True):
        compiled = sd.decode_loop_window.lower(
            params, cfg, buf, cache, cursors, active, limits, keys,
            ph, jnp.asarray(8, jnp.int32), jnp.asarray(1 << 30, jnp.int32),
            3, 0.0, 0, mesh,
        ).compile()
    measured = collective_bytes(compiled.as_text())
    expected = sd.decode_step_comm(cfg, mesh, slots, top_k=0, paged=True)
    assert measured == expected, (measured, expected)


# ---------------------------------------------------------------------------
# The full engine behind --fused_decode: same streams as the unfused
# engine (which is itself serial-exact) on the round-15 tight pool, with
# correct device-reported step accounting and complete traces.
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "temperature,top_k,stream_seed",
    [(0.0, 0, 3), (0.9, 5, 11)],
    ids=["greedy", "sampled_topk"],
)
def test_fused_engine_tight_pool_parity(tok, cfg, params, temperature, top_k,
                                        stream_seed):
    serve_kw = dict(slots=3, buckets=(8, 16), max_new_tokens=MAX_NEW,
                    temperature=temperature, top_k=top_k, window_steps=8,
                    page_size=4, num_pages=12)
    reqs = synthetic_request_stream(
        tok, 8, seed=stream_seed, max_new_tokens=MAX_NEW, buckets=(8, 16),
        qps=50.0 if temperature else 0.0,
    )
    outs = {}
    for fused in (False, True):
        eng = ServeEngine(params, cfg,
                          ServeConfig(**serve_kw, fused_decode=fused),
                          eos_id=int(tok.eos_token_id))
        outs[fused] = {c.rid: c
                       for c in eng.run(list(reqs), max_wall_s=300)}
        if fused:
            assert not eng._lanes and len(eng._free) == 3
            assert eng.allocator.live_pages == 0
            assert eng.steps > 0  # device-reported ticks landed
    assert outs[True].keys() == outs[False].keys() == {r.rid for r in reqs}
    for rid, c in outs[True].items():
        np.testing.assert_array_equal(c.ids, outs[False][rid].ids,
                                      err_msg=f"rid {rid} vs unfused")
        want = _serial(params, cfg, c.ids[: c.prompt_len], MAX_NEW,
                       tok.eos_token_id, temperature, top_k,
                       seed=stream_seed + rid)
        np.testing.assert_array_equal(c.ids, want, err_msg=f"rid {rid}")


def test_fused_engine_trace_complete_with_window_quanta(tok, cfg, params):
    from tpukit.obs import TraceRecorder
    from tpukit.obs import trace as trace_lib

    serve = ServeConfig(slots=2, buckets=(8, 16), max_new_tokens=MAX_NEW,
                        window_steps=4, page_size=4, fused_decode=True,
                        decode_quantum=4)
    reqs = synthetic_request_stream(tok, 6, seed=3, max_new_tokens=MAX_NEW,
                                    buckets=(8, 16))
    tracer = TraceRecorder()
    eng = ServeEngine(params, cfg, serve, eos_id=int(tok.eos_token_id),
                      tracer=tracer)
    comps = eng.run(list(reqs), max_wall_s=300)
    assert len(comps) == 6
    trees = trace_lib.build_trees(tracer.snapshot())
    assert trace_lib.completeness(trees) == 1.0
    quanta = [e for e in tracer.snapshot() if e.get("ev") == "quantum"]
    assert quanta
    # window-granular spans: `steps` is the DEVICE-reported tick count —
    # at least one tick each, never more than the window, and summing to
    # the engine's step account
    assert all(1 <= e["steps"] <= serve.decode_quantum for e in quanta)
    assert sum(e["steps"] for e in quanta) == eng.steps


def test_fused_engine_requires_paged_cache():
    with pytest.raises(ValueError, match="fused_decode"):
        ServeConfig(slots=2, buckets=(8, 16), fused_decode=True)

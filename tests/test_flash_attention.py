"""Flash-attention kernel parity vs the XLA attention path (the reference
semantics): forward logit parity on non-padded rows, gradient parity for
q/k/v, and end-to-end model parity with attention_impl='flash'. Kernels run
in Pallas interpreter mode on the CPU mesh — the same code path the TPU
compiles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.model import GPTConfig, forward, init_params
from tpukit.ops.attention import causal_attention
from tpukit.ops.pallas_attention import flash_causal_attention

B, H, S, D = 2, 4, 48, 32  # short-sequence branch: one 48-wide block, no pad
SCALE = D**-0.5


@pytest.fixture(scope="module")
def qkv(request):
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    return mk(), mk(), mk()


@pytest.fixture(scope="module")
def pad_mask():
    mask = np.zeros((B, S), dtype=bool)
    mask[0, 40:] = True  # row 0 has trailing padding
    return jnp.asarray(mask)


def test_forward_matches_xla_no_mask(qkv):
    q, k, v = qkv
    ours = flash_causal_attention(q, k, v, scale=SCALE)
    ref = causal_attention(q, k, v, scale=SCALE)
    np.testing.assert_allclose(np.asarray(ours), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_forward_matches_xla_with_mask(qkv, pad_mask):
    q, k, v = qkv
    ours = flash_causal_attention(q, k, v, scale=SCALE, pad_mask=pad_mask)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=pad_mask)
    valid = ~np.asarray(pad_mask)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(ours)[b, :, valid[b]],
            np.asarray(ref)[b, :, valid[b]],
            atol=2e-5,
            rtol=1e-4,
        )


def test_grads_match_xla(qkv, pad_mask):
    q, k, v = qkv

    def loss_flash(q, k, v):
        out = flash_causal_attention(q, k, v, scale=SCALE, pad_mask=pad_mask)
        return jnp.sum(jnp.where(~pad_mask[:, None, :, None], out, 0.0) ** 2)

    def loss_ref(q, k, v):
        out = causal_attention(q, k, v, scale=SCALE, pad_mask=pad_mask)
        return jnp.sum(jnp.where(~pad_mask[:, None, :, None], out, 0.0) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=5e-4, rtol=1e-3,
            err_msg=f"d{name} mismatch",
        )


def test_model_end_to_end_flash(tiny_config, tiny_params, rng):
    """forward() with attention_impl='flash' reproduces the XLA model."""
    cfg_flash = tiny_config.replace(attention_impl="flash")
    ids = jnp.asarray(rng.randint(0, tiny_config.vocab_size, size=(2, 24)).astype(np.int32))
    pos = jnp.broadcast_to(jnp.arange(24, dtype=jnp.int32), (2, 24))
    mask = jnp.zeros((2, 24), dtype=bool).at[1, 20:].set(True)

    ref_logits = forward(tiny_params, tiny_config, ids, pos, mask)
    flash_logits = forward(tiny_params, cfg_flash, ids, pos, mask)
    np.testing.assert_allclose(
        np.asarray(flash_logits)[:, :20], np.asarray(ref_logits)[:, :20],
        atol=1e-4, rtol=1e-4,
    )


def test_padded_sequence_path():
    """S=130 > 128 and not lane-aligned: exercises the wrapper's pad-to-block
    path (seq_pad=256, padded query rows sliced off, padded key columns
    causally unreachable) — the regime where misaligned blocks once crashed
    Mosaic lowering."""
    rng = np.random.RandomState(3)
    s = 130
    q = jnp.asarray(rng.randn(1, 2, s, D).astype(np.float32))
    k = jnp.asarray(rng.randn(1, 2, s, D).astype(np.float32))
    v = jnp.asarray(rng.randn(1, 2, s, D).astype(np.float32))
    mask = jnp.zeros((1, s), dtype=bool).at[0, 120:].set(True)
    ours = flash_causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    np.testing.assert_allclose(
        np.asarray(ours)[0, :, :120], np.asarray(ref)[0, :, :120], atol=2e-5, rtol=1e-4
    )


def test_block_plan_alignment():
    """Every (block, seq_pad) the wrapper can produce must satisfy Mosaic's
    lane alignment: 128-multiples for seq >= 128, and seq_pad % block == 0."""
    from tpukit.ops.pallas_attention import _plan

    for seq in (1, 16, 48, 127, 128, 130, 255, 256, 511, 512, 520, 639, 1024, 2048, 8191):
        block, seq_pad = _plan(seq)
        assert seq_pad >= seq
        assert seq_pad % block == 0
        if seq >= 128:
            assert block % 128 == 0 and seq_pad % 128 == 0
        else:
            assert block % 16 == 0 and block == seq_pad


def test_multiblock_fused_and_split_backward(monkeypatch):
    """Multi-block grads on BOTH backward variants: the fused dkv+dq-partials
    kernel (num_k <= _DQ_FUSED_MAX_NUM_K) and the split two-kernel path that
    takes over for long sequences (no S^2-scaled dq partials in HBM). Block
    size is pinned to 128 so a 384-token sequence spans 3 blocks."""
    import tpukit.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "_BLOCK", 128)
    rng = np.random.RandomState(7)
    s = 384
    q, k, v = (jnp.asarray(rng.randn(1, 2, s, D), jnp.float32) for _ in range(3))
    mask = jnp.zeros((1, s), dtype=bool).at[0, 370:].set(True)

    def loss(fn):
        def f(q, k, v):
            out = fn(q, k, v, scale=SCALE, pad_mask=mask)
            return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)
        return f

    g_ref = jax.grad(loss(causal_attention), argnums=(0, 1, 2))(q, k, v)

    monkeypatch.setattr(pa, "_DQ_FUSED_MAX_NUM_K", 3)  # 3 blocks ride fused
    g_fused = jax.grad(loss(flash_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_fused, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=5e-4, rtol=1e-3,
            err_msg=f"fused d{name} mismatch",
        )

    monkeypatch.setattr(pa, "_DQ_FUSED_MAX_NUM_K", 1)  # force the split path
    g_split = jax.grad(loss(flash_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_split, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=5e-4, rtol=1e-3,
            err_msg=f"split d{name} mismatch",
        )

    # the byte-budget gate alone must also route to the split path (and
    # still match): a large-batch long-sequence config whose dq-partials
    # exceed _DQ_PARTIALS_BUDGET never allocates them
    monkeypatch.setattr(pa, "_DQ_FUSED_MAX_NUM_K", 3)
    monkeypatch.setattr(pa, "_DQ_PARTIALS_BUDGET", 1)  # bytes
    g_budget = jax.grad(loss(flash_causal_attention), argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_budget, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=5e-4, rtol=1e-3,
            err_msg=f"budget-gated d{name} mismatch",
        )


def test_auto_dispatch_gspmd_safe():
    """Under GSPMD-sharded jit on a multi-device mesh, impl='auto' is
    sharded-correct (on the CPU test backend it picks the XLA path; on TPU
    it picks the flash kernel, whose per-shard call the
    test_flash_under_dp_mesh tests below exercise explicitly)."""
    import jax.sharding as jsh

    from tpukit.mesh import create_mesh

    mesh = create_mesh({"data": 8})
    rng = np.random.RandomState(0)
    q = rng.randn(8, 2, 16, D).astype(np.float32)
    fn = jax.jit(
        lambda q: causal_attention(q, q, q, scale=SCALE, impl="auto"),
        in_shardings=jsh.NamedSharding(mesh, jsh.PartitionSpec("data")),
    )
    out = fn(q)
    ref = causal_attention(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q), scale=SCALE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5, rtol=1e-4)


def test_bf16_forward(qkv):
    q, k, v = (t.astype(jnp.bfloat16) for t in qkv)
    ours = flash_causal_attention(q, k, v, scale=SCALE)
    ref = causal_attention(q, k, v, scale=SCALE)
    assert ours.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(ours, dtype=np.float32), np.asarray(ref, dtype=np.float32),
        atol=3e-2, rtol=3e-2,
    )


def _dp_mesh():
    from tpukit.mesh import create_mesh

    return create_mesh({"data": 8})


def test_flash_under_dp_mesh(qkv, pad_mask):
    """VERDICT r1 #2: the kernel must keep working when its operands are
    GSPMD-sharded over a data mesh — given the mesh axes (`shard`) it runs
    per-shard with no collectives and no all-gather."""
    import jax.sharding as jsh

    mesh = _dp_mesh()
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(8, H, S, D), jnp.float32) for _ in range(3))
    mask = np.zeros((8, S), dtype=bool)
    mask[::2, 40:] = True
    mask = jnp.asarray(mask)

    sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
    fn = jax.jit(
        lambda q, k, v, m: flash_causal_attention(
            q, k, v, scale=SCALE, pad_mask=m, shard=(mesh, "data", None)
        ),
        in_shardings=(sh, sh, sh, sh),
    )
    out = fn(q, k, v, mask)
    assert out.sharding.spec == jsh.PartitionSpec("data")
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    valid = ~np.asarray(mask)
    for b in range(8):
        np.testing.assert_allclose(
            np.asarray(out)[b, :, valid[b]], np.asarray(ref)[b, :, valid[b]],
            atol=2e-5, rtol=1e-4,
        )
    # the partitioned kernel must not gather the sharded operands
    hlo = fn.lower(q, k, v, mask).compile().as_text()
    assert "all-gather" not in hlo


def test_flash_grads_under_dp_mesh(qkv):
    """Backward kernels partition too: sharded grads match unsharded."""
    import jax.sharding as jsh

    mesh = _dp_mesh()
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(8, H, S, D), jnp.float32) for _ in range(3))

    def loss(q, k, v, shard=None):
        return jnp.sum(
            flash_causal_attention(q, k, v, scale=SCALE, shard=shard) ** 2
        )

    g_ref = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
    sharded = lambda q, k, v: loss(q, k, v, shard=(mesh, "data", None))  # noqa: E731
    g_dp = jax.jit(jax.grad(sharded, argnums=(0, 1, 2)), in_shardings=(sh, sh, sh))(q, k, v)
    for a, b in zip(g_ref, g_dp):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-5, rtol=1e-4)


def test_flash_inside_shard_map():
    """The pipeline recipes call attention inside a Manual shard_map region;
    the kernel must compose there as well."""
    import jax.sharding as jsh
    from jax import shard_map

    mesh = _dp_mesh()
    rng = np.random.RandomState(5)
    q, k, v = (jnp.asarray(rng.randn(8, H, S, D), jnp.float32) for _ in range(3))
    P = jsh.PartitionSpec

    sm = shard_map(
        lambda q, k, v: flash_causal_attention(q, k, v, scale=SCALE),
        mesh=mesh, in_specs=(P("data"),) * 3, out_specs=P("data"), check_vma=False,
    )
    out = jax.jit(sm)(q, k, v)
    ref = causal_attention(q, k, v, scale=SCALE)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


# ---------------------------------------------------------------------------
# The causal walk inside a grid step (sub-blocks of the diagonal grid block)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("block,sub,computed,of", [
    (1024, 128, 36, 64), (1024, 256, 10, 16), (1024, 512, 3, 4), (256, 256, 1, 1),
    (640, 128, 15, 25),
])
def test_causal_walk_is_the_lower_triangle(block, sub, computed, of):
    """`causal_walk` is what says how often the skip engages: its pairs are
    the brute-force `k <= q` list, q-major, and their share of the n^2
    sub-blocks is the share of the score block a diagonal grid step
    computes; the panels the kernels walk cover exactly those pairs."""
    from tpukit.ops.pallas_attention import _walk_panels, causal_walk

    n = block // sub
    pairs = causal_walk(block, sub)
    assert pairs == [(q, k) for q in range(n) for k in range(n) if k <= q]
    assert (len(pairs), n * n) == (computed, of)
    # the panels the kernels take are those pairs, side by side from column 0
    assert [(q, k) for q, width in _walk_panels(block, sub) for k in range(width)] == pairs


@pytest.mark.parametrize("block,want", [(1024, 256), (512, 256), (256, 256), (128, 128),
                                         (640, 128), (384, 128), (48, 48), (16, 16)])
def test_sub_block_edge_adapts_from_the_shape(block, want):
    """No knob: a block no larger than one sub-block, and a short sequence's
    16-aligned block, is ONE sub-block; a 128-multiple that the module's
    edge does not divide walks the largest edge that divides both."""
    import tpukit.ops.pallas_attention as pa

    assert pa._SUB == 256  # the cases above are stated for the edge fixed on the chip
    assert pa._sub_edge(block) == want and block % want == 0


def _walk_sizes(monkeypatch, block, sub):
    import tpukit.ops.pallas_attention as pa

    monkeypatch.setattr(pa, "_BLOCK", block)
    monkeypatch.setattr(pa, "_SUB", sub)
    return pa


def _masked_sq_loss(fn, mask, **kw):
    keep = 1.0 if mask is None else ~mask[:, None, :, None]

    def f(q, k, v):
        out = fn(q, k, v, scale=SCALE, pad_mask=mask, **kw).astype(jnp.float32)
        return jnp.sum((out * keep) ** 2)

    return f


# (block, sub, tokens): one diagonal grid block of 4 x 4 sub-blocks; 2 x 2
# grid blocks of 2 x 2 sub-blocks (diagonal AND whole off-diagonal blocks);
# a length that is no lane multiple (padded to 2 grid blocks, rows sliced
# off); a block the edge does not divide (5 x 5 sub-blocks of 128)
WALKS = [(512, 128, 512), (256, 128, 512), (256, 128, 383), (1024, 256, 640)]


@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "padmask"])
@pytest.mark.parametrize("block,sub,s", WALKS, ids=lambda x: str(x))
def test_walk_forward_matches_xla(monkeypatch, block, sub, s, masked):
    _walk_sizes(monkeypatch, block, sub)
    rng = np.random.RandomState(11)
    q, k, v = (jnp.asarray(rng.randn(2, 2, s, D), jnp.float32) for _ in range(3))
    mask = jnp.zeros((2, s), dtype=bool).at[0, s - 77:].set(True) if masked else None
    ours = flash_causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    assert ours.shape == ref.shape
    valid = np.ones((2, s), bool) if mask is None else ~np.asarray(mask)
    for b in range(2):
        np.testing.assert_allclose(
            np.asarray(ours)[b, :, valid[b]], np.asarray(ref)[b, :, valid[b]],
            atol=2e-5, rtol=1e-4,
        )


@pytest.mark.parametrize("backward", ["fused", "split"])
@pytest.mark.parametrize("masked", [False, True], ids=["nomask", "padmask"])
@pytest.mark.parametrize("block,sub,s", WALKS, ids=lambda x: str(x))
def test_walk_grads_match_xla(monkeypatch, block, sub, s, masked, backward):
    """dq, dk and dv of the walked kernels against the XLA path: the fused
    backward (`flash_bwd`) and the split pair (`flash_dq`, `flash_dkv`)."""
    pa = _walk_sizes(monkeypatch, block, sub)
    monkeypatch.setattr(pa, "_DQ_FUSED_MAX_NUM_K", 4 if backward == "fused" else 0)
    rng = np.random.RandomState(12)
    q, k, v = (jnp.asarray(rng.randn(1, 2, s, D), jnp.float32) for _ in range(3))
    mask = jnp.zeros((1, s), dtype=bool).at[0, s - 77:].set(True) if masked else None
    g_ref = jax.grad(_masked_sq_loss(causal_attention, mask), argnums=(0, 1, 2))(q, k, v)
    g = jax.grad(_masked_sq_loss(flash_causal_attention, mask), argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=5e-4, rtol=1e-3,
            err_msg=f"{backward} d{name} mismatch",
        )


@pytest.mark.parametrize("backward", ["fused", "split"])
def test_walk_bf16_forward_and_grads(monkeypatch, backward):
    """bf16 operands, float32 scores and accumulators: output and all three
    gradients stay within bf16 rounding of the XLA path and keep the dtype."""
    pa = _walk_sizes(monkeypatch, 512, 128)
    monkeypatch.setattr(pa, "_DQ_FUSED_MAX_NUM_K", 4 if backward == "fused" else 0)
    rng = np.random.RandomState(13)
    q, k, v = (jnp.asarray(rng.randn(1, 2, 512, D), jnp.bfloat16) for _ in range(3))
    ours = flash_causal_attention(q, k, v, scale=SCALE)
    ref = causal_attention(q, k, v, scale=SCALE)
    assert ours.dtype == jnp.bfloat16
    np.testing.assert_allclose(
        np.asarray(ours, np.float32), np.asarray(ref, np.float32), atol=3e-2, rtol=3e-2)
    g = jax.grad(_masked_sq_loss(flash_causal_attention, None), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(_masked_sq_loss(causal_attention, None), argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g, g_ref, "qkv"):
        assert ours.dtype == jnp.bfloat16
        a, b = np.asarray(ours, np.float32), np.asarray(ref, np.float32)
        assert np.abs(a - b).max() <= 0.05 * np.abs(b).max(), f"d{name}"


def test_walk_fully_padded_row_stays_finite_and_uniform(monkeypatch):
    """A batch row whose every key is padding: each query row's scores are
    all finfo.min, so it softmaxes uniformly over the columns of the
    sub-blocks the walk computes for it (up to the end of its diagonal
    sub-block) and nothing is NaN; the other batch row is untouched, and
    the gradients stay finite."""
    _walk_sizes(monkeypatch, 512, 128)
    rng = np.random.RandomState(14)
    s = 512
    q, k, v = (jnp.asarray(rng.randn(2, 2, s, D), jnp.float32) for _ in range(3))
    mask = jnp.zeros((2, s), dtype=bool).at[1].set(True)
    ours = np.asarray(flash_causal_attention(q, k, v, scale=SCALE, pad_mask=mask))
    ref = np.asarray(causal_attention(q, k, v, scale=SCALE, pad_mask=mask))
    np.testing.assert_allclose(ours[0], ref[0], atol=2e-5, rtol=1e-4)
    assert np.isfinite(ours).all()
    for i in (0, 127, 128, 300, 511):
        upto = (i // 128 + 1) * 128
        np.testing.assert_allclose(
            ours[1, :, i], np.asarray(v)[1, :, :upto].mean(axis=1), atol=2e-5, rtol=1e-4)
    grads = jax.grad(_masked_sq_loss(flash_causal_attention, mask), argnums=(0, 1, 2))(q, k, v)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


@pytest.mark.parametrize("poison", [np.nan, np.inf], ids=["nan", "inf"])
def test_walk_never_reads_the_sub_blocks_it_skips(monkeypatch, poison):
    """The skipped sub-blocks are not computed at all: NaN or Inf planted
    in the K and V rows of the LAST k sub-block, which queries of the
    earlier sub-blocks meet only in sub-blocks above the diagonal, does not
    reach their outputs (a kernel that computed the whole block and masked
    it would give 0 x NaN there)."""
    _walk_sizes(monkeypatch, 512, 128)
    rng = np.random.RandomState(15)
    s, last = 512, 384
    q, k, v = (rng.randn(1, 2, s, D).astype(np.float32) for _ in range(3))
    clean = np.asarray(causal_attention(*(jnp.array(t) for t in (q, k, v)), scale=SCALE))
    k[:, :, last:], v[:, :, last:] = poison, poison
    ours = np.asarray(flash_causal_attention(*(jnp.array(t) for t in (q, k, v)), scale=SCALE))
    np.testing.assert_allclose(ours[:, :, :last], clean[:, :, :last], atol=2e-5, rtol=1e-4)
    assert not np.isfinite(ours[:, :, last:]).all()  # the poison was really there


def test_walk_under_dp_mesh(monkeypatch):
    """The walked kernels per shard under a GSPMD data mesh: forward and
    gradients match the unsharded XLA path, with a padding mask."""
    import jax.sharding as jsh

    _walk_sizes(monkeypatch, 256, 128)
    mesh = _dp_mesh()
    rng = np.random.RandomState(16)
    s = 256
    q, k, v = (jnp.asarray(rng.randn(8, 2, s, D), jnp.float32) for _ in range(3))
    mask = jnp.asarray(np.arange(s)[None, :] >= np.array([s, 200] * 4)[:, None])
    sh = jsh.NamedSharding(mesh, jsh.PartitionSpec("data"))
    loss = _masked_sq_loss(flash_causal_attention, mask, shard=(mesh, "data", None))
    val, g_dp = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2)), in_shardings=(sh, sh, sh))(q, k, v)
    ref_loss = _masked_sq_loss(causal_attention, mask)
    ref, g_ref = jax.value_and_grad(ref_loss, argnums=(0, 1, 2))(q, k, v)
    np.testing.assert_allclose(float(val), float(ref), rtol=1e-5)
    for a, b in zip(g_dp, g_ref):
        assert a.sharding.spec == jsh.PartitionSpec("data")
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=5e-4, rtol=1e-3)

"""Fused head+CE kernel equivalence vs the unfused apply_head +
cross_entropy_sum + masked_accuracy path (the reference semantics,
main-single.py:95-96,128-131). Runs in Pallas interpreter mode on CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpukit.model import GPTConfig, gpt
from tpukit.ops.fused_head_ce import fused_head_ce
from tpukit.ops.layers import cross_entropy_sum, masked_accuracy

N, DIM, VOCAB = 200, 32, 300  # N not a tile multiple; vocab pads 300 -> 384


@pytest.fixture(scope="module")
def setup():
    rng = np.random.RandomState(0)
    h = jnp.asarray(rng.randn(N, DIM), jnp.float32)
    v_pad = -(-VOCAB // 128) * 128
    w = jnp.asarray(rng.randn(DIM, v_pad) * 0.1, jnp.float32)
    tgt = rng.randint(0, VOCAB, N).astype(np.int32)
    tgt[::7] = -100  # ignore rows
    return h, w, jnp.asarray(tgt)


def _unfused(h, w, tgt):
    logits = h @ w
    col = jax.lax.broadcasted_iota(jnp.int32, (w.shape[1],), 0)
    logits = jnp.where(col < VOCAB, logits, -1e9)
    loss_sum, count = cross_entropy_sum(logits, tgt)
    acc = masked_accuracy(logits, tgt)
    return logits, loss_sum, count, acc


def test_forward_matches_unfused(setup):
    h, w, tgt = setup
    logits, ref_sum, ref_count, ref_acc = _unfused(h, w, tgt)
    loss_sum, count, correct = fused_head_ce(h, w, tgt, VOCAB, with_accuracy=True)
    np.testing.assert_allclose(float(loss_sum), float(ref_sum), rtol=1e-5)
    assert float(count) == float(ref_count)
    valid = np.asarray(tgt) != -100
    ref_correct = ref_acc * valid.sum() / 100.0
    np.testing.assert_allclose(float(correct), float(ref_correct), atol=0.5)


def test_grads_match_unfused(setup):
    h, w, tgt = setup

    def fused_loss(h, w):
        s, c, _ = fused_head_ce(h, w, tgt, VOCAB)
        return s / jnp.maximum(c, 1.0)

    def unfused_loss(h, w):
        _, s, c, _ = _unfused(h, w, tgt)
        return s / jnp.maximum(c, 1.0)

    gf = jax.grad(fused_loss, argnums=(0, 1))(h, w)
    gu = jax.grad(unfused_loss, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gu[0]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gu[1]), atol=2e-6)
    # vocab-pad columns get zero gradient, exactly as the masked unfused head
    assert (np.asarray(gf[1])[:, VOCAB:] == 0).all()


def test_multi_tile_vocab_matches_unfused(monkeypatch):
    """vocab spanning several vocab tiles — the production shape (GPT-2
    vocab = ~25 tiles). Targets land in tiles >= 1, where a tile-relative/
    global index confusion in the one-hot select returns 0 instead of the
    target logit (caught by review; this test pins the fix)."""
    import tpukit.ops.fused_head_ce as m

    monkeypatch.setattr(m, "_V_BLK", 128)  # 300-vocab -> 3 tiles
    rng = np.random.RandomState(3)
    h = jnp.asarray(rng.randn(64, DIM), jnp.float32)
    v_pad = -(-VOCAB // 128) * 128
    w = jnp.asarray(rng.randn(DIM, v_pad) * 0.1, jnp.float32)
    tgt_np = rng.randint(130, VOCAB, 64).astype(np.int32)  # all in tiles >= 1
    tgt_np[::9] = -100
    tgt = jnp.asarray(tgt_np)

    logits, ref_sum, ref_count, _ = _unfused(h, w, tgt)
    loss_sum, count, correct = fused_head_ce(h, w, tgt, VOCAB, with_accuracy=True)
    np.testing.assert_allclose(float(loss_sum), float(ref_sum), rtol=1e-5)
    assert float(count) == float(ref_count)
    valid = tgt_np != -100
    ref_correct = (np.asarray(jnp.argmax(logits, -1))[valid] == tgt_np[valid]).sum()
    assert float(correct) == float(ref_correct)

    def fused_loss(h, w):
        s, c, _ = fused_head_ce(h, w, tgt, VOCAB)
        return s / jnp.maximum(c, 1.0)

    def unfused_loss(h, w):
        _, s, c, _ = _unfused(h, w, tgt)
        return s / jnp.maximum(c, 1.0)

    gf = jax.grad(fused_loss, argnums=(0, 1))(h, w)
    gu = jax.grad(unfused_loss, argnums=(0, 1))(h, w)
    np.testing.assert_allclose(np.asarray(gf[0]), np.asarray(gu[0]), atol=2e-6)
    np.testing.assert_allclose(np.asarray(gf[1]), np.asarray(gu[1]), atol=2e-6)


def test_gpt2_scale_vocab_target_logit():
    """Full-size check at a real multi-tile vocab (no monkeypatch): a
    target above _V_BLK must contribute its true logit to the loss."""
    dim, vocab = 16, 5000
    v_pad = -(-vocab // 128) * 128
    h = jnp.ones((8, dim), jnp.float32)
    w = jnp.zeros((dim, v_pad), jnp.float32).at[:, 3000].set(2.0)  # logit 32
    tgt = jnp.full((8,), 3000, jnp.int32)
    loss_sum, count, _ = fused_head_ce(h, w, tgt, vocab)
    # lse ~= log(exp(32) + 4999*exp(0)) ~= 32; loss = lse - 32 ~= 0
    assert float(loss_sum) / float(count) < 1e-3


def test_argmax_tie_break_first_index():
    h = jnp.zeros((8, DIM), jnp.float32)  # all logits equal -> argmax = 0
    w = jnp.zeros((DIM, 128), jnp.float32)
    tgt = jnp.zeros((8,), jnp.int32)
    _, _, correct = fused_head_ce(h, w, tgt, 100, with_accuracy=True)
    assert float(correct) == 8.0  # predicted index 0 == target 0 everywhere


def test_token_sharded_grads_match_unsharded(setup):
    """The sharded call: with h/targets sharded over an 8-device data axis
    (and w replicated), loss and both grads equal the unsharded result —
    the backward's dw psums local token partials."""
    import tpukit.mesh as mesh_lib
    from jax.sharding import NamedSharding, PartitionSpec as P

    if len(jax.devices()) < 8:
        pytest.skip("needs the 8-device mesh")
    h, w, tgt = setup
    n8 = (N // 8) * 8
    h8, tgt8 = h[:n8], tgt[:n8]
    mesh = mesh_lib.create_mesh({"data": 8})

    def loss(h, w, t, shard=None):
        s, c, _ = fused_head_ce(h, w, t, VOCAB, shard=shard)
        return s / jnp.maximum(c, 1.0)

    ref_l, ref_g = jax.value_and_grad(loss, argnums=(0, 1))(h8, w, tgt8)
    hs = jax.device_put(h8, NamedSharding(mesh, P("data", None)))
    ws = jax.device_put(w, NamedSharding(mesh, P(None, None)))
    ts = jax.device_put(tgt8, NamedSharding(mesh, P("data")))
    sharded = lambda h, w, t: loss(h, w, t, shard=(mesh, "data"))  # noqa: E731
    sh_l, sh_g = jax.jit(jax.value_and_grad(sharded, argnums=(0, 1)))(hs, ws, ts)
    np.testing.assert_allclose(float(sh_l), float(ref_l), rtol=1e-6)
    np.testing.assert_allclose(np.asarray(sh_g[0]), np.asarray(ref_g[0]), atol=1e-6)
    np.testing.assert_allclose(np.asarray(sh_g[1]), np.asarray(ref_g[1]), atol=1e-6)


def test_overfit_multi_tile_vocab():
    """End-to-end semantic guard: a 2-layer model must overfit one repeated
    batch at a MULTI-TILE vocab (here forced via a small _V_BLK). An
    indexing bug anywhere in the fused loss (e.g. a tile-relative target
    select) leaves the loss near log(vocab) and fails this, even when
    per-op equivalence tests are green."""
    import tpukit.ops.fused_head_ce as m
    from tpukit.shardings import SingleDevice
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    orig = m._V_BLK
    m._V_BLK = 128  # vocab 300 -> 3 tiles
    try:
        cfg = GPTConfig(
            dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=300,
            max_position_embeddings=32, compute_dtype=jnp.float32,
        )
        strategy = SingleDevice()
        assert strategy.fused_head
        optimizer = make_optimizer(3e-3)
        state = create_train_state(jax.random.PRNGKey(0), cfg, optimizer)
        shapes = jax.eval_shape(lambda: state)
        step, _, sh = make_step_fns(cfg, optimizer, strategy, shapes)
        state = jax.device_put(state, sh)

        r = np.random.RandomState(0)
        ids = jnp.asarray(r.randint(130, 300, (4, 32)).astype(np.int32))
        batch = {
            "input_ids": ids,
            "position_ids": jnp.broadcast_to(
                jnp.arange(32, dtype=jnp.int32), (4, 32)
            ),
            "mask": jnp.zeros((4, 32), bool),
        }
        tgt = jnp.asarray(r.randint(130, 300, (4, 32)).astype(np.int32))
        first = None
        for _ in range(60):
            state, loss = step(state, batch, tgt)
            if first is None:
                first = float(loss)
        # random-chance loss is log(300) ~ 5.7; memorizing one batch must
        # cut it far below that
        assert first > 5.0
        assert float(loss) < 2.0, f"loss stuck at {float(loss)} (started {first})"
    finally:
        m._V_BLK = orig


def test_strategy_loss_fused_matches_unfused_path():
    """The default strategy loss (fused) equals the same computation through
    gpt.forward + cross_entropy_loss (unfused)."""
    from tpukit.shardings import SingleDevice

    cfg = GPTConfig(
        dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=97,
        max_position_embeddings=32, compute_dtype=jnp.float32,
    )
    params = gpt.init_params(jax.random.PRNGKey(0), cfg)
    r = np.random.RandomState(1)
    ids = jnp.asarray(r.randint(0, 97, (4, 32)).astype(np.int32))
    batch = {
        "input_ids": ids,
        "position_ids": jnp.broadcast_to(jnp.arange(32, dtype=jnp.int32), (4, 32)),
        "mask": jnp.zeros((4, 32), bool),
    }
    tgt = jnp.asarray(r.randint(0, 97, (4, 32)).astype(np.int32))

    strategy = SingleDevice()
    assert strategy.fused_head
    fused_loss, fused_acc = strategy.loss_fn(params, cfg, batch, tgt, with_accuracy=True)

    from tpukit.ops.layers import cross_entropy_loss

    logits = gpt.forward(params, cfg, ids, batch["position_ids"], batch["mask"])
    ref_loss = cross_entropy_loss(logits, tgt)
    ref_acc = masked_accuracy(logits, tgt)
    np.testing.assert_allclose(float(fused_loss), float(ref_loss), rtol=1e-6)
    np.testing.assert_allclose(float(fused_acc), float(ref_acc), atol=1e-3)

"""Ring attention / context parallelism on the 8-fake-device mesh:
ring == dense attention bit-near, and the ContextParallel strategy
reproduces the single-device train step."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import shard_map
from jax.sharding import PartitionSpec as P

from tpukit.mesh import create_mesh
from tpukit.model import GPTConfig
from tpukit.ops.attention import causal_attention
from tpukit.ring_attention import (
    ring_causal_attention,
    ulysses_attention,
    zigzag_order,
)
from tpukit.shardings import ContextParallel, SingleDevice
from tpukit.train import create_train_state, make_optimizer, make_step_fns

B, H, S, D = 2, 4, 64, 8
SCALE = D**-0.5


def _ring_on_mesh(q, k, v, mask, seq_shards):
    mesh = create_mesh({"seq": seq_shards})

    def local(q, k, v, m):
        return ring_causal_attention(q, k, v, scale=SCALE, axis_name="seq", pad_mask=m)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"), P(None, None, "seq"), P(None, "seq")),
        out_specs=P(None, None, "seq"),
        check_vma=False,
    )(q, k, v, mask)


@pytest.fixture(scope="module")
def qkvm():
    rng = np.random.RandomState(0)
    mk = lambda: jnp.asarray(rng.randn(B, H, S, D).astype(np.float32))
    mask = np.zeros((B, S), dtype=bool)
    mask[0, 50:] = True
    return mk(), mk(), mk(), jnp.asarray(mask)


@pytest.mark.parametrize("seq_shards", [2, 4, 8])
def test_ring_matches_dense(qkvm, seq_shards):
    q, k, v, mask = qkvm
    ours = _ring_on_mesh(q, k, v, mask, seq_shards)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    valid = ~np.asarray(mask)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(ours)[b, :, valid[b]],
            np.asarray(ref)[b, :, valid[b]],
            atol=1e-5,
            rtol=1e-4,
        )


def test_ring_grads_match_dense(qkvm):
    q, k, v, mask = qkvm

    def loss_ring(q, k, v):
        out = _ring_on_mesh(q, k, v, mask, 4)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    def loss_dense(q, k, v):
        out = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    g_ring = jax.grad(loss_ring, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_ring, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name}",
        )


def _zigzag_on_mesh(q, k, v, mask, seq_shards):
    """Permute to the zigzag layout, run the balanced ring, unpermute."""
    order = zigzag_order(S, seq_shards)
    inv = np.argsort(order)
    mesh = create_mesh({"seq": seq_shards})

    def local(q, k, v, m):
        return ring_causal_attention(
            q, k, v, scale=SCALE, axis_name="seq", pad_mask=m, layout="zigzag"
        )

    out = shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"), P(None, None, "seq"), P(None, "seq")),
        out_specs=P(None, None, "seq"),
        check_vma=False,
    )(q[:, :, order], k[:, :, order], v[:, :, order], mask[:, order])
    return out[:, :, inv]


@pytest.mark.parametrize("seq_shards", [2, 4, 8])
def test_zigzag_matches_dense(qkvm, seq_shards):
    q, k, v, mask = qkvm
    ours = _zigzag_on_mesh(q, k, v, mask, seq_shards)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    valid = ~np.asarray(mask)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(ours)[b, :, valid[b]],
            np.asarray(ref)[b, :, valid[b]],
            atol=1e-5,
            rtol=1e-4,
        )


def test_zigzag_grads_match_dense(qkvm):
    q, k, v, mask = qkvm

    def loss_zz(q, k, v):
        out = _zigzag_on_mesh(q, k, v, mask, 4)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    def loss_dense(q, k, v):
        out = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    g_zz = jax.grad(loss_zz, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_zz, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name}",
        )


def _ulysses_on_mesh(q, k, v, mask, seq_shards):
    mesh = create_mesh({"seq": seq_shards})

    def local(q, k, v, m):
        return ulysses_attention(q, k, v, scale=SCALE, axis_name="seq", pad_mask=m)

    return shard_map(
        local,
        mesh=mesh,
        in_specs=(P(None, None, "seq"), P(None, None, "seq"), P(None, None, "seq"), P(None, "seq")),
        out_specs=P(None, None, "seq"),
        check_vma=False,
    )(q, k, v, mask)


@pytest.mark.parametrize("seq_shards", [2, 4])
def test_ulysses_matches_dense(qkvm, seq_shards):
    q, k, v, mask = qkvm
    ours = _ulysses_on_mesh(q, k, v, mask, seq_shards)
    ref = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
    valid = ~np.asarray(mask)
    for b in range(B):
        np.testing.assert_allclose(
            np.asarray(ours)[b, :, valid[b]],
            np.asarray(ref)[b, :, valid[b]],
            atol=1e-5,
            rtol=1e-4,
        )


def test_ulysses_grads_match_dense(qkvm):
    q, k, v, mask = qkvm

    def loss_uly(q, k, v):
        out = _ulysses_on_mesh(q, k, v, mask, 4)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    def loss_dense(q, k, v):
        out = causal_attention(q, k, v, scale=SCALE, pad_mask=mask)
        return jnp.sum(jnp.where(~mask[:, None, :, None], out, 0.0) ** 2)

    g_uly = jax.grad(loss_uly, argnums=(0, 1, 2))(q, k, v)
    g_dense = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for ours, ref, name in zip(g_uly, g_dense, "qkv"):
        np.testing.assert_allclose(
            np.asarray(ours), np.asarray(ref), atol=1e-4, rtol=1e-3,
            err_msg=f"d{name}",
        )


def test_ulysses_rejects_undividable_heads(qkvm):
    q, k, v, mask = qkvm  # H=4 heads, 8 shards -> 4 % 8 != 0
    with pytest.raises(ValueError, match="divisible"):
        _ulysses_on_mesh(q, k, v, mask, 8)


# ---- strategy-level parity (same scheme as tests/test_strategies.py) ------

CFG = dict(dim=32, head_dim=8, heads=4, num_layers=2, vocab_size=151)
SEQ = 32
BATCH = 8


@pytest.fixture(scope="module")
def cfg():
    return GPTConfig(max_position_embeddings=SEQ, compute_dtype=jnp.float32, **CFG)


@pytest.fixture(scope="module")
def batch(cfg):
    rng = np.random.RandomState(5)
    ids = rng.randint(3, cfg.vocab_size, size=(BATCH, SEQ)).astype(np.int32)
    mask = np.zeros((BATCH, SEQ), dtype=bool)
    mask[0, 28:] = True
    targets = np.roll(ids, -1, axis=1).astype(np.int32)
    targets[mask] = -100
    model_batch = {
        "input_ids": ids,
        "position_ids": np.ascontiguousarray(
            np.broadcast_to(np.arange(SEQ, dtype=np.int32), ids.shape)
        ),
        "mask": mask,
    }
    return model_batch, targets


def _one_step(strategy, cfg, batch, targets):
    opt = make_optimizer(1e-3)
    state = create_train_state(jax.random.PRNGKey(0), cfg, opt)
    shapes = jax.eval_shape(lambda: state)
    train_step, eval_step, _ = make_step_fns(cfg, opt, strategy, shapes)
    new_state, loss = train_step(state, batch, targets)
    eval_loss, eval_acc = eval_step(new_state, batch, targets)
    return jax.device_get(new_state.params), float(loss), float(eval_loss), float(eval_acc)


def test_cp_matches_single(cfg, batch):
    model_batch, targets = batch
    ref = _one_step(SingleDevice(), cfg, model_batch, targets)
    cp = _one_step(ContextParallel(create_mesh({"seq": 8})), cfg, model_batch, targets)
    assert abs(cp[1] - ref[1]) < 1e-5
    assert abs(cp[2] - ref[2]) < 1e-2
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4),
        cp[0],
        ref[0],
    )


def test_cp_host_permuted_matches_injit(cfg, batch):
    """ADVICE r4: the zigzag permutation applied host-side (host_batch_fn,
    what fit() does — no per-step reshard collective) must produce exactly
    the in-jit permute's loss and parameter update."""
    model_batch, targets = batch
    # fit()'s convention: the model consumes sequence_length - 1 tokens
    cfg33 = cfg.replace(max_position_embeddings=SEQ + 1)

    injit = _one_step(
        ContextParallel(create_mesh({"seq": 8})), cfg33, model_batch, targets
    )

    host_strategy = ContextParallel(create_mesh({"seq": 8}), host_permute=True)
    permute = host_strategy.host_batch_fn(cfg33)
    assert permute is not None  # 32 % (2*8) == 0 -> zigzag active
    # without the explicit opt-in, no permute fn and loss_fn permutes in-jit
    assert ContextParallel(create_mesh({"seq": 8})).host_batch_fn(cfg33) is None
    h_batch, h_targets = permute(model_batch, targets)
    hosted = _one_step(host_strategy, cfg33, h_batch, h_targets)

    assert abs(hosted[1] - injit[1]) < 1e-6
    assert abs(hosted[2] - injit[2]) < 1e-6
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=1e-6),
        hosted[0], injit[0],
    )


def test_cp_ulysses_matches_single(cfg, batch):
    model_batch, targets = batch
    ref = _one_step(SingleDevice(), cfg, model_batch, targets)
    # 4 shards: heads=4 divides, exercising the all_to_all schedule
    cp = _one_step(
        ContextParallel(create_mesh({"seq": 4}), attention="ulysses"),
        cfg, model_batch, targets,
    )
    assert abs(cp[1] - ref[1]) < 1e-5
    assert abs(cp[2] - ref[2]) < 1e-2
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4),
        cp[0],
        ref[0],
    )


def test_cp_ulysses_rejects_undividable_heads(cfg):
    strategy = ContextParallel(create_mesh({"seq": 8}), attention="ulysses")
    # sequence divides (33 - 1 = 32 over 8) so the HEADS check is what fires
    with pytest.raises(ValueError, match="heads"):
        strategy.validate_config(cfg.replace(max_position_embeddings=33))


def test_cp_data_hybrid_matches_single(cfg, batch):
    model_batch, targets = batch
    ref = _one_step(SingleDevice(), cfg, model_batch, targets)
    cp = _one_step(
        ContextParallel(create_mesh({"data": 2, "seq": 4})), cfg, model_batch, targets
    )
    assert abs(cp[1] - ref[1]) < 1e-5
    jax.tree.map(
        lambda a, b: np.testing.assert_allclose(a, b, atol=5e-5, rtol=1e-4),
        cp[0],
        ref[0],
    )


def test_cp_rejects_undividable_seq(cfg, batch):
    model_batch, targets = batch
    strategy = ContextParallel(create_mesh({"seq": 5}))
    with pytest.raises(ValueError, match="divide"):
        strategy.loss_fn(None, cfg, model_batch, targets)

"""The plain float32 reference against the program's forward, loss and
gradient at a tiny float32 size: it shares the parameter tree's layout with
tpukit and none of its code."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.reference import gpt_block as ref
from tpukit.model import GPTConfig, gpt
from tpukit.ops.layers import cross_entropy_loss

CFG = GPTConfig(dim=32, head_dim=8, heads=4, num_layers=3, vocab_size=97,
                max_position_embeddings=48, compute_dtype=jnp.float32)
SIZES = dict(heads=4, head_dim=8, vocab_size=97)


@pytest.fixture(scope="module")
def case():
    params = gpt.init_params(jax.random.PRNGKey(3), CFG)
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 97, (3, 40)), jnp.int32)
    targets = np.asarray(rng.integers(0, 97, (3, 40)), np.int32)
    targets[0, :5] = -100
    return params, ids, jnp.asarray(targets)


def program_logits(params, ids):
    pos = jnp.broadcast_to(jnp.arange(ids.shape[1], dtype=jnp.int32), ids.shape)
    return gpt.forward(params, CFG, ids, pos, jnp.zeros(ids.shape, bool))


def test_reference_imports_nothing_from_the_program():
    import ast
    import inspect

    imported = set()
    for node in ast.walk(ast.parse(inspect.getsource(ref))):
        if isinstance(node, ast.Import):
            imported |= {a.name.split(".")[0] for a in node.names}
        elif isinstance(node, ast.ImportFrom):
            imported.add((node.module or "").split(".")[0])
    assert imported <= {"__future__", "functools", "jax"}


def test_logits_agree(case):
    params, ids, _ = case
    got = ref.logits(params, ids, **SIZES)
    want = program_logits(params, ids)
    assert got.shape == want.shape == (3, 40, 128) and got.dtype == jnp.float32
    np.testing.assert_allclose(got[..., :97], want[..., :97], rtol=2e-4, atol=2e-4)
    assert (np.asarray(got[..., 97:]) == -1e9).all()


def test_walking_the_layers_equals_one_whole_gradient(case):
    """The layer-by-layer back-propagation is jax.grad of the same forward."""
    params, ids, targets = case

    def whole(p):
        x = ref.embed(p["embeddings"], ids)
        for i in range(3):
            x = ref.block(x, jax.tree_util.tree_map(lambda t: t[i], p["layers"]), 4, 8)
        return ref.cross_entropy(ref.head(x, p, 97), targets)

    want_loss, grads = jax.value_and_grad(whole)(params)
    want_norm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree_util.tree_leaves(grads)))
    loss, norm = ref.loss_and_grad_norm(params, ids, targets, **SIZES)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-6)
    assert float(norm) == pytest.approx(float(want_norm), rel=1e-5)


def test_loss_and_gradient_norm_agree(case):
    params, ids, targets = case
    loss, norm = ref.loss_and_grad_norm(params, ids, targets, **SIZES)
    want_loss, grads = jax.value_and_grad(lambda p: cross_entropy_loss(program_logits(p, ids), targets))(params)
    want_norm = jnp.sqrt(sum(jnp.sum(g**2) for g in jax.tree_util.tree_leaves(grads)))
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert float(norm) == pytest.approx(float(want_norm), rel=1e-4)


def test_reference_is_causal(case):
    params, ids, _ = case
    changed = ids.at[:, 30:].set(5)
    a, b = ref.logits(params, ids, **SIZES), ref.logits(params, changed, **SIZES)
    np.testing.assert_allclose(a[:, :30], b[:, :30], rtol=1e-6, atol=1e-6)
    assert not np.allclose(a[:, 30:], b[:, 30:])


def test_a_lower_precision_forward_is_outside_the_configurations_tolerance(case):
    """The tolerance has to bite: a forward whose matmuls run in bf16 at this
    float32 configuration is off by more than the tight float32 agreement."""
    params, ids, targets = case
    exact = float(ref.loss(params, ids, targets, **SIZES))
    low = gpt.forward(params, CFG.replace(compute_dtype=jnp.bfloat16), ids,
                      jnp.broadcast_to(jnp.arange(40, dtype=jnp.int32), ids.shape), jnp.zeros(ids.shape, bool))
    low_loss = float(cross_entropy_loss(low.astype(jnp.float32), targets))
    assert abs(low_loss - exact) / exact > 1e-5

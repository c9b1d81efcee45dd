"""Hardening tests (ADVICE r2 / VERDICT r2 #9): loud multi-host init
failures, checkpoint shape-mismatch diagnostics, stable tokenizer output
types, and the flash-kernel sequence-sharding warning."""

import numpy as np
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from tpukit import checkpoint as ckpt_lib
from tpukit import mesh as mesh_lib
from tpukit.model import GPTConfig, init_params


# ---------------------------------------------------------------------------
# initialize_runtime must not silently degrade (VERDICT r2 weak #8)
# ---------------------------------------------------------------------------


@pytest.fixture()
def fresh_runtime(monkeypatch):
    monkeypatch.setattr(mesh_lib, "_initialized", False)
    yield
    mesh_lib._initialized = True  # never re-run real init in later tests


@pytest.mark.parametrize(
    "var,value,match",
    [
        ("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234", "JAX_COORDINATOR_ADDRESS"),
        # a DETECTED multi-host world (pod slice) must fail just as loudly
        ("TPU_WORKER_HOSTNAMES", "host-0,host-1", "multi-host environment"),
    ],
    ids=["explicit_coordinator", "detected_pod"],
)
def test_initialize_runtime_raises_on_failed_rendezvous(
    monkeypatch, fresh_runtime, var, value, match
):
    monkeypatch.delenv("JAX_COORDINATOR_ADDRESS", raising=False)
    monkeypatch.setenv(var, value)
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda *a, **k: (_ for _ in ()).throw(RuntimeError("connection refused")),
    )
    with pytest.raises(RuntimeError, match=match):
        mesh_lib.initialize_runtime()


def test_initialize_runtime_rejects_half_set_identity_pair(monkeypatch, fresh_runtime):
    """ADVICE r4: only one of JAX_NUM_PROCESSES / JAX_PROCESS_ID set must
    fail with an error NAMING the missing variable — not an opaque failure
    deep inside jax.distributed.initialize."""
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setenv("JAX_NUM_PROCESSES", "2")
    monkeypatch.delenv("JAX_PROCESS_ID", raising=False)
    called = []
    monkeypatch.setattr(jax.distributed, "initialize", lambda *a, **k: called.append(1))
    with pytest.raises(RuntimeError, match="JAX_PROCESS_ID"):
        mesh_lib.initialize_runtime()
    assert not called  # rejected before touching jax.distributed


def test_initialize_runtime_tolerates_already_initialized(monkeypatch, fresh_runtime):
    monkeypatch.setenv("JAX_COORDINATOR_ADDRESS", "10.0.0.1:1234")
    monkeypatch.setattr(
        jax.distributed, "initialize",
        lambda *a, **k: (_ for _ in ()).throw(
            RuntimeError("distributed.initialize has already been called")
        ),
    )
    mesh_lib.initialize_runtime()  # must not raise
    assert mesh_lib._initialized


# ---------------------------------------------------------------------------
# Restore shape mismatches name vocab_pad_multiple (ADVICE r2 low #3)
# ---------------------------------------------------------------------------


def _params(pad_multiple):
    cfg = GPTConfig(
        dim=16, head_dim=8, heads=2, num_layers=1, vocab_size=97,
        max_position_embeddings=32, vocab_pad_multiple=pad_multiple,
    )
    return init_params(jax.random.PRNGKey(0), cfg)


def test_consolidated_restore_mismatch_names_vocab_padding(tmp_path):
    path = ckpt_lib.save(_params(128), directory=tmp_path, name="padded")
    with pytest.raises(ValueError, match="vocab_pad_multiple"):
        ckpt_lib.restore(_params(1), path)


def test_sharded_restore_mismatch_names_vocab_padding(tmp_path):
    path = ckpt_lib.save_sharded(_params(128), directory=tmp_path, name="padded")
    with pytest.raises(ValueError, match="vocab_pad_multiple"):
        ckpt_lib.restore_sharded(path, _params(1))


# ---------------------------------------------------------------------------
# Tokenizer output type is batch-size independent (ADVICE r2 low #4)
# ---------------------------------------------------------------------------


def test_tokenizer_padded_output_type_stable():
    from tpukit.data import get_tokenizer

    tok = get_tokenizer()
    small = tok(["a cat", "a dog"], padding="max_length", truncation=True, max_length=8)
    large = tok(["a cat sat"] * 80, padding="max_length", truncation=True, max_length=8)
    for enc, n in ((small, 2), (large, 80)):
        ids = np.asarray(enc["input_ids"])
        mask = np.asarray(enc["attention_mask"])
        assert isinstance(enc["input_ids"], np.ndarray)
        assert ids.dtype == np.int32 and ids.shape == (n, 8)
        assert mask.dtype == np.int32 and mask.shape == (n, 8)


# ---------------------------------------------------------------------------
# Strategies name the mesh axes their GSPMD jit shards batch and heads over,
# for the Pallas kernels' per-shard calls (no sharding is inferred: libtpu
# cannot compile custom_partitioning)
# ---------------------------------------------------------------------------


def test_strategy_kernel_shard_names_batch_and_head_axes(tiny_config):
    from tpukit.shardings import DataParallel, SingleDevice, TensorParallel

    assert SingleDevice().kernel_shard(tiny_config) is None
    dp = DataParallel(mesh_lib.create_mesh({"data": 8}))
    assert dp.kernel_shard(tiny_config) == (dp.mesh, "data", None)
    tp = TensorParallel(mesh_lib.create_mesh({"data": 2, "model": 4}))
    assert tp.kernel_shard(tiny_config) == (tp.mesh, "data", "model")
    # 4 heads do not divide over model=8: heads stay whole per device
    tp8 = TensorParallel(mesh_lib.create_mesh({"model": 8}))
    assert tp8.kernel_shard(tiny_config) == (tp8.mesh, None, None)


# ---------------------------------------------------------------------------
# Sharded-save crash/re-save semantics (code-review r3)
# ---------------------------------------------------------------------------


def test_sharded_save_clears_stale_tmp(tmp_path):
    """A crashed save leaves a .tmp dir at the (deterministic) step name;
    the retry must not publish its leftover shard files."""
    stale = tmp_path / "padded.sharded.tmp"
    stale.mkdir(parents=True)
    np.savez(stale / "shard-00099.npz", **{"0|0,0": np.ones((4, 4))})
    params = _params(128)
    path = ckpt_lib.save_sharded(params, directory=tmp_path, name="padded")
    assert not (path / "shard-00099.npz").exists()
    restored = ckpt_lib.restore_sharded(path, params)
    np.testing.assert_array_equal(
        np.asarray(restored["embeddings"]["token"]),
        np.asarray(params["embeddings"]["token"]),
    )


def test_sharded_resave_replaces_contents(tmp_path):
    """Saving again under the same name must publish the NEW data, not
    silently keep the old directory."""
    v1 = _params(128)
    v2 = jax.tree.map(lambda x: x + 1.0, v1)
    ckpt_lib.save_sharded(v1, directory=tmp_path, name="same")
    path = ckpt_lib.save_sharded(v2, directory=tmp_path, name="same")
    restored = ckpt_lib.restore_sharded(path, v1)
    np.testing.assert_array_equal(
        np.asarray(restored["embeddings"]["token"]),
        np.asarray(v2["embeddings"]["token"]),
    )
    assert not path.with_name(path.name + ".tmp").exists()
    assert not path.with_name(path.name + ".old").exists()


def test_uneven_pipeline_checkpoint_cross_strategy_restore(tmp_path):
    """Identity-padded pipeline checkpoints restore into unpadded templates
    (padding sliced off) and vice versa (zero slots appended) — the
    pipe -> single contract survives uneven layer counts."""
    from tpukit.mesh import create_mesh
    from tpukit.pipeline import Pipeline

    cfg = GPTConfig(
        dim=16, head_dim=8, heads=2, num_layers=3, vocab_size=97,
        max_position_embeddings=32,
    )
    pipe = Pipeline(create_mesh({"stage": 2}), num_microbatches=2)
    padded = pipe.prepare_params(init_params(jax.random.PRNGKey(0), cfg), cfg)
    assert jax.tree.leaves(padded["layers"])[0].shape[0] == 4

    # padded (4 slots) -> unpadded template (3 layers): padding sliced off
    spath = ckpt_lib.save_sharded(padded, directory=tmp_path, name="padded-layers")
    template = init_params(jax.random.PRNGKey(1), cfg)
    restored = ckpt_lib.restore_sharded(spath, template)
    jax.tree.map(
        lambda r, p: np.testing.assert_array_equal(np.asarray(r), np.asarray(p)[:3]),
        restored["layers"], padded["layers"],
    )

    # unpadded (3 layers) -> padded template (4 slots): zero slots appended
    cpath = ckpt_lib.save(template, directory=tmp_path, name="unpadded")
    restored2 = ckpt_lib.restore(padded, cpath)
    for leaf, src in zip(
        jax.tree.leaves(restored2["layers"]), jax.tree.leaves(template["layers"])
    ):
        np.testing.assert_array_equal(np.asarray(leaf)[:3], np.asarray(src))
        assert (np.asarray(leaf)[3:] == 0).all()

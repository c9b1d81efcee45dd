"""Round-13 elastic world resize: reshard-on-restore, tested end to end.

The recovery stack (rounds 8-9) assumed the world that comes back after a
failure is the world that left. This file tests the round-13 elastic
path:

  - world metadata (tpukit/reshard.py): every save records the saving
    world (nprocs, devices, strategy, mesh axes) in its meta sidecar;
    `describe_mismatch` names a topology change, legacy checkpoints never
    trigger a spurious reshard;
  - the streaming reshard pass: a checkpoint saved under one strategy and
    world restores BIT-identically onto another strategy's shardings at a
    different device count (shrink, grow, cross-strategy), reading only
    the blocks each target shard needs (planned from npz headers);
  - checkpoints saved by a LARGER multi-process world restore into a
    smaller one (`latest_good` resolves them, `restore_any` and the
    reshard pass read every recorded shard file regardless of the current
    process count) — satellite: today's undefined behavior is pinned;
  - `verify_checkpoint`'s world/geometry cross-check: a manifest paired
    with shard files from a different world fails with a named detail
    even when per-file checksums pass;
  - `--keep_checkpoints` retention: oldest published checkpoints pruned
    past K, quarantined timelines and the `latest_good` candidate never
    pruned;
  - the `resize@N:M` chaos spec: preempt-save at step N recording target
    world M; the relaunch must reshard to M (fit raises at any other
    world) — and fit() end to end: mesh-8 save -> mesh-4 elastic resume
    with a kind="resize" JSONL record, stale-incarnation sweep, and
    post-resume window losses matching an unresized control at the dense
    tolerance (global batch held constant across the resize).
"""

from __future__ import annotations

import json
import os
import shutil
import signal
from pathlib import Path

import jax
import numpy as np
import pytest

from tpukit import chaos as chaos_lib
from tpukit import checkpoint as ckpt_lib
from tpukit import reshard as reshard_lib
from tpukit.mesh import create_mesh
from tpukit.recovery import Preempted, RecoveryEngine
from tpukit.shardings import FSDP, DataParallel, SingleDevice
from tpukit.train import create_train_state, make_optimizer

# ---------------------------------------------------------------------------
# world metadata
# ---------------------------------------------------------------------------


def test_current_world_and_describe_mismatch():
    ddp8 = DataParallel(create_mesh({"data": 8}))
    ddp4 = DataParallel(create_mesh({"data": 4}, jax.devices()[:4]))
    w8 = reshard_lib.current_world(ddp8, global_batch=64)
    assert w8["device_count"] == 8 and w8["mesh_axes"] == {"data": 8}
    assert w8["strategy"] == "ddp" and w8["global_batch"] == 64
    w4 = reshard_lib.current_world(ddp4)
    assert reshard_lib.describe_mismatch(w4, w4) is None
    detail = reshard_lib.describe_mismatch(w8, w4)
    assert "device_count 8 -> 4" in detail and "mesh_axes" in detail
    # global_batch alone is NOT a topology change (plain restore handles it)
    assert reshard_lib.describe_mismatch({**w4, "global_batch": 16}, w4) is None
    # legacy checkpoints (no world record) never trigger a spurious reshard
    assert reshard_lib.describe_mismatch(None, w4) is None
    assert reshard_lib.describe_mismatch({}, w4) is None
    # cross-strategy is a named mismatch even at equal device counts
    fsdp4 = FSDP(create_mesh({"data": 4}, jax.devices()[:4]))
    assert "strategy" in reshard_lib.describe_mismatch(
        reshard_lib.current_world(fsdp4), w4
    )


def _tiny_state(tiny_config, seed=0):
    return create_train_state(
        jax.random.PRNGKey(seed), tiny_config, make_optimizer(1e-3)
    )


def test_saved_world_meta_and_manifest_fallback(tmp_path, tiny_config):
    state = _tiny_state(tiny_config)
    ddp = DataParallel(create_mesh({"data": 2}, jax.devices()[:2]))
    world = reshard_lib.current_world(ddp)
    path = ckpt_lib.save(state, tmp_path, meta={"world": world})
    assert reshard_lib.saved_world(path) == world
    # consolidated without meta: no world signal (and none needed)
    bare = ckpt_lib.save(state, tmp_path, name="bare")
    assert reshard_lib.saved_world(bare) is None
    # sharded without meta: the manifest's nprocs is the fallback signal
    sharded = ckpt_lib.save_sharded(state, tmp_path, name="noworld")
    assert reshard_lib.saved_world(sharded) == {"nprocs": 1}


def test_sweep_stale_world(tmp_path):
    stale = [
        "heartbeat-p00003.json", "heartbeat-p00007.json",
        "rollback-0001.json", "rollback-0001-ack-p00002.json",
        "rollback-final-drain.json", "preempt-request-p00001.json",
        "preempt-decision.json",
    ]
    for name in stale:
        (tmp_path / name).write_text("{}")
    (tmp_path / "unrelated.txt").write_text("keep me")
    removed = reshard_lib.sweep_stale_world(tmp_path)
    assert sorted(removed) == sorted(stale)
    assert (tmp_path / "unrelated.txt").exists()
    assert not list(tmp_path.glob("heartbeat-*"))
    # missing directory is inert (fresh run, no heartbeat dir yet)
    assert reshard_lib.sweep_stale_world(tmp_path / "nope") == []


def test_copy_overlap_and_overlaps_unit():
    dest = np.zeros((4, 4), np.float32)  # target block at global [2:6, 0:4]
    block = np.arange(12, dtype=np.float32).reshape(3, 4)  # at [4:7, 0:4]
    assert reshard_lib._overlaps([2, 0], [4, 4], [4, 0], [3, 4])
    n = reshard_lib._copy_overlap(dest, [2, 0], block, [4, 0])
    assert n == 8  # rows 4..5 of the global space
    np.testing.assert_array_equal(dest[2:4], block[:2])
    assert dest[:2].sum() == 0
    # disjoint: nothing copied
    assert not reshard_lib._overlaps([0, 0], [2, 4], [4, 0], [3, 4])
    assert reshard_lib._copy_overlap(dest[:2], [0, 0], block, [4, 0]) == 0
    # scalars
    d0 = np.zeros((), np.float32)
    assert reshard_lib._copy_overlap(d0, [], np.float32(7.0), []) == 1
    assert float(d0) == 7.0


# ---------------------------------------------------------------------------
# the reshard pass: shrink / grow / cross-strategy, both formats
# ---------------------------------------------------------------------------


def _assert_exact(restored, reference, sharding_tree=None):
    r = jax.tree_util.tree_leaves(restored)
    s = jax.tree_util.tree_leaves(reference)
    assert len(r) == len(s)
    for a, b in zip(r, s):
        assert tuple(a.shape) == tuple(np.asarray(b).shape)
        np.testing.assert_array_equal(
            np.asarray(jax.device_get(a)), np.asarray(jax.device_get(b))
        )
    if sharding_tree is not None:
        shardings = jax.tree_util.tree_leaves(
            sharding_tree, is_leaf=lambda x: isinstance(x, jax.sharding.Sharding)
        )
        for a, sh in zip(r, shardings):
            assert a.sharding.is_equivalent_to(sh, a.ndim), (a.sharding, sh)


@pytest.fixture(scope="module")
def fsdp8_sharded_checkpoint(tmp_path_factory, tiny_config):
    """One FSDP@8 state saved in the sharded format — the shrink/grow/
    cross-strategy tests below all reshard from it."""
    tmp = tmp_path_factory.mktemp("reshard_src")
    src = FSDP(create_mesh({"data": 8}))
    state = create_train_state(
        jax.random.PRNGKey(3), tiny_config, make_optimizer(1e-3), src
    )
    shapes = jax.eval_shape(lambda: state)
    state = jax.device_put(state, src.state_sharding(shapes))
    path = ckpt_lib.save_sharded(
        state, tmp, meta={"world": reshard_lib.current_world(src)}
    )
    return path, state, shapes


def test_reshard_sharded_shrink_cross_strategy(fsdp8_sharded_checkpoint):
    """FSDP@8 -> DDP@4: re-slice ZeRO-3 shards onto a replicated layout at
    half the world — exact values, target placement, streamed blocks."""
    path, state, shapes = fsdp8_sharded_checkpoint
    tgt = DataParallel(create_mesh({"data": 4}, jax.devices()[:4]))
    tsh = tgt.state_sharding(shapes)
    restored, info = reshard_lib.reshard_restore(path, shapes, tsh)
    _assert_exact(restored, state, tsh)
    assert info["format"] == "sharded"
    assert info["bytes_read"] > 0 and info["blocks_read"] > 0


def test_reshard_sharded_same_strategy_rechunk(fsdp8_sharded_checkpoint):
    """FSDP@8 -> FSDP@2: the ZeRO-3 chunking re-derives at the new world
    (min_shard_size + divisibility against 2, not 8) — exact values land
    in the re-derived layout."""
    path, state, shapes = fsdp8_sharded_checkpoint
    tgt = FSDP(create_mesh({"data": 2}, jax.devices()[:2]))
    tsh = tgt.state_sharding(shapes)
    restored, _ = reshard_lib.reshard_restore(path, shapes, tsh)
    _assert_exact(restored, state, tsh)


def test_reshard_consolidated_grow(tmp_path, tiny_config):
    """Consolidated DDP@2 save -> FSDP@8 restore (grow + cross-strategy):
    the world-agnostic msgpack lands sharded at the larger world."""
    src = DataParallel(create_mesh({"data": 2}, jax.devices()[:2]))
    state = create_train_state(
        jax.random.PRNGKey(5), tiny_config, make_optimizer(1e-3), src
    )
    shapes = jax.eval_shape(lambda: state)
    path = ckpt_lib.save(
        state, tmp_path, meta={"world": reshard_lib.current_world(src)}
    )
    tgt = FSDP(create_mesh({"data": 8}))
    tsh = tgt.state_sharding(shapes)
    restored, info = reshard_lib.reshard_restore(path, shapes, tsh)
    _assert_exact(restored, state, tsh)
    assert info["format"] == "consolidated" and info["bytes_read"] > 0


def _split_into_two_proc_checkpoint(src_dir: Path, dest: Path) -> None:
    """Rewrite a 1-process sharded checkpoint as the 2-process layout a
    larger world would have written: the single shard's blocks split
    across shard-00000/shard-00001 by leaf parity, manifest nprocs=2 with
    re-derived checksums. This is the on-disk shape multi-host saves
    produce — which this container cannot run natively (see the PR-2
    multiprocess note)."""
    import hashlib

    manifest = json.loads((src_dir / "manifest.json").read_text())
    blocks = dict(np.load(src_dir / "shard-00000.npz"))
    halves: list[dict] = [{}, {}]
    for key, arr in blocks.items():
        leaf = int(key.partition("|")[0])
        halves[leaf % 2][key] = arr
    dest.mkdir()
    manifest["nprocs"] = 2
    checksums = {}
    for pid, half in enumerate(halves):
        shard = dest / f"shard-{pid:05d}.npz"
        with open(shard, "wb") as f:
            np.savez(f, **half)
        checksums[shard.name] = hashlib.sha256(shard.read_bytes()).hexdigest()
    manifest["checksums"] = checksums
    (dest / "manifest.json").write_text(json.dumps(manifest))
    meta = src_dir / "resume.json"
    if meta.exists():
        rec = json.loads(meta.read_text())
        rec.setdefault("world", {})["nprocs"] = 2
        (dest / "resume.json").write_text(json.dumps(rec))


def test_restore_from_larger_world_nprocs(tmp_path, tiny_config):
    """Satellite: the newest checkpoint was saved by a LARGER world (more
    processes) than the current one. `latest_good` must resolve it (its
    integrity check reads the manifest's world, not the current one),
    `restore_any` must read every recorded shard file, and the reshard
    pass must land it exactly on the smaller world's shardings."""
    src = FSDP(create_mesh({"data": 8}))
    state = create_train_state(
        jax.random.PRNGKey(7), tiny_config, make_optimizer(1e-3), src
    )
    shapes = jax.eval_shape(lambda: state)
    state = jax.device_put(
        state.replace(step=state.step * 0 + 12), src.state_sharding(shapes)
    )
    one_proc = ckpt_lib.save_sharded(state, tmp_path, name="tmp-oneproc")
    big = tmp_path / "checkpoint-step000000012.sharded"
    _split_into_two_proc_checkpoint(one_proc, big)
    shutil.rmtree(one_proc)
    assert json.loads((big / "manifest.json").read_text())["nprocs"] == 2
    assert ckpt_lib.verify_checkpoint(big) == (True, "verified")
    assert ckpt_lib.latest_good(tmp_path) == big
    assert reshard_lib.saved_world(big)["nprocs"] == 2

    tgt = DataParallel(create_mesh({"data": 4}, jax.devices()[:4]))
    tsh = tgt.state_sharding(shapes)
    restored, info = reshard_lib.reshard_restore(big, shapes, tsh)
    _assert_exact(restored, state, tsh)
    assert info["blocks_read"] > 0
    # restore_any (the pre-elastic reader) also reads every recorded shard
    via_any, was_sharded = ckpt_lib.restore_any(big, shapes, tsh)
    assert was_sharded
    _assert_exact(via_any, state)


def test_reshard_missing_block_fails_named(tmp_path, tiny_config):
    """A shard file whose blocks vanish must fail the assembly coverage
    check with a named leaf, not restore zeros silently."""
    state = _tiny_state(tiny_config, seed=9)
    path = ckpt_lib.save_sharded(state, tmp_path)
    blocks = dict(np.load(path / "shard-00000.npz"))
    dropped = next(iter(blocks))
    del blocks[dropped]
    with open(path / "shard-00000.npz", "wb") as f:
        np.savez(f, **blocks)
    shapes = jax.eval_shape(lambda: state)
    sd = SingleDevice()
    with pytest.raises(ValueError, match="assembled"):
        reshard_lib.reshard_restore(path, shapes, sd.state_sharding(shapes))


# ---------------------------------------------------------------------------
# verify_checkpoint: world/geometry cross-check (satellite)
# ---------------------------------------------------------------------------


def test_verify_geometry_catches_foreign_world_manifest(tmp_path, tiny_config):
    """A manifest paired with shard files from a DIFFERENT world must fail
    verification with a named detail even when nothing is bit-corrupt:
    the per-file checksums prove each shard is intact, the geometry check
    proves the set belongs to THIS manifest's world."""
    state = _tiny_state(tiny_config)
    path = ckpt_lib.save_sharded(state, tmp_path)
    manifest = json.loads((path / "manifest.json").read_text())
    # shrink one leaf's recorded global shape: the shards now describe a
    # bigger world than the manifest claims
    victim = next(
        i for i, l in enumerate(manifest["leaves"]) if len(l["shape"]) >= 1
        and l["shape"][0] > 1
    )
    manifest["leaves"][victim]["shape"][0] -= 1
    (path / "manifest.json").write_text(json.dumps(manifest))
    ok, detail = ckpt_lib.verify_checkpoint(path)
    assert not ok and "different world" in detail
    assert manifest["paths"][victim] in detail

    # legacy manifests (no checksums) get the same geometry protection
    del manifest["checksums"]
    (path / "manifest.json").write_text(json.dumps(manifest))
    ok, detail = ckpt_lib.verify_checkpoint(path)
    assert not ok and "different world" in detail


def test_verify_geometry_catches_missing_elements(tmp_path, tiny_config):
    """Coverage: a manifest claiming more processes than contributed
    blocks (a stale shard swap) fails with the per-leaf element count."""
    state = _tiny_state(tiny_config, seed=2)
    path = ckpt_lib.save_sharded(state, tmp_path)
    import hashlib

    # drop one block from the shard, refresh its checksum so only the
    # geometry check can notice
    blocks = dict(np.load(path / "shard-00000.npz"))
    del blocks[next(iter(blocks))]
    shard = path / "shard-00000.npz"
    with open(shard, "wb") as f:
        np.savez(f, **blocks)
    manifest = json.loads((path / "manifest.json").read_text())
    manifest["checksums"][shard.name] = hashlib.sha256(
        shard.read_bytes()
    ).hexdigest()
    (path / "manifest.json").write_text(json.dumps(manifest))
    ok, detail = ckpt_lib.verify_checkpoint(path)
    assert not ok and "elements" in detail and "different world" in detail


def test_verify_geometry_accepts_honest_checkpoints(tmp_path, tiny_config):
    state = _tiny_state(tiny_config, seed=4)
    path = ckpt_lib.save_sharded(state, tmp_path)
    assert ckpt_lib.verify_checkpoint(path) == (True, "verified")


def test_duplicate_blocks_rejected_by_verify_and_reshard(tmp_path, tiny_config):
    """A duplicate (leaf, starts) block across shard files could mask a
    missing block EXACTLY under element-count coverage (two same-size
    blocks: one duplicated, one absent) and would silently restore
    uninitialized memory — both the geometry check and the reshard pass
    must reject it by identity, not by count."""
    import hashlib

    state = _tiny_state(tiny_config, seed=6)
    path = ckpt_lib.save_sharded(state, tmp_path)
    manifest = json.loads((path / "manifest.json").read_text())
    blocks = dict(np.load(path / "shard-00000.npz"))
    keys = sorted(blocks)
    dup, drop = next(
        (a, b) for a in keys for b in keys
        if a != b and blocks[a].shape == blocks[b].shape
    )
    halves = [
        {k: v for k, v in blocks.items() if k != drop},  # `drop` missing
        {dup: blocks[dup]},  # ... masked by a same-size duplicate of `dup`
    ]
    manifest["nprocs"] = 2
    manifest["checksums"] = {}
    for pid, half in enumerate(halves):
        shard = path / f"shard-{pid:05d}.npz"
        with open(shard, "wb") as f:
            np.savez(f, **half)
        manifest["checksums"][shard.name] = hashlib.sha256(
            shard.read_bytes()
        ).hexdigest()
    (path / "manifest.json").write_text(json.dumps(manifest))
    ok, detail = ckpt_lib.verify_checkpoint(path)
    assert not ok and "duplicate block" in detail
    shapes = jax.eval_shape(lambda: state)
    sd = SingleDevice()
    with pytest.raises(ValueError, match="duplicate block"):
        reshard_lib.reshard_restore(path, shapes, sd.state_sharding(shapes))


# ---------------------------------------------------------------------------
# --keep_checkpoints retention (satellite)
# ---------------------------------------------------------------------------


def _fake_state(step: int):
    from flax import struct

    @struct.dataclass
    class S:
        step: int
        w: np.ndarray

    return S(step=step, w=np.arange(8, dtype=np.float32) + step)


def test_prune_checkpoints_keeps_newest_k(tmp_path):
    for step in (2, 4, 6, 8, 10):
        ckpt_lib.save(_fake_state(step), tmp_path, meta={"step": step})
    removed = ckpt_lib.prune_checkpoints(tmp_path, keep=2)
    assert sorted(removed) == [
        "checkpoint-step000000002.msgpack",
        "checkpoint-step000000004.msgpack",
        "checkpoint-step000000006.msgpack",
    ]
    steps = [ckpt_lib._step_of(p) for p in ckpt_lib.all_checkpoints(tmp_path)]
    assert steps == [8, 10]
    # sidecars went with their blobs
    assert not list(tmp_path.glob("checkpoint-step000000002.*"))
    # idempotent
    assert ckpt_lib.prune_checkpoints(tmp_path, keep=2) == []
    with pytest.raises(ValueError):
        ckpt_lib.prune_checkpoints(tmp_path, keep=0)


def test_prune_never_touches_quarantined_timelines(tmp_path):
    """The quarantine interaction: checkpoints renamed aside by a rollback
    are forensic evidence — retention must never delete them, and they
    must not count against the keep budget."""
    for step in (2, 4, 6, 8, 10):
        ckpt_lib.save(_fake_state(step), tmp_path)
    eng = RecoveryEngine(tmp_path, max_rollbacks=3)
    plan = eng.plan("nan", anomaly_step=11, window=4)  # target step 6
    quarantined = eng.quarantine(plan)  # steps 8, 10 renamed aside
    assert len(quarantined) == 2
    removed = ckpt_lib.prune_checkpoints(tmp_path, keep=1)
    # published world is now {2, 4, 6}: keep 6, drop 2 and 4
    assert sorted(removed) == [
        "checkpoint-step000000002.msgpack",
        "checkpoint-step000000004.msgpack",
    ]
    assert [ckpt_lib._step_of(p) for p in ckpt_lib.all_checkpoints(tmp_path)] == [6]
    # both quarantined checkpoints still on disk, untouched
    assert len(list(tmp_path.glob("*.quarantined-0001"))) >= 2


def test_prune_protects_latest_good_when_kept_are_corrupt(tmp_path):
    for step in (2, 4, 6, 8):
        ckpt_lib.save(_fake_state(step), tmp_path)
    # corrupt the two NEWEST (the keep window at keep=2): latest_good now
    # resolves to step 4, which must survive the prune
    for step in (6, 8):
        bad = tmp_path / f"checkpoint-step{step:09d}.msgpack"
        bad.write_bytes(b"bitrot" + bad.read_bytes()[6:])
    removed = ckpt_lib.prune_checkpoints(tmp_path, keep=2)
    assert removed == ["checkpoint-step000000002.msgpack"]
    with pytest.warns(UserWarning):
        assert ckpt_lib._step_of(ckpt_lib.latest_good(tmp_path)) == 4


# ---------------------------------------------------------------------------
# chaos resize@N:M grammar + engine
# ---------------------------------------------------------------------------


def test_chaos_resize_spec_parses_and_validates():
    entries = chaos_lib.parse_spec("resize@6:4")
    assert entries == [{"kind": "resize", "at": 6, "param": 4.0}]
    for bad in ("resize@6", "resize@6:0", "resize@6:2.5"):
        with pytest.raises(chaos_lib.ChaosSpecError, match="resize"):
            chaos_lib.parse_spec(bad)


def test_chaos_resize_fires_sigterm_and_records_target():
    import jax.numpy as jnp

    eng = chaos_lib.ChaosEngine("resize@5:4")
    assert eng.resize_target is None  # set when the fault FIRES
    caught = []
    prev = signal.signal(signal.SIGTERM, lambda s, f: caught.append(s))
    try:
        state = {"w": jnp.zeros(3)}
        _, _, fired = eng.on_step(4, state, jnp.float32(1.0))
        assert not fired and not caught
        s, _, fired = eng.on_step(5, state, jnp.float32(1.0))
        assert s is state  # resize never mutates state in-process
        assert fired[0]["fault"] == "resize" and fired[0]["to"] == 4
        assert caught == [signal.SIGTERM]
        assert eng.resize_target == 4
        # fire-once, like every step-indexed fault
        _, _, fired = eng.on_step(5, state, jnp.float32(1.0))
        assert not fired and len(caught) == 1
    finally:
        signal.signal(signal.SIGTERM, prev)


# ---------------------------------------------------------------------------
# heartbeat: stale larger-world beats never poison divergence
# ---------------------------------------------------------------------------


def test_divergence_ignores_beats_beyond_world(tmp_path):
    from tpukit.obs.heartbeat import Heartbeat

    h0 = Heartbeat(tmp_path, process_index=0, process_count=2)
    h1 = Heartbeat(tmp_path, process_index=1, process_count=2)
    h0.beat(8, checksum="aaaa", checksum_step=8)
    h1.beat(8, checksum="aaaa", checksum_step=8)
    # a stale beat from rank 7 of a previous 8-process incarnation, at the
    # same step with a different checksum — landed after the resize sweep
    (tmp_path / "heartbeat-p00007.json").write_text(
        json.dumps({"process": 7, "step": 8, "time": 0.0,
                    "checksum": "ffff", "checksum_step": 8})
    )
    assert h0.check_divergence() == []
    # the guard is scoped to real multi-process worlds: a single-process
    # reader keeps comparing every beat (the established fake-peer test
    # harness pattern, tests/test_flightrec.py divergence_run)
    solo = Heartbeat(tmp_path, process_index=0, process_count=1)
    assert solo.check_divergence() != []


# ---------------------------------------------------------------------------
# fit() end to end: resize@N:M -> preempt-save -> elastic resume
# ---------------------------------------------------------------------------

TINY = dict(
    epochs=1, sequence_length=33, dim=32, head_dim=8, heads=4, num_layers=2,
    learning_rate=1e-3, dataset_slice="200", num_workers=0, disable_amp=True,
    seed=0,
)
# 200 rows at global batch 8 = 25 steps; resize@6:4 preempt-saves at step 6.


def _run_fit(tmp, log_name, strategy_fn, **overrides):
    from tpukit.flags import TrainFlags
    from tpukit.train import fit

    flags = TrainFlags(**{**TINY, "metrics_log": str(tmp / log_name), **overrides})
    cwd = os.getcwd()
    os.chdir(tmp)
    try:
        result = fit(flags, strategy_fn())
    finally:
        os.chdir(cwd)
    records = [
        json.loads(line) for line in (tmp / log_name).read_text().splitlines()
    ]
    return result, records


@pytest.fixture(scope="module")
def elastic_resume_run(tmp_path_factory):
    """The acceptance scenario: resize@6:4 preempt-saves a mesh-8 DDP run
    at step 6 (exit 75 semantics); the relaunch at mesh-4 (same GLOBAL
    batch: batch_size doubles as shards halve) reshards and completes; an
    unresized mesh-8 control resumes the same checkpoint for parity."""
    tmp = tmp_path_factory.mktemp("elastic_fit")
    hb = tmp / "hb"
    hb.mkdir()
    (hb / "heartbeat-p00007.json").write_text(
        '{"process": 7, "step": 99, "time": 0}'
    )
    (hb / "rollback-0001.json").write_text('{"seq": 1}')
    with pytest.raises(Preempted):
        _run_fit(
            tmp, "run1.jsonl",
            lambda: DataParallel(create_mesh({"data": 8})),
            batch_size=1, chaos_spec="resize@6:4",
        )
    shutil.copytree(tmp / "checkpoints", tmp / "ck_saved")
    resized, rz_records = _run_fit(
        tmp, "run2.jsonl",
        lambda: DataParallel(create_mesh({"data": 4}, jax.devices()[:4])),
        batch_size=2, resume="latest", heartbeat_dir=str(hb),
    )
    control = tmp_path_factory.mktemp("elastic_fit_control")
    shutil.copytree(tmp / "ck_saved", control / "checkpoints")
    _, ctrl_records = _run_fit(
        control, "run.jsonl",
        lambda: DataParallel(create_mesh({"data": 8})),
        batch_size=1, resume="latest",
    )
    return tmp, resized, rz_records, ctrl_records


def test_elastic_resume_reshards_and_completes(elastic_resume_run):
    tmp, resized, records, _ = elastic_resume_run
    meta = ckpt_lib.read_meta(
        tmp / "ck_saved" / "checkpoint-step000000006.msgpack"
    )
    assert meta["preempted"] and meta["resize_to"] == 4
    assert meta["world"]["mesh_axes"] == {"data": 8}
    assert meta["world"]["global_batch"] == 8
    rz = [r for r in records if r["kind"] == "resize"]
    assert len(rz) == 1
    assert "device_count 8 -> 4" in rz[0]["mismatch"]
    assert rz[0]["world"]["mesh_axes"] == {"data": 4}
    assert rz[0]["bytes_read"] > 0
    assert sorted(rz[0]["swept"]) == [
        "heartbeat-p00007.json", "rollback-0001.json",
    ]
    assert not (tmp / "hb" / "heartbeat-p00007.json").exists()
    # the run COMPLETED at the resized world: full epoch, validation, the
    # same final step the unresized run would reach
    assert int(jax.device_get(resized.state.step)) == 25
    assert any(r["kind"] == "validation" for r in records)


def test_elastic_resume_loss_parity_with_unresized_control(elastic_resume_run):
    """Topology-change parity: post-resume window losses at mesh-4 track
    the unresized mesh-8 control within the dense tolerance (the global
    batch is held constant, so reduction order across the smaller mesh is
    the only difference)."""
    _, _, records, ctrl_records = elastic_resume_run
    resized = [r["loss"] for r in records if r["kind"] == "train"]
    control = [r["loss"] for r in ctrl_records if r["kind"] == "train"]
    assert resized and len(resized) == len(control)
    np.testing.assert_allclose(resized, control, rtol=0, atol=5e-4)


def test_wrong_world_relaunch_raises(elastic_resume_run, tmp_path):
    """The resize@N:M contract: coming back at any world other than M is
    the test harness NOT testing what it claims — fail loud."""
    src_tmp, _, _, _ = elastic_resume_run
    shutil.copytree(src_tmp / "ck_saved", tmp_path / "checkpoints")
    with pytest.raises(RuntimeError, match="expecting relaunch at 4"):
        _run_fit(
            tmp_path, "bad.jsonl",
            lambda: DataParallel(create_mesh({"data": 2}, jax.devices()[:2])),
            batch_size=4, resume="latest",
        )


def test_fit_rejects_negative_keep_checkpoints():
    from tpukit.flags import TrainFlags
    from tpukit.train import fit

    with pytest.raises(ValueError, match="keep_checkpoints"):
        fit(
            TrainFlags(**TINY, batch_size=8, keep_checkpoints=-1),
            SingleDevice(),
        )


def test_keep_checkpoints_retention_in_fit(tmp_path):
    """--keep_checkpoints 2 on a 25-step run with checkpoint_every=4:
    periodic saves at 4..24 plus the final save at 25 — only the newest
    two survive, and the JSONL carries the prune audit."""
    _, records = _run_fit(
        tmp_path, "run.jsonl", SingleDevice,
        batch_size=8, checkpoint_every=4, keep_checkpoints=2,
    )
    steps = [
        ckpt_lib._step_of(p)
        for p in ckpt_lib.all_checkpoints(tmp_path / "checkpoints")
    ]
    assert steps == [24, 25]
    prunes = [r for r in records if r["kind"] == "ckpt_prune"]
    assert prunes and prunes[0]["keep"] == 2
    assert sum(len(r["pruned"]) for r in prunes) == 5  # steps 4..20

"""Elastic world resize: reshard a checkpoint saved at world N onto M devices.

The recovery stack (rollback, collective preemption, integrity-verified
checkpoints, the 0/75/76/77 exit-code contract) assumed the world that
comes back after a failure is the world that left. Production fleets
shrink and grow — spot/preemptible capacity is the cheapest route to
scale — so round 13 makes `--resume` elastic:

  - every save's `meta` sidecar records the SAVING world
    (`current_world`: nprocs, device count, strategy name, mesh axes,
    global batch), so a relaunch can detect a topology change instead of
    failing on a shard-count mismatch or silently misloading;
  - `reshard_restore` reads a checkpoint of either format and lands it on
    the CURRENT run's `state_sharding` specs. The sharded path streams
    leaf-block by leaf-block: for each leaf it plans, from the shard
    files' npy HEADERS alone, which saved blocks intersect each target
    device shard, reads only those, and assembles per-device host buffers
    — no host ever materializes the full global state (at most one
    leaf's addressable target blocks at a time). The checkpoint format
    already separates global shape from per-leaf placement (the
    SimpleFSDP-style portability property), so DDP<->FSDP<->EP and
    N<->M device-count changes are all the same operation: re-slice the
    recorded global leaves along the new world's PartitionSpecs. FSDP's
    `min_shard_size` threshold and divisibility rules re-derive at the
    new world automatically — the target specs come from the CURRENT
    strategy, never from the checkpoint;
  - `sweep_stale_world` clears the previous incarnation's coordination
    state (heartbeat beat files, rollback decision/ack files, preemption
    request/decision files) when a resize is detected: step numbers,
    checksums and process indices from the old world must never be
    compared against the new one's (a stale beat from process 7 of an
    8-process world would poison the 4-process world's divergence check
    forever — its file is never overwritten by a process that no longer
    exists).

Resharding moves data, never math: the restored state is bit-identical to
the saved one, leaf for leaf. Loss-trajectory parity after a resize is
therefore the parity of the COMPUTATION at the new world — reduction
order across a different mesh — which the multichip dryrun's resize
family and tests/test_reshard.py pin at the dense tolerance (hold the
global batch constant across the resize: per-shard batch x shards, not
per-shard batch, is what the trajectory depends on).
"""

from __future__ import annotations

import json
import time
from pathlib import Path

import numpy as np

from tpukit import checkpoint as ckpt_lib

# ---------------------------------------------------------------------------
# World metadata: what a save records, what a resume compares.
# ---------------------------------------------------------------------------

# Keys that participate in the mismatch decision. `global_batch` is
# deliberately NOT compared: a batch-size change alone reshapes the input
# stream, not the state layout — the plain restore path handles it (with a
# mid-epoch-position warning in fit()).
_COMPARED = ("nprocs", "device_count", "strategy", "mesh_axes")


def current_world(strategy, global_batch: int | None = None) -> dict:
    """The world descriptor a save's meta sidecar records: process count,
    device count, strategy name and mesh axes — everything a relaunch
    needs to decide "same world, plain restore" vs "resized, reshard"."""
    import jax

    mesh = strategy.mesh
    world = {
        "nprocs": int(jax.process_count()),
        "device_count": int(mesh.devices.size),
        "strategy": str(strategy.name),
        "mesh_axes": {
            ax: int(s) for ax, s in zip(mesh.axis_names, mesh.devices.shape)
        },
    }
    if global_batch is not None:
        world["global_batch"] = int(global_batch)
    return world


def saved_world(path) -> dict | None:
    """The world a checkpoint was saved by: the meta sidecar's `world`
    record (round 13+), falling back to the sharded manifest's `nprocs`
    for older sharded checkpoints. None for consolidated checkpoints
    without metadata — those carry no world signal at all (and need none:
    the consolidated format is world-agnostic by construction)."""
    meta = ckpt_lib.read_meta(path)
    if meta and isinstance(meta.get("world"), dict):
        return meta["world"]
    path = Path(path)
    if path.is_dir():
        try:
            manifest = json.loads((path / "manifest.json").read_text())
            return {"nprocs": int(manifest["nprocs"])}
        except (OSError, ValueError, KeyError):
            return None
    return None


def describe_mismatch(saved: dict | None, current: dict) -> str | None:
    """Named detail of how the saving world differs from the current one,
    or None when they match (or when the saved record predates world
    metadata on every compared key — legacy checkpoints never trigger a
    spurious reshard)."""
    if not saved:
        return None
    diffs = []
    for key in _COMPARED:
        if key not in saved:
            continue
        if saved[key] != current.get(key):
            diffs.append(f"{key} {saved[key]} -> {current.get(key)}")
    return "; ".join(diffs) or None


# ---------------------------------------------------------------------------
# Stale-incarnation sweep.
# ---------------------------------------------------------------------------

# Everything the old world published into the shared heartbeat directory.
# The coordinators' own construction sweeps (RollbackCoordinator /
# PreemptCoordinator) only run on multi-process worlds and only clear what
# the NEW world's ranks own — a resize that shrinks the world leaves the
# vanished ranks' files forever, so the resize path sweeps the whole
# namespace once, before any new-world reader is constructed.
_STALE_PATTERNS = (
    "heartbeat-p*.json",
    "rollback-*.json",
    "preempt-request-p*.json",
    "preempt-decision.json",
)


def sweep_stale_world(directory) -> list[str]:
    """Remove the previous incarnation's heartbeat/rollback/preemption
    state from the shared coordination directory. Called (process 0) when
    `--resume` detects a topology change, BEFORE the new world's
    Heartbeat/coordinators are constructed: a stale beat file from a rank
    that no longer exists would otherwise feed the straggler check and the
    divergence comparison with another world's steps and checksums
    forever. Returns the removed names."""
    directory = Path(directory)
    removed = []
    if not directory.is_dir():
        return removed
    for pattern in _STALE_PATTERNS:
        for path in sorted(directory.glob(pattern)):
            try:
                path.unlink()
            except OSError:
                continue  # racing another sweep: a miss costs nothing
            removed.append(path.name)
    return removed


# ---------------------------------------------------------------------------
# The reshard pass.
# ---------------------------------------------------------------------------


def _copy_overlap(dest, dest_start, block, block_start) -> int:
    """Copy the overlap of `block` (sitting at global offset `block_start`)
    into `dest` (a local buffer whose [0...] corner sits at global offset
    `dest_start`). Returns the number of elements copied (0 = disjoint)."""
    if dest.ndim == 0:
        dest[()] = block
        return 1
    src_idx, dst_idx, n = [], [], 1
    for d0, ds, b0, bs in zip(dest_start, dest.shape, block_start, block.shape):
        lo = max(d0, b0)
        hi = min(d0 + ds, b0 + bs)
        if hi <= lo:
            return 0
        src_idx.append(slice(lo - b0, hi - b0))
        dst_idx.append(slice(lo - d0, hi - d0))
        n *= hi - lo
    dest[tuple(dst_idx)] = block[tuple(src_idx)]
    return n


def _overlaps(dest_start, dest_shape, block_start, block_shape) -> bool:
    """Header-only intersection test — decides whether a saved block must
    be READ at all for a given target shard."""
    for d0, ds, b0, bs in zip(dest_start, dest_shape, block_start, block_shape):
        if min(d0 + ds, b0 + bs) <= max(d0, b0):
            return False
    return True


def _index_bounds(idx, shape):
    """Normalize a sharding index (tuple of slices, possibly with None
    bounds for unsharded dims) into (starts, sizes)."""
    starts, sizes = [], []
    for sl, dim in zip(idx, shape):
        start = 0 if sl.start is None else int(sl.start)
        stop = int(dim) if sl.stop is None else int(sl.stop)
        starts.append(start)
        sizes.append(stop - start)
    return starts, sizes


def _place_full(full, want, sharding, path_str):
    """Fallback placement for a leaf that needed whole-leaf materialization
    (identity-padded layer-axis adaptation, or no target sharding)."""
    import jax

    from tpukit.mesh import place_host_array

    shape = tuple(full.shape)
    if want != shape:
        adapted = ckpt_lib._adapt_layer_axis(path_str, full, want)
        if adapted is None:
            raise ValueError(
                f"reshard: leaf {path_str} was saved with shape {shape} but "
                f"the target expects {want}. {ckpt_lib._VOCAB_PAD_HINT}"
            )
        full = adapted
    if sharding is None:
        return jax.numpy.asarray(full)
    return place_host_array(full, sharding)


def _reshard_sharded(base: Path, template, sharding_tree, info: dict):
    """Stream a sharded checkpoint onto the target shardings, leaf-block by
    leaf-block. For each leaf, the target sharding's addressable device
    indices are computed, the saved blocks that intersect each target
    shard are identified from npz HEADERS (no data read), and only the
    intersecting blocks are read and copied into per-device host buffers
    — so host memory is bounded by one leaf's addressable target blocks,
    never the global state (the round-9 lazy-reader discipline, extended
    from per-leaf to per-target-shard)."""
    import jax

    manifest, shard_files = ckpt_lib._read_shard_manifest(base)
    flat, treedef = jax.tree_util.tree_flatten(template)
    shardings = ckpt_lib._sharding_leaves(flat, sharding_tree)
    if len(flat) != len(manifest["leaves"]):
        raise ValueError(
            f"template has {len(flat)} leaves, checkpoint has "
            f"{len(manifest['leaves'])} ({base})"
        )
    readers = [ckpt_lib._ShardReader(f) for f in shard_files]
    # One header pass over every shard builds the global block index:
    # leaf -> [(reader, key, starts, shape)]. Duplicate (leaf, starts)
    # keys across shard files are rejected here — a duplicate would be
    # copied twice and its element count could mask a missing block
    # exactly (the same rule verify_checkpoint's geometry check enforces).
    by_leaf: dict[int, list] = {}
    seen_blocks: set[tuple[int, tuple[int, ...]]] = set()
    for reader in readers:
        for key, (bshape, _) in reader.block_headers().items():
            i, starts = ckpt_lib._parse_block_key(key)
            block_id = (i, tuple(starts))
            if block_id in seen_blocks:
                raise ValueError(
                    f"checkpoint {base}: duplicate block {key!r} across "
                    f"shard files — shards from a different world mixed in?"
                )
            seen_blocks.add(block_id)
            by_leaf.setdefault(i, []).append(
                (reader, key, starts, tuple(bshape))
            )

    # Per-LEAF block cache: a saved block can intersect several distinct
    # target shards (every shard, on a grow or a reshard onto a replicated
    # layout), and re-reading it from the zip once per buffer would
    # multiply restore I/O by the target shard count. The cache lives for
    # one leaf's assembly and is dropped with it, so the host-memory bound
    # stays one leaf — and each byte is read exactly once.
    block_cache: dict[tuple, np.ndarray] = {}

    def read_block(reader, key):
        cached = block_cache.get((id(reader), key))
        if cached is not None:
            return cached
        block = reader.read(key)
        block_cache[(id(reader), key)] = block
        info["bytes_read"] += int(block.nbytes)
        info["blocks_read"] += 1
        return block

    restored = []
    for i, (leaf, lmeta, sharding) in enumerate(
        zip(flat, manifest["leaves"], shardings)
    ):
        block_cache.clear()  # the cache bounds host memory per LEAF
        shape, dtype = tuple(lmeta["shape"]), np.dtype(lmeta["dtype"])
        want = tuple(getattr(leaf, "shape", shape))
        blocks = by_leaf.get(i, [])
        if want != shape or sharding is None:
            # layer-axis adaptation (identity-padded pipeline stacks) or an
            # untargeted leaf: assemble the whole leaf, then adapt + place —
            # the one case where per-shard streaming cannot apply, because
            # the adaptation is a function of the full layer axis.
            full = np.empty(shape, dtype)
            covered = 0
            for reader, key, starts, bshape in blocks:
                block = read_block(reader, key)
                covered += _copy_overlap(
                    full, [0] * full.ndim, block, starts or []
                )
            _check_covered(covered, shape, base, i, manifest)
            restored.append(
                _place_full(full, want, sharding, manifest["paths"][i])
            )
            continue
        # streaming path: one host buffer per DISTINCT target index (all
        # replicas of a shard share the buffer; device_put copies per
        # device), filled from exactly the saved blocks that intersect it.
        idx_map = sharding.addressable_devices_indices_map(shape)
        buffers: dict[tuple, np.ndarray] = {}
        arrays = []
        for device, idx in idx_map.items():
            starts_d, sizes_d = _index_bounds(idx or (), shape)
            bkey = tuple(zip(starts_d, sizes_d))
            buf = buffers.get(bkey)
            if buf is None:
                buf = np.empty(sizes_d, dtype)
                covered = 0
                for reader, key, bstarts, bshape in blocks:
                    if buf.ndim and not _overlaps(
                        starts_d, sizes_d, bstarts, bshape
                    ):
                        continue
                    block = read_block(reader, key)
                    covered += _copy_overlap(buf, starts_d, block, bstarts)
                _check_covered(covered, tuple(sizes_d), base, i, manifest)
                buffers[bkey] = buf
            arrays.append(jax.device_put(buf, device))
        restored.append(
            jax.make_array_from_single_device_arrays(shape, sharding, arrays)
        )
    for reader in readers:
        reader.close()
    return jax.tree_util.tree_unflatten(treedef, restored)


def _check_covered(covered: int, shape: tuple, base, i: int, manifest) -> None:
    expected = 1
    for d in shape:
        expected *= int(d)
    if covered != expected:
        raise ValueError(
            f"checkpoint {base}: leaf {i} ({manifest['paths'][i]}) assembled "
            f"{covered}/{expected} elements — a shard block is missing or "
            f"overlapping (saved from {manifest['nprocs']} processes; "
            f"verify_checkpoint names the offending shard)"
        )


def _reshard_consolidated(path: Path, template, sharding_tree, info: dict):
    """Consolidated checkpoints are world-agnostic host pytrees already:
    read, shape-validate against the template (restore handles the
    identity-padded layer-axis adaptation), place at the target
    shardings. The blob is one msgpack — the format's memory floor is the
    full state on each restoring host, which is exactly why `save_auto`
    only picks it when the state is host-gatherable in the first place."""
    import jax

    from tpukit.mesh import place_host_array

    restored = ckpt_lib.restore(template, path)
    info["bytes_read"] = int(path.stat().st_size)
    info["blocks_read"] = 1
    if sharding_tree is None:
        return restored
    return jax.tree.map(place_host_array, restored, sharding_tree)


def reshard_restore(path, template, sharding_tree=None):
    """Restore a checkpoint of either format onto the CURRENT world's
    shardings, resharding as needed. Returns `(state, info)` where state's
    leaves are placed at `sharding_tree` (host arrays when None) and info
    records `{format, bytes_read, blocks_read, wall_s}` for the resize
    JSONL record.

    The target shardings need not match the ones the checkpoint was
    written under in world size, strategy, or both — resharding is pure
    data movement (bit-identical leaves), so a checkpoint written by
    FSDP@N restores into DDP@M exactly."""
    path = Path(path)
    info = {
        "format": "sharded" if path.is_dir() else "consolidated",
        "bytes_read": 0,
        "blocks_read": 0,
    }
    t0 = time.perf_counter()
    if path.is_dir():
        state = _reshard_sharded(path, template, sharding_tree, info)
    else:
        state = _reshard_consolidated(path, template, sharding_tree, info)
    info["wall_s"] = round(time.perf_counter() - t0, 6)
    return state, info

"""Runtime initialization and device-mesh construction.

TPU-native replacement for the reference's L0 comms layer (SURVEY §2.5):
`dist.init_process_group("nccl")` + torchrun/c10d rendezvous + manual
rank->`cuda:{rank % ndev}` binding (reference main-ddp.py:25-35, docstring
main-ddp.py:1-6). Under JAX there is no backend string and no launcher
incantation: the PJRT runtime owns the devices, `jax.distributed.initialize`
does the multi-host rendezvous (driven by the TPU runtime's own metadata),
and parallelism is expressed as a `jax.sharding.Mesh` over the device grid.
The compiler emits the ICI/DCN collectives from sharding annotations.
"""

from __future__ import annotations

import os

import jax
import numpy as np
from jax.sharding import Mesh

_initialized = False


def initialize_runtime() -> None:
    """Multi-host rendezvous (twin of init_mp, reference main-ddp.py:25-31).

    On a single host this is a no-op: the TPU runtime already knows its
    topology. On multi-host deployments (JAX_COORDINATOR_ADDRESS or a TPU pod
    environment), `jax.distributed.initialize()` wires up DCN — the
    capability the reference delegates to torchrun + c10d rendezvous.
    """
    global _initialized
    if _initialized:
        return
    if _distributed_client_active():
        _initialized = True  # launcher/runtime already did the rendezvous
        return
    # NB: must run BEFORE any backend-initializing JAX call (jax.devices(),
    # jax.process_count(), ...) — jax.distributed.initialize() refuses to run
    # after the XLA backend exists. So multi-host detection here is env-only.
    explicit = bool(os.environ.get("JAX_COORDINATOR_ADDRESS"))
    if explicit or _pod_env_detected():
        # jax.distributed.initialize has no env-var fallback for the process
        # count/rank (only launchers/cluster detection supply them), so an
        # explicit-coordinator launch passes them through from the
        # environment: the torchrun-style contract (reference main-ddp.py:1-6
        # rendezvous) without a launcher dependency.
        kwargs = {}
        if explicit:
            kwargs["coordinator_address"] = os.environ["JAX_COORDINATOR_ADDRESS"]
            n_procs = os.environ.get("JAX_NUM_PROCESSES")
            proc_id = os.environ.get("JAX_PROCESS_ID")
            # The pair must be set (or unset) together: passing only one to
            # jax.distributed.initialize fails with an opaque error deep in
            # JAX instead of naming the missing variable (ADVICE r4).
            if bool(n_procs) != bool(proc_id):
                missing = "JAX_PROCESS_ID" if n_procs else "JAX_NUM_PROCESSES"
                raise RuntimeError(
                    f"JAX_COORDINATOR_ADDRESS is set but only one of the "
                    f"process-identity pair is: {missing} is missing. Set "
                    "both JAX_NUM_PROCESSES and JAX_PROCESS_ID (or neither, "
                    "to let a launcher/cluster environment supply them)."
                )
            if n_procs:
                kwargs["num_processes"] = int(n_procs)
                kwargs["process_id"] = int(proc_id)
        try:
            jax.distributed.initialize(**kwargs)
        except Exception as exc:
            msg = str(exc).lower()
            # Actual JAX error texts for the two benign races:
            # "distributed.initialize should only be called once" and
            # "... must be called before any JAX calls that might initialise
            # the XLA backend" (when the launcher initialized both for us and
            # a client is now active).
            # "already been called"/"already initialized" are double-init
            # races (benign); a bare "already" substring would also swallow
            # genuine failures like "address already in use".
            if (
                "only be called once" in msg
                or "already been called" in msg
                or "already initialized" in msg
                or _distributed_client_active()
            ):
                pass  # initialized by the launcher/runtime — fine
            else:
                # The operator (explicit coordinator) or the environment (a
                # detected pod / multislice / SLURM / OMPI world) says this is
                # a multi-host run. Silently falling back would train N
                # independent single-host copies — the worst possible
                # failure mode on a pod. Fail loudly instead.
                why = (
                    "JAX_COORDINATOR_ADDRESS is set"
                    if explicit
                    else "a multi-host environment was detected"
                )
                raise RuntimeError(
                    f"{why} but jax.distributed.initialize() failed; "
                    "refusing to silently degrade to independent "
                    f"single-host training. Original error: {exc}"
                ) from exc
    _initialized = True


def _distributed_client_active() -> bool:
    """True when `jax.distributed` is already wired up (by us, a launcher,
    or the TPU runtime) — detected via the distributed client object, not by
    string-matching error messages."""
    try:
        from jax._src import distributed as _dist

        return _dist.global_state.client is not None
    except Exception:
        return False


def _pod_env_detected() -> bool:
    """Env-var-only sniff for a multi-host environment (no JAX calls, so the
    backend stays uninitialized and `jax.distributed.initialize()` is still
    legal). Covers Cloud TPU pod slices, megascale, SLURM and OMPI launchers
    — the environments JAX's own cluster auto-detection understands. Each
    signal must show MORE THAN ONE host (single-host TPU VMs also export
    TPU_WORKER_HOSTNAMES, as a one-entry list)."""
    if "," in os.environ.get("TPU_WORKER_HOSTNAMES", ""):  # pod slice
        return True
    if os.environ.get("MEGASCALE_COORDINATOR_ADDRESS"):  # multislice
        return True
    for k in ("SLURM_JOB_NUM_NODES", "OMPI_COMM_WORLD_SIZE"):
        try:
            if int(os.environ.get(k, "1")) > 1:
                return True
        except ValueError:
            pass
    return False


def is_process_zero() -> bool:
    """Twin of the reference's `rank == 0` gating (main-ddp.py:106,170,180)."""
    return jax.process_index() == 0


def create_mesh(axes: dict[str, int] | None = None, devices=None) -> Mesh:
    """Build a named device mesh.

    `axes` maps axis name -> size, e.g. `{"data": 8}` for DP/FSDP,
    `{"stage": 4}` for pipeline, `{"data": 2, "stage": 4}` for the 2-D
    hybrid. A size of -1 means "all remaining devices". With `axes=None`,
    returns a trivial 1-device mesh (the single-device recipe).
    """
    devices = np.asarray(devices if devices is not None else jax.devices())
    if axes is None:
        return Mesh(devices[:1].reshape(1), ("data",))

    names = tuple(axes.keys())
    sizes = list(axes.values())
    n = devices.size
    if -1 in sizes:
        known = int(np.prod([s for s in sizes if s != -1]))
        if n % known:
            raise ValueError(f"{n} devices not divisible by fixed axes {axes}")
        sizes[sizes.index(-1)] = n // known
    total = int(np.prod(sizes))
    if total > n:
        raise ValueError(f"mesh {dict(zip(names, sizes))} needs {total} devices, have {n}")
    return Mesh(devices[:total].reshape(sizes), names)


def place_host_array(x, sharding):
    """Place a host array at `sharding`, multi-host safe: single-process
    uses `device_put`; multi-process builds the global array from each
    host's addressable shards (`device_put` onto a sharding spanning
    non-addressable devices would raise). Every process must call this with
    the same value. Shared by checkpoint restore, resume placement and the
    decode-buffer path."""
    x = np.asarray(x)
    if jax.process_count() == 1:
        return jax.device_put(x, sharding)
    return jax.make_array_from_callback(x.shape, sharding, lambda idx, x=x: x[idx])


def device_kind() -> str:
    return jax.devices()[0].device_kind


def sync_global_devices(tag: str = "barrier") -> None:
    """Host-level sync where one is truly needed (twin of `dist.barrier()`,
    reference main-ddp.py:176,179 — but note SPMD needs none of the
    reference's barriers; this exists for multi-host checkpoint sequencing)."""
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)

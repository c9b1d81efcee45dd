"""tpukit.serve — continuous-batching inference engine (round 14, ROADMAP #1).

Device programs (batched KV-cached decode, per-bucket prefill, the fused
whole-batch loop, the TP comm audit) in `decode.py`; the host-side slot
scheduler, request/completion types, serving telemetry and the synthetic
stream in `engine.py`; the paged KV cache — page pool + block tables,
shared-prefix registry, chunked prefill, int8 page payloads (round 15,
ROADMAP #2) — in `paged.py`; speculative decoding — draft-and-verify
with distribution-exact rejection sampling, self-speculation and draft-
model proposers (round 17, ROADMAP #3) — in `spec.py`; fleet serving —
a request router over N replica engines on disjoint device subsets,
disaggregated prefill via paged-KV handoff, occupancy autoscale,
chaos kill with exactly-once requeue (round 19, ROADMAP #1) — in
`fleet.py`; the crash-tolerance plane — durable request ledger
(write-ahead leases, exactly-once completion records, replay), the
process-fleet supervisor with real-SIGKILL chaos and heartbeat
liveness, and the ledger-driven worker loop (round 24) — in
`ledger.py`. Recipe: `main-serve.py`.
"""

from tpukit.serve import paged, spec  # noqa: F401
from tpukit.serve.decode import (  # noqa: F401
    decode_loop,
    decode_step,
    decode_step_comm,
    prefill_chunk_paged,
    prefill_slots,
)
from tpukit.serve.engine import (  # noqa: F401
    STREAM_PROFILES,
    Completion,
    Request,
    ServeConfig,
    ServeEngine,
    synthetic_request_stream,
)
from tpukit.serve.fleet import (  # noqa: F401
    FleetConfig,
    FleetRouter,
    pick_serve_grid,
)
from tpukit.serve.ledger import (  # noqa: F401
    ProcessFleet,
    RequestLedger,
    local_tpu_chips,
    serve_from_ledger,
    worker_chip_env,
)

"""XLA static analysis of compiled step functions + live device memory.

Every run should know its roofline position and memory watermark without a
profiler attached (SURVEY §5; EQuARX in PAPERS.md shows collective volume
is a first-order cost worth metering). Three captures:

  - `compiled_stats(jitted_fn, *avals)`: AOT `lower().compile()` at the
    given avals and pull XLA's `cost_analysis()` (FLOPs, bytes accessed)
    and `memory_analysis()` (argument/output/temp/peak bytes). The AOT path
    does NOT share a compile with a later jit call of the same function, so
    fit() runs the executable this hands back (`hlo_out["executable"]`):
    one compile serves the analysis and the step.
  - `collective_bytes(hlo_text)`: per-collective-kind op counts and payload
    bytes parsed from the optimized HLO — the DP grad psum, FSDP
    all-gather/reduce-scatter, pipeline/ring ppermute, and MoE all_to_all
    traffic REPORTED from the compiled module instead of estimated from
    first principles. Each strategy declares which kinds it expects
    (`Strategy.comm_ops`), so a report can flag surprises.
  - `kernel_calls(hlo_text)` / `instruction_scopes(hlo_text)`: which Pallas
    kernels a module holds, and which of the program's named scopes each
    instruction belongs to — the join from a trace's op events to the
    program's own names.
  - `live_memory_stats()`: `device.memory_stats()` gauges (bytes in use,
    peak, limit) for the per-window HBM watermark line. Returns None on
    backends without the API (CPU).

Round 10 adds the hand-scheduled-collective audit half:
`capture_compiler_stderr()` (fd-level stderr capture — the channel XLA's
C++ partitioner warnings arrive on) and `count_involuntary_remat()` (the
`[SPMD] Involuntary full rematerialization` fallback, GSPMD's
replicate-then-repartition last resort — the round-5 EP dispatch
regression MULTICHIP_r05.json caught; zero is the bar for any step whose
collectives are placed by hand).

Round 16: the flat-regex HLO parse moved into `tpukit/analysis/hlo_ir.py`
as a structured IR (computations → instructions, while-body membership,
async pairing, the alias table). `collective_bytes`/`wire_bytes` here are
thin wrappers over it — same contract, same numbers (the golden-fixture
tests prove byte-for-byte equality against the original regex, kept below
as `_collective_bytes_regex` for exactly that proof).

Everything here is best-effort: any backend that lacks an analysis returns
None for that field rather than raising — telemetry must never take down a
training run.
"""

from __future__ import annotations

import contextlib
import os
import re
import sys
import tempfile

import jax

from tpukit.analysis import hlo_ir as _ir
from tpukit.analysis import plan as _plan
from tpukit.analysis.rules import (  # noqa: F401  (re-exported API)
    INVOLUNTARY_REMAT,
    count_involuntary_remat,
)

# Re-exported: the one spelling lives in analysis/hlo_ir.py.
COLLECTIVE_OPS = _ir.COLLECTIVE_OPS

# The pre-round-16 flat parse: `%x = SHAPES op-name(` where SHAPES is a
# single shape or a (tuple). Kept ONLY as the equivalence oracle for the
# golden-fixture tests (tests/test_analysis.py) — production callers go
# through the IR.
_OP_RE = re.compile(
    r"=\s+((?:\([^)]*\))|(?:\S+))\s+("
    + "|".join(COLLECTIVE_OPS)
    + r")(-start)?\("
)


def _collective_bytes_regex(hlo_text: str) -> dict[str, dict[str, int]]:
    """The original flat-regex parse, verbatim semantics. Test oracle."""
    out: dict[str, dict[str, int]] = {}
    for m in _OP_RE.finditer(hlo_text):
        shape_str, op, start = m.group(1), m.group(2), m.group(3)
        rec = out.setdefault(op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += _ir.result_payload_bytes(
            shape_str, op, is_start=start is not None
        )
    return out


def collective_bytes(hlo_text: str) -> dict[str, dict[str, int]]:
    """Parse optimized HLO text into {op: {count, bytes}} for the
    collective kinds above. `bytes` is the summed RESULT payload of each op
    instance — the volume moved per executed step (an all-reduce's result
    equals its input size; an all-gather's result is the post-gather
    size). Async `-start`/`-done` pairs count once, by their result.

    Thin wrapper over the structured IR (analysis/hlo_ir.py): each op is
    attributed to its computation once — a collective inside a while body
    is the body's, not a text offset — so rule-engine callers and this
    summary read the same parse."""
    return _ir.collective_summary(_ir.parse_hlo(hlo_text))


_KERNEL_CALL = re.compile(
    r"%([A-Za-z_]\w*?)(?:\.\d+)? = [^\n]*custom_call_target=\"tpu_custom_call\""
)


def kernel_calls(hlo_text: str) -> dict[str, int]:
    """{kernel name: call sites} of the Pallas kernels COMPILED into a
    module (`tpu_custom_call` custom calls; an interpreted kernel lowers to
    plain HLO and is absent). The name is the `name=` every tpukit
    `pallas_call` carries — flash_fwd, head_ce_bwd, paged_attend, ... — so
    a run can state which kernels were on its path."""
    out: dict[str, int] = {}
    for name in _KERNEL_CALL.findall(hlo_text):
        # autodiff and scan wrap the name: jvp_flash_fwd_, transpose_jvp_...
        name = re.sub(r"^(?:transpose_|jvp_)+", "", name).rstrip("_")
        out[name] = out.get(name, 0) + 1
    return out


# The program's device-side names (`jax.named_scope`), one spelling
# everywhere: the model's parts (tpukit/model/gpt.py), the train step's two
# halves (train.make_step_fns) and the serve programs' (serve/decode.py,
# serve/paged.py).
SCOPES = (
    "embed", "attn", "ffn", "moe", "ln", "head", "loss", "optimizer",
    "kv_gather", "kv_write", "attend", "sample", "prefill", "decode",
)

_INSTRUCTION_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%([\w.\-]+) = [^\n]*?metadata=\{[^}\n]*?op_name="([^"]*)"',
    re.MULTILINE,
)


def instruction_scopes(hlo_text: str, scopes=SCOPES) -> dict[str, str]:
    """{instruction name: scope path} for a compiled module's text, e.g.
    `{"fusion.12": "loss/attn", "flash_bwd.1": "loss/attn"}`. A profiler
    trace's op events carry the instruction (their name is its HLO text) and
    no scope, so this is the join from device time to the program's names.
    The path keeps the components of the instruction's `op_name` that are in
    `scopes`, outermost first, with autodiff/vmap wrappers (`jvp(attn)`,
    `transpose(jvp(attn))`) read through and a wrapper that restates the
    enclosing scope (`loss/transpose(loss)/...`) counted once; `jit(...)`
    components name functions, the last component the primitive. Instructions under no scope
    are left out. A fusion carries the metadata XLA kept for it (its root's)."""
    out: dict[str, str] = {}
    for name, op_name in _INSTRUCTION_OP_NAME.findall(hlo_text):
        path = []
        # XLA joins the op_names of instructions it merged with ";": the
        # first is the one the others were folded into
        parts = op_name.split(";")[0].split("/")
        for part in parts:
            if part.startswith(("jit(", "pjit(")):
                if part == parts[0]:
                    path = []  # a branch or loop body traced apart repeats the stack from its root
                continue
            scope = re.sub(r"^(?:\w+\()+", "", part).rstrip(")")
            if scope not in scopes:
                continue
            # autodiff restates the scope it was taken under (`loss/
            # transpose(loss)/jvp(attn)`): a wrapper naming the scope we are
            # already in adds no level
            if not (scope != part and path and path[-1] == scope):
                path.append(scope)
        if path:
            out[name] = "/".join(path)
    return out


def wire_bytes(collectives: dict[str, dict[str, int]], world: int) -> int:
    """Ring-model per-device interconnect bytes for a parsed collective
    summary — see `analysis.plan.ring_wire_bytes` (the one spelling; this
    wrapper keeps the historical obs import path)."""
    return _plan.ring_wire_bytes(collectives, world)


@contextlib.contextmanager
def capture_compiler_stderr(check: bool = False):
    """Capture OS-level stderr (fd 2) for the duration of the block — the
    channel XLA's C++ partitioner warnings arrive on, which Python-level
    sys.stderr redirection cannot see. Yields a dict whose "text" key holds
    the captured output after the block exits; whatever was captured is
    re-emitted to the real stderr so no diagnostics are swallowed.

    The involuntary-remat count is tallied at exit into the holder's
    "involuntary_remat" key — callers that used to re-spell
    `count_involuntary_remat(cap["text"])` read the count instead.
    `check=True` additionally RAISES on a nonzero count (the dryrun/test
    discipline: hand-placed collectives must compile warning-free).

    Used to audit a compile for involuntary-remat warnings (the dryrun's
    EP world, tests). Note: a compile served from the persistent
    compilation cache emits no warnings either way — the audit is
    meaningful on cold compiles.
    """
    sys.stderr.flush()
    holder = {"text": "", "involuntary_remat": 0}
    saved = os.dup(2)
    tmp = tempfile.TemporaryFile(mode="w+b")
    try:
        os.dup2(tmp.fileno(), 2)
        yield holder
    finally:
        sys.stderr.flush()
        os.dup2(saved, 2)
        os.close(saved)
        tmp.seek(0)
        holder["text"] = tmp.read().decode("utf-8", errors="replace")
        tmp.close()
        if holder["text"]:
            sys.stderr.write(holder["text"])
            sys.stderr.flush()
        holder["involuntary_remat"] = count_involuntary_remat(holder["text"])
    if check and holder["involuntary_remat"]:
        raise AssertionError(
            f"compile emitted {holder['involuntary_remat']} involuntary-"
            f"remat warning(s) — hand-placed collectives are supposed to "
            f"make these zero:\n{holder['text'][-2000:]}"
        )


def _cost_analysis_dict(compiled) -> dict | None:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    # jax returned a list-of-dicts (one per computation) before ~0.5, a
    # plain dict after
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else None
    return dict(ca) if ca else None


def _memory_analysis_dict(compiled) -> dict | None:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return None
    if ma is None:
        return None
    fields = (
        "argument_size_in_bytes",
        "output_size_in_bytes",
        "temp_size_in_bytes",
        "alias_size_in_bytes",
        "generated_code_size_in_bytes",
    )
    out = {f: int(getattr(ma, f)) for f in fields if hasattr(ma, f)}
    if "argument_size_in_bytes" in out and "temp_size_in_bytes" in out:
        # arguments alias in place (donated state), so live peak is
        # args + outputs-not-aliased + temps; report the conservative sum
        out["peak_bytes_estimate"] = (
            out["argument_size_in_bytes"]
            + out.get("output_size_in_bytes", 0)
            - out.get("alias_size_in_bytes", 0)
            + out["temp_size_in_bytes"]
        )
    return out or None


def compiled_stats(jitted_fn, *args, hlo_out: dict | None = None,
                   **kwargs) -> dict | None:
    """Static analysis record for `jitted_fn` at the given avals (pass
    `jax.ShapeDtypeStruct`s or arrays). Returns None when lowering fails;
    individual analyses a backend lacks come back as None fields.

    Record fields: `flops`, `bytes_accessed`, `transcendentals` (per
    executed step, from cost_analysis), `memory` (memory_analysis sizes),
    `collectives` ({op: {count, bytes}} from the optimized HLO), `kernels`
    ({name: call sites} of compiled Pallas kernels, `kernel_calls`).

    `hlo_out`: optional dict that receives the optimized module text under
    "text" — fit()'s rule-engine pass (analysis/rules.py) reads it so the
    hlolint verdicts ride the same AOT compile as the stats instead of
    paying a second lower() — and the executable itself under
    "executable": a second lowering of the same function numbers its
    private functions differently, so a later `jitted_fn(...)` call would
    miss both the jit and the persistent cache and compile the whole step
    again. The caller runs this executable instead.
    """
    try:
        compiled = jitted_fn.lower(*args, **kwargs).compile()
    except Exception:
        return None
    if hlo_out is not None:
        hlo_out["executable"] = compiled
    out: dict = {"flops": None, "bytes_accessed": None, "memory": None,
                 "collectives": None, "kernels": None}
    ca = _cost_analysis_dict(compiled)
    if ca:
        out["flops"] = ca.get("flops")
        out["bytes_accessed"] = ca.get("bytes accessed")
        if ca.get("transcendentals"):
            out["transcendentals"] = ca.get("transcendentals")
    out["memory"] = _memory_analysis_dict(compiled)
    try:
        text = compiled.as_text()
        if hlo_out is not None:
            hlo_out["text"] = text
        out["collectives"] = collective_bytes(text)
        out["kernels"] = kernel_calls(text)
    except Exception:
        pass
    return out


def live_memory_stats(device=None) -> dict | None:
    """Current device memory gauges, or None where the backend has no
    `memory_stats()` (CPU). Keys mirror PJRT's: bytes_in_use,
    peak_bytes_in_use, bytes_limit (whichever the platform reports)."""
    d = device if device is not None else jax.devices()[0]
    try:
        stats = d.memory_stats()
    except Exception:
        return None
    if not stats:
        return None
    keep = ("bytes_in_use", "peak_bytes_in_use", "bytes_limit",
            "largest_alloc_size")
    out = {k: int(stats[k]) for k in keep if k in stats}
    return out or None

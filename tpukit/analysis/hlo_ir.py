"""Structured IR over optimized HLO module text.

Every hard perf/correctness win since round 10 was caught or proven by an
HLO audit — the involuntary-remat detection, the exact closed-form byte
asserts, the s32 scatter-plumbing rewrite, the wire-dtype upcast, the
jaxlib donation mis-alias — but each check read the module as FLAT TEXT
(one regex over `compiled.as_text()`). Flat text cannot scope an op to its
computation (a collective inside the decode-quantum `while` body is the
body's, once — not a line at a text offset), cannot pair an async
`-start` with its `-done` to ask what runs between them, and never sees
the executable's input–output alias table at all. This module parses the
text once into computations → instructions and keeps those relationships,
so the rule engine (analysis/rules.py) asks structural questions instead
of re-deriving them per check.

The parser is deliberately jax-free: it consumes the printed text of an
optimized module (what `compiled.as_text()` returns, or a saved fixture)
and nothing else, so `tools/hlolint.py` can lint a captured `.hlo.txt`
without a backend and the golden-fixture tests stay import-light.

Grammar actually relied on (XLA's HloPrinter, stable across the versions
this repo has seen):

  HloModule <name>, key={...}, input_output_alias={ {0}: (0, {}, may-alias) }, ...
  %comp.1 (arg: (s32[], f32[8,8])) -> f32[8,8] { ... }
  ENTRY %main.25 (Arg_0.1: f32[8,8]) -> f32[8,8] { ... }
  [ROOT ]%name = SHAPE opcode(operands), attr=..., metadata={...}

Anything that does not match the instruction grammar is kept as an opaque
line rather than raising: lint must degrade to "less information", never
take down the audit that invoked it.
"""

from __future__ import annotations

import re
from dataclasses import dataclass, field

# HLO collective ops worth metering, normalized (async "-start" variants
# fold into the base name; "-done" carries no payload and is skipped).
# One spelling, shared with obs.xla (which re-exports it).
COLLECTIVE_OPS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "collective-permute",
    "all-to-all",
)

_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "f8e4m3fn": 1, "f8e5m2": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

# Integer element types wide enough to be GSPMD index plumbing (s8/u8 are
# quantized payloads, never indices; pred is a mask).
INDEX_DTYPES = ("s32", "u32", "s64", "u64")

# `f32[8,256]{1,0}` or scalar `f32[]` — group 1 dtype, group 2 dims.
_SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")


def itemsize(dtype: str) -> int | None:
    """Bytes per element for an HLO primitive type name, None for
    token/opaque types that carry no payload."""
    return _ITEMSIZE.get(dtype)


def _shape_list(shape_str: str) -> list[tuple[str, int]]:
    """[(dtype, bytes)] for every array shape in a shape/tuple string."""
    out = []
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        size = _ITEMSIZE.get(dtype)
        if size is None:
            continue  # token/opaque types carry no payload
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        out.append((dtype, n * size))
    return out


@dataclass
class Instruction:
    """One HLO instruction, as printed."""

    name: str                       # without the leading %
    opcode: str                     # as printed, e.g. "all-gather-start"
    raw_shape: str                  # result shape text, tuples included
    operands: tuple[str, ...]       # operand instruction names, without %
    attrs: str                      # raw text after the operand list
    computation: str = ""           # owning computation name
    index: int = 0                  # position within the computation
    is_root: bool = False

    @property
    def base_op(self) -> str:
        """Opcode with any async -start/-done suffix stripped."""
        for suffix in ("-start", "-done"):
            if self.opcode.endswith(suffix):
                return self.opcode[: -len(suffix)]
        return self.opcode

    @property
    def is_start(self) -> bool:
        return self.opcode.endswith("-start")

    @property
    def is_done(self) -> bool:
        return self.opcode.endswith("-done")

    def result_shapes(self) -> list[tuple[str, int]]:
        """[(dtype, bytes)] for every array in the result shape."""
        return _shape_list(self.raw_shape)

    def result_dtypes(self) -> set[str]:
        return {dt for dt, _ in self.result_shapes()}

    def attr(self, key: str) -> str | None:
        """Value of a `key=%name` / `key=value` attribute, or None."""
        m = re.search(rf"\b{re.escape(key)}=%?([\w.\-]+)", self.attrs)
        return m.group(1) if m else None


@dataclass
class Computation:
    """A named computation block: ENTRY, a while body/cond, a fusion, a
    reduction — whatever the printer emitted."""

    name: str
    instructions: list[Instruction] = field(default_factory=list)
    is_entry: bool = False
    # role, derived from the instruction that references this computation:
    # "entry" | "while_body" | "while_cond" | "fusion" | "reduction" |
    # "call" | "other"
    role: str = "other"
    # name of the referencing instruction's computation, e.g. the entry
    # computation for a top-level while body
    parent: str | None = None

    def find(self, opcode: str) -> list[Instruction]:
        return [i for i in self.instructions if i.opcode == opcode]


@dataclass
class Alias:
    """One input_output_alias table entry: output {output_index} aliases
    parameter `param_number` at {param_index}."""

    output_index: tuple[int, ...]
    param_number: int
    param_index: tuple[int, ...]
    kind: str  # "may-alias" | "must-alias"


@dataclass
class AsyncPair:
    """A matched `-start`/`-done` pair inside one computation, with the
    instructions scheduled between them. `compute_between` counts the
    non-trivial ones — the overlap the async form exists to buy."""

    start: Instruction
    done: Instruction
    between: list[Instruction]
    compute_between: int

    @property
    def overlapped(self) -> bool:
        return self.compute_between > 0


# Opcodes that shuffle or annotate values without doing work worth hiding
# a collective behind; everything else between a start/done pair counts as
# overlap compute.
_NONCOMPUTE_OPS = frozenset({
    "get-tuple-element", "tuple", "parameter", "constant", "bitcast",
    "copy", "after-all", "opt-barrier", "partition-id", "replica-id",
    "broadcast", "reshape", "transpose",
})


@dataclass
class HloModule:
    """Parsed module: computations by name, entry name, alias table."""

    name: str
    computations: dict[str, Computation]
    entry: str | None
    aliases: list[Alias]
    header: str = ""

    # -- navigation --------------------------------------------------------

    def instructions(self):
        """Every instruction in every computation, in printed order —
        exactly once each, because the printer emits each computation
        once no matter how many call sites it has."""
        for comp in self.computations.values():
            yield from comp.instructions

    def computation_of(self, instr: Instruction) -> Computation | None:
        return self.computations.get(instr.computation)

    def in_loop_body(self, instr: Instruction) -> bool:
        """True when the instruction's computation is (transitively) a
        while-loop body — a scan/decode-quantum op executed per iteration,
        printed once."""
        comp = self.computations.get(instr.computation)
        seen = set()
        while comp is not None and comp.name not in seen:
            seen.add(comp.name)
            if comp.role == "while_body":
                return True
            comp = self.computations.get(comp.parent) if comp.parent else None
        return False

    def collectives(self) -> list[Instruction]:
        """Every payload-carrying collective instance: the sync form and
        the async `-start` (the `-done` is the same transfer completing)."""
        out = []
        for instr in self.instructions():
            if instr.base_op in COLLECTIVE_OPS and not instr.is_done:
                out.append(instr)
        return out

    def async_pairs(self) -> list[AsyncPair]:
        """Matched `-start`/`-done` pairs, each with the instructions the
        schedule placed between them. A done whose start lives in another
        computation (never printed by XLA today) is skipped rather than
        mispaired."""
        pairs = []
        for comp in self.computations.values():
            starts = {
                i.name: i for i in comp.instructions if i.is_start
            }
            for done in comp.instructions:
                if not done.is_done:
                    continue
                start = next(
                    (starts[op] for op in done.operands if op in starts), None
                )
                if start is None:
                    continue
                between = comp.instructions[start.index + 1: done.index]
                compute = sum(
                    1 for i in between if i.opcode not in _NONCOMPUTE_OPS
                )
                pairs.append(AsyncPair(start, done, list(between), compute))
        return pairs

    def aliased_params(self) -> set[int]:
        """Parameter numbers covered by at least one alias entry."""
        return {a.param_number for a in self.aliases}

    def concurrent_compute(self, instr: Instruction) -> int:
        """How many compute instructions in `instr`'s computation are
        INDEPENDENT of it — neither in its operand (ancestor) cone nor in
        its result (descendant) cone. This is the dataflow form of the
        overlap question: independent work is exactly what a scheduler
        (XLA's latency-hiding scheduler on TPU, the thunk executor's
        concurrency on CPU) may place between a collective's start and
        done. An async `-start`/`-done` pair's compute_between is a
        schedule SAMPLE of this set; the cone measure is the
        backend-independent upper structure — a collective with an empty
        independent set can never overlap anything, whatever the
        scheduler does. Non-compute shuffles (_NONCOMPUTE_OPS), other
        collectives and `-done` halves don't count: hiding a wire behind
        another wire is not overlap."""
        comp = self.computations.get(instr.computation)
        if comp is None:
            return 0
        by_name, users = self._adjacency(comp)

        def cone(start: str, edges) -> set[str]:
            seen, todo = set(), [start]
            while todo:
                name = todo.pop()
                if name in seen:
                    continue
                seen.add(name)
                todo.extend(edges(name))
            return seen

        ancestors = cone(
            instr.name,
            lambda n: (op for op in by_name[n].operands if op in by_name),
        )
        descendants = cone(instr.name, lambda n: users.get(n, ()))
        dependent = ancestors | descendants
        count = 0
        for i in comp.instructions:
            if i.name in dependent:
                continue
            if i.opcode in _NONCOMPUTE_OPS or i.is_done:
                continue
            if i.base_op in COLLECTIVE_OPS:
                continue
            count += 1
        return count

    def _adjacency(self, comp: Computation):
        """(by_name, users) maps for one computation, memoized — the
        overlap gate walks one cone pair per declared collective, and
        rebuilding the maps per walk is O(collectives x instructions)
        for nothing."""
        cache = getattr(self, "_adjacency_cache", None)
        if cache is None:
            cache = self._adjacency_cache = {}
        hit = cache.get(comp.name)
        if hit is not None:
            return hit
        by_name = {i.name: i for i in comp.instructions}
        users: dict[str, list[str]] = {}
        for i in comp.instructions:
            for op in i.operands:
                if op in by_name:
                    users.setdefault(op, []).append(i.name)
        cache[comp.name] = (by_name, users)
        return cache[comp.name]


# -- parsing ----------------------------------------------------------------

# `%region_0.5 (arg: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {` /
# `ENTRY %main.25 (Arg_0.1: f32[8,8]) -> f32[8,8] {`
_COMP_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\)\s*->\s*.*\{\s*$")

# `[ROOT ]%name = SHAPE opcode(` — SHAPE is one shape or a (tuple); the
# tuple never nests for real result shapes, and XLA's printer interleaves
# /*index=N*/ comments which the permissive [^)]* absorbs.
_INSTR_RE = re.compile(
    r"^\s*(ROOT\s+)?%([\w.\-]+)\s*=\s*"
    r"((?:\([^)]*\))|(?:[^\s(]+))\s+"
    r"([a-z][\w\-]*)\("
)

_OPERAND_NAME_RE = re.compile(r"%([\w.\-]+)")

# A TPU module prints tiled layouts after every shape —
# `f32[512,512]{1,0:T(8,128)}`, `bf16[8,64]{1,0:T(8,128)(2,1)S(1)}` — whose
# parentheses the shape group of _INSTR_RE cannot cross. They carry nothing
# this IR reads, so they are dropped before matching (a plain `{1,0}` stays).
_TILED_LAYOUT_RE = re.compile(r"\]\{[^{}]*:[^{}]*\}")

_ALIAS_ENTRY_RE = re.compile(
    r"\{([\d,\s]*)\}:\s*\(\s*(\d+)\s*,\s*\{([\d,\s]*)\}\s*(?:,\s*([\w-]+))?\)"
)


def _index_tuple(text: str) -> tuple[int, ...]:
    return tuple(int(t) for t in text.replace(" ", "").split(",") if t)


def _parse_header(line: str) -> tuple[str, list[Alias]]:
    """Module name + alias table from the `HloModule ...` header line."""
    m = re.match(r"HloModule\s+([^\s,]+)", line)
    name = m.group(1) if m else ""
    aliases: list[Alias] = []
    key = "input_output_alias={"
    at = line.find(key)
    if at >= 0:
        # balanced-brace scan: the table nests {output_index} entries
        depth, start = 1, at + len(key)
        end = start
        while end < len(line) and depth:
            if line[end] == "{":
                depth += 1
            elif line[end] == "}":
                depth -= 1
            end += 1
        body = line[start: end - 1]
        for om, pn, pi, kind in _ALIAS_ENTRY_RE.findall(body):
            aliases.append(
                Alias(
                    output_index=_index_tuple(om),
                    param_number=int(pn),
                    param_index=_index_tuple(pi),
                    kind=kind or "may-alias",
                )
            )
    return name, aliases


def _split_operand_list(line: str, open_at: int) -> tuple[str, str]:
    """(operand text, attr tail) given the index of the opening paren —
    scans to the balanced close so nested tuple-shape parens inside the
    operand list don't truncate it."""
    depth, i = 0, open_at
    while i < len(line):
        c = line[i]
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
            if depth == 0:
                return line[open_at + 1: i], line[i + 1:]
        i += 1
    return line[open_at + 1:], ""


def parse_hlo(text: str) -> HloModule:
    """Parse printed (optimized) HLO module text. Tolerant by design:
    unrecognized lines are skipped, a truncated module still yields the
    computations that did print."""
    module_name = ""
    aliases: list[Alias] = []
    header = ""
    computations: dict[str, Computation] = {}
    entry: str | None = None
    current: Computation | None = None

    for raw in text.splitlines():
        line = raw.rstrip()
        if not line:
            continue
        if line.startswith("HloModule"):
            header = line
            module_name, aliases = _parse_header(line)
            continue
        if current is None:
            cm = _COMP_RE.match(line)
            if cm:
                comp = Computation(name=cm.group(2), is_entry=bool(cm.group(1)))
                computations[comp.name] = comp
                if comp.is_entry:
                    comp.role = "entry"
                    entry = comp.name
                current = comp
                continue
        elif line.startswith("}"):
            current = None
            continue
        line = _TILED_LAYOUT_RE.sub("]", line)
        im = _INSTR_RE.match(line)
        if not im:
            continue  # comments/continuations: opaque, never fatal
        if current is None:
            # instruction with no enclosing computation: a snippet or a
            # truncated dump. The flat regex this parser replaced accepted
            # those, so they land in an implicit "<toplevel>" computation
            # (per line — a later real computation header still opens its
            # own block) rather than vanishing.
            target = computations.setdefault(
                "<toplevel>", Computation(name="<toplevel>")
            )
        else:
            target = current
        root, name, shape, opcode = im.groups()
        open_at = im.end() - 1
        operand_text, attrs = _split_operand_list(line, open_at)
        instr = Instruction(
            name=name,
            opcode=opcode,
            raw_shape=shape,
            operands=tuple(_OPERAND_NAME_RE.findall(operand_text)),
            attrs=attrs,
            computation=target.name,
            index=len(target.instructions),
            is_root=bool(root),
        )
        target.instructions.append(instr)

    module = HloModule(
        name=module_name,
        computations=computations,
        entry=entry,
        aliases=aliases,
        header=header,
    )
    _link_roles(module)
    return module


def _link_roles(module: HloModule) -> None:
    """Derive each computation's role + parent from the instructions that
    reference it (`body=`/`condition=`/`calls=`/`to_apply=`)."""
    for instr in module.instructions():
        for key, role in (
            ("body", "while_body"),
            ("condition", "while_cond"),
            ("calls", "fusion" if instr.opcode == "fusion" else "call"),
            ("to_apply", "reduction"),
        ):
            target = instr.attr(key)
            if target is None:
                continue
            comp = module.computations.get(target)
            if comp is not None and not comp.is_entry:
                comp.role = role
                comp.parent = instr.computation


# -- collective summary (the obs.xla.collective_bytes contract) -------------

# Async `-start` ops whose result tuple ALIASES the operands alongside the
# results: `(operands..., results..., ctx scalars...)`. all-reduce-start's
# tuple (when present) holds only the reduced results — XLA's combiner
# fuses grad buffers into one variadic all-reduce — so halving it would
# drop real payload.
_START_WITH_OPERAND_ALIASES = ("all-gather", "collective-permute")


def payload_shapes(shape_str: str, op: str, is_start: bool) -> list[tuple[str, int]]:
    """(dtype, bytes) of the real payload arrays of one collective — the
    RULES' view of an instruction: async ctx scalars (small u32/s32
    appendages) dropped for every form, and the operand-alias half of
    `-start` tuples dropped, so a rule never prices the same buffer twice
    on the backends (TPU) that emit async pairs. `result_payload_bytes`
    below keeps the historical sync-op contract (full result tuple, ctx
    scalars only dropped on async starts) — that is the byte accounting
    the regex-equality fixtures pin; rules want the true payload."""
    shapes = [
        (dt, b) for dt, b in _shape_list(shape_str)
        if not (b <= 8 and dt in ("u32", "s32", "u64", "s64"))
    ]
    if is_start and op in _START_WITH_OPERAND_ALIASES:
        if len(shapes) >= 2 and len(shapes) % 2 == 0:
            shapes = shapes[len(shapes) // 2:]
    return shapes


def result_payload_bytes(shape_str: str, op: str, is_start: bool) -> int:
    """Result payload of one collective instance. Sync ops: the full result
    shape (a tuple IS the result for multi-operand all-reduce). For async
    `-start` forms of the operand-aliasing ops above, count only the
    results half, else the aliases double the reported volume on exactly
    the backends (TPU) that emit async pairs."""
    shapes = _shape_list(shape_str)
    if is_start and op in _START_WITH_OPERAND_ALIASES:
        # drop the u32/s32 context scalars these async ops append
        shapes = [
            (dt, b) for dt, b in shapes
            if not (b <= 8 and dt in ("u32", "s32", "u64", "s64"))
        ]
        if len(shapes) >= 2 and len(shapes) % 2 == 0:
            shapes = shapes[len(shapes) // 2:]
    return sum(b for _, b in shapes)


def collective_summary(module: HloModule) -> dict[str, dict[str, int]]:
    """{op: {count, bytes}} over every payload-carrying collective in the
    module — the contract `obs.xla.collective_bytes` has always reported,
    now computed from the IR (each op attributed to its computation once,
    not rediscovered by text position). Byte-for-byte equal to the
    original flat-regex parse on the golden fixtures
    (tests/test_analysis.py proves it)."""
    out: dict[str, dict[str, int]] = {}
    for instr in module.collectives():
        rec = out.setdefault(instr.base_op, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += result_payload_bytes(
            instr.raw_shape, instr.base_op, instr.is_start
        )
    return out

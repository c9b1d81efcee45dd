#!/usr/bin/env python
"""lint_invariants — AST-level repo lint for hard-won host-side rules.

Three one-spelling rules, each earned by a real incident, each cheap to
re-break in review because the broken form LOOKS idiomatic:

  atomic-publish     Every tmp+rename file publish goes through
                     `fsio.atomic_write_text` (historically reached as
                     `checkpoint._atomic_write_text`, now a delegate) —
                     one tmp-naming scheme, one rename rule. A
                     hand-rolled `write_text` + rename pair re-opens the
                     torn-read/tmp-collision class the round-9 review
                     closed (recovery._atomic_write_json was delegated
                     for exactly this). Flags all three spellings:
                     `os.replace`/`os.rename`, the bare names when
                     `from os import replace/rename` is in scope, and
                     pathlib's one-argument `.replace(target)` /
                     `.rename(target)` method calls (str.replace takes
                     two arguments, so the single-operand form is the
                     Path publish idiom) — anywhere outside
                     `atomic_write_text` itself.
  retry-io           Checkpoint blob/shard/manifest I/O is wrapped in
                     `retry.retry_io`: the raw helpers (`_read_blob`,
                     `_write_blob`, `_write_shard`, `_write_shard_digest`)
                     may be passed TO retry_io but never called directly —
                     a direct call silently opts that path out of the
                     round-9 transient-fault budget. Round 24 puts the
                     request-ledger helpers (`_write_rec`, `_read_rec`,
                     tpukit/serve/ledger.py) under the same rule: fleet
                     serving's durable records share the transient-fault
                     budget, and the chaos harness's ledger_io_fail
                     injections must always land inside a retry.
  sampling-spelling  No new `fold_in`-based sampling math outside
                     `sampling._sample_next`: flags
                     `jax.random.categorical` calls anywhere else. The
                     round-14 review collapsed three copies of the
                     temperature/top-k/fold_in math into that one
                     function BECAUSE the triplication was the
                     token-parity guarantee's weak point.
  collective-spelling The wire-collective launches (`lax.all_to_all`,
                     `lax.all_gather`, `lax.psum_scatter` — each lowers
                     to an async start/done pair on TPU) live in
                     `tpukit/ops/quant_comm.py`, the bucket scheduler's
                     home (round 18): a raw launch anywhere else
                     bypasses the packed-payload/closed-form-byte/
                     overlap-declaration machinery the audits gate, the
                     way sampling math outside `_sample_next` bypassed
                     the parity guarantee. ring_attention's ulysses
                     head-repartition a2a + pad-mask gather carry
                     reasoned waivers (activation re-layout inside the
                     attention schedule, audited by CP's comm_ops — not
                     a grad/dispatch wire).
  online-softmax-spelling
                     The flash-attention running-max/renormalize update
                     has ONE spelling: `pallas_attention.
                     online_softmax_update`, shared by the training
                     kernels and the paged decode kernel (round 21). A
                     re-derived copy in a new kernel is exactly how the
                     max/exp/correction order drifts and the paged
                     kernel's token-parity bar silently moves — the
                     degenerate-to-plain-softmax exactness argument
                     holds for the owner's spelling, not for "a"
                     spelling. Flags `maximum(..., max(...))` — nested,
                     or through a name assigned from a `.max(...)` call
                     in the same function — inside tpukit/ops/ outside
                     the owner. fused_head_ce's online LOGSUMEXP carries
                     a reasoned waiver (it streams lse + argmax
                     tie-break state, a different contract than the
                     owner's `(m, l, correction, p)`).
  stdlib-only        `tpukit/obs/trace.py` and `tpukit/obs/metrics.py`
                     import NOTHING heavier than the stdlib — no jax,
                     no numpy, no tpukit (round 22; trace.py pioneered
                     the discipline, metrics.py is the second owner).
                     The post-mortem tools (traceview.py, top.py,
                     report.py) load them by file path on machines the
                     logs were merely copied to, and `import tpukit`
                     transitively pulls jax; one convenience import
                     silently breaks every offline consumer. Flags any
                     `import`/`from ... import` of jax/numpy/tpukit (or
                     a submodule) in those two files.

Waivers: a site that is legitimately outside a rule carries an inline
comment on the flagged line —

    os.replace(path, dest)  # lint: allow(atomic-publish): quarantine rename, not a publish

The rule name must match and a reason is REQUIRED (a bare allow is
itself a violation). Zero violations on the current tree; CI runs this
next to tools/hlolint.py.

Usage:
    python tools/lint_invariants.py            # lint the repo
    python tools/lint_invariants.py --root DIR # lint another tree
Exit 1 on any violation.
"""

from __future__ import annotations

import argparse
import ast
import re
import sys
from dataclasses import dataclass
from pathlib import Path

# Scanned relative to the root: production host-side code. tests/ are
# excluded — they plant broken spellings on purpose.
SCAN_GLOBS = (
    "tpukit/**/*.py",
    "tools/*.py",
    "main-*.py",
    "__graft_entry__.py",
)

RULES = ("atomic-publish", "retry-io", "sampling-spelling",
         "collective-spelling", "online-softmax-spelling", "stdlib-only")

# Module roots banned in the stdlib-only files: anything that would make
# a by-file-path load pull an accelerator stack (tpukit/__init__ imports
# jax via tpukit.model).
_HEAVY_ROOTS = frozenset({"jax", "jaxlib", "numpy", "np", "tpukit",
                          "flax", "optax"})

# The raw checkpoint I/O helpers that must ride retry_io.
_RAW_IO_HELPERS = frozenset({
    "_read_blob", "_write_blob", "_write_shard", "_write_shard_digest",
})

# The raw request-ledger I/O helpers (tpukit/serve/ledger.py, round 24)
# under the same discipline: every call site outside their home file
# wraps them in retry_io so fleet serving survives transient filesystem
# errors — and so the chaos harness's ledger_io_fail injections always
# land inside a retry budget.
_LEDGER_IO_HELPERS = frozenset({"_write_rec", "_read_rec"})

# The wire-collective primitives quant_comm.py owns (collective-spelling):
# the async-start spellings of the grad/dispatch wire. lax.psum/ppermute
# stay unrestricted — scalar reductions and ring hops are not the bucket
# scheduler's payload ops.
_WIRE_COLLECTIVES = frozenset({"all_to_all", "all_gather", "psum_scatter"})

_WAIVER_RE = re.compile(r"#\s*lint:\s*allow\(([\w\-]+)\)\s*:?\s*(.*)")


@dataclass
class Violation:
    rule: str
    path: str
    line: int
    message: str

    def __str__(self) -> str:
        return f"{self.path}:{self.line}: [{self.rule}] {self.message}"


def _is_max_call(node: ast.AST) -> bool:
    """True for a `<mod>.max(...)` call (jnp.max / np.max / lax.max —
    any attribute spelling of a row-max reduction)."""
    return (
        isinstance(node, ast.Call)
        and isinstance(node.func, ast.Attribute)
        and node.func.attr == "max"
    )


def _waiver_on(lines: list[str], lineno: int) -> tuple[str, str] | None:
    """(rule, reason) of a waiver comment on the given 1-based line."""
    if 1 <= lineno <= len(lines):
        m = _WAIVER_RE.search(lines[lineno - 1])
        if m:
            return m.group(1), m.group(2).strip()
    return None


class _Visitor(ast.NodeVisitor):
    def __init__(self, path: Path, rel: str, lines: list[str],
                 owner_funcs: frozenset[str],
                 wire_collective_owner: bool = False,
                 ops_kernel_file: bool = False,
                 stdlib_only_file: bool = False):
        self.path = path
        self.rel = rel
        self.lines = lines
        # function names whose bodies this FILE may legitimately contain
        # (the one-spelling owners); a same-named function in any other
        # file must not self-exempt
        self.owner_funcs = owner_funcs
        # True only for tpukit/ops/quant_comm.py: the one file allowed to
        # launch the wire collectives directly (collective-spelling)
        self.wire_collective_owner = wire_collective_owner
        # True for files under tpukit/ops/: the only tree where the
        # online-softmax-spelling rule applies (kernel code)
        self.ops_kernel_file = ops_kernel_file
        # True for tpukit/obs/{trace,metrics}.py: the by-file-path
        # loadable modules that must stay jax/numpy/tpukit-free
        self.stdlib_only_file = stdlib_only_file
        self.out: list[Violation] = []
        self.func_stack: list[str] = []
        # names bound by `from os import replace/rename` in this file
        self.os_fn_aliases: set[str] = set()
        # per-scope names assigned from a `.max(...)` call — the
        # spelled-out form of the online-softmax running max
        # (`row_max = jnp.max(s); maximum(m, row_max)`); [0] is module
        # scope, one frame pushed per function
        self._max_names: list[set[str]] = [set()]

    # -- helpers -----------------------------------------------------------

    def _flag(self, rule: str, node: ast.AST, message: str) -> None:
        waiver = _waiver_on(self.lines, node.lineno)
        if waiver is not None:
            wrule, reason = waiver
            if wrule == rule:
                if not reason:
                    self.out.append(Violation(
                        rule, self.rel, node.lineno,
                        f"waiver without a reason — `# lint: "
                        f"allow({rule}): <why>` must say why",
                    ))
                return
        self.out.append(Violation(rule, self.rel, node.lineno, message))

    def _in_function(self, name: str) -> bool:
        return name in self.owner_funcs and name in self.func_stack

    # -- traversal ---------------------------------------------------------

    def visit_FunctionDef(self, node):
        self.func_stack.append(node.name)
        self._max_names.append(set())
        self.generic_visit(node)
        self._max_names.pop()
        self.func_stack.pop()

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Assign(self, node: ast.Assign):
        if _is_max_call(node.value):
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._max_names[-1].add(t.id)
        self.generic_visit(node)

    def _check_stdlib_only(self, node: ast.AST, module: str) -> None:
        if not self.stdlib_only_file:
            return
        root = module.split(".")[0]
        if root in _HEAVY_ROOTS:
            self._flag(
                "stdlib-only", node,
                f"import of {module} in a stdlib-only module — "
                f"traceview.py/top.py/report.py load this file by path on "
                f"machines without jax; keep it importable bare (round-22 "
                f"discipline, tests assert it too)",
            )

    def visit_Import(self, node: ast.Import):
        for a in node.names:
            self._check_stdlib_only(node, a.name)
        self.generic_visit(node)

    def visit_ImportFrom(self, node: ast.ImportFrom):
        if node.module == "os":
            for a in node.names:
                if a.name in ("replace", "rename"):
                    self.os_fn_aliases.add(a.asname or a.name)
        if node.module and node.level == 0:
            self._check_stdlib_only(node, node.module)
        self.generic_visit(node)

    def _is_rename_call(self, node: ast.Call) -> str | None:
        """Spelling of a file-rename call, or None: `os.replace(...)`,
        a bare `replace(...)` bound by `from os import replace`, or
        pathlib's one-positional-argument `p.replace(target)` (str.replace
        needs two operands, so the single-operand method form is the Path
        publish idiom)."""
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in ("replace", "rename"):
            if isinstance(fn.value, ast.Name) and fn.value.id == "os":
                return f"os.{fn.attr}"
            if len(node.args) == 1 and not node.keywords:
                return f"Path.{fn.attr}"
        if (
            isinstance(fn, ast.Name)
            and fn.id in self.os_fn_aliases
        ):
            return f"os.{fn.id} (imported bare)"
        return None

    def visit_Call(self, node: ast.Call):
        fn = node.func
        # atomic-publish: any rename spelling outside atomic_write_text
        rename = self._is_rename_call(node)
        if rename is not None and not (
            self._in_function("atomic_write_text")
            or self._in_function("atomic_write_bytes")
        ):
            self._flag(
                "atomic-publish", node,
                f"{rename}() outside fsio.atomic_write_text — file "
                f"publishes go through the one atomic-write spelling (or "
                f"carry a waiver naming why this is a rename, not a "
                f"publish)",
            )
        # retry-io: direct call of a raw checkpoint/ledger I/O helper
        if (
            isinstance(fn, ast.Name)
            and fn.id in (_RAW_IO_HELPERS | _LEDGER_IO_HELPERS)
            and not self._in_function(fn.id)
        ):
            what = ("checkpoint blob/manifest"
                    if fn.id in _RAW_IO_HELPERS else "request-ledger")
            self._flag(
                "retry-io", node,
                f"direct call of {fn.id}() — {what} I/O "
                f"must be wrapped: retry_io({fn.id}, ...) keeps it inside "
                f"the transient-fault budget",
            )
        # sampling-spelling: jax.random.categorical outside _sample_next
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr == "categorical"
            and not self._in_function("_sample_next")
        ):
            self._flag(
                "sampling-spelling", node,
                "categorical() sampling outside sampling._sample_next — "
                "every decode path shares ONE fold_in/temperature/top-k "
                "spelling (the round-14 parity guarantee); route through "
                "_sample_next",
            )
        # online-softmax-spelling: a hand-rolled flash running-max update
        # (`maximum(m, max(s))`, nested or via an assigned row-max name)
        # in kernel code outside online_softmax_update
        if (
            self.ops_kernel_file
            and isinstance(fn, ast.Attribute)
            and fn.attr == "maximum"
            and not self._in_function("online_softmax_update")
            and any(
                _is_max_call(a)
                or (isinstance(a, ast.Name) and a.id in self._max_names[-1])
                for a in node.args
            )
        ):
            self._flag(
                "online-softmax-spelling", node,
                "hand-rolled online-softmax running-max update — the "
                "flash max/renormalize step has ONE spelling, "
                "pallas_attention.online_softmax_update, so the training "
                "and paged-decode kernels cannot drift (round 21); call "
                "the owner (or carry a waiver naming why this "
                "maximum-of-max is not an online softmax)",
            )
        # collective-spelling: a raw wire-collective launch (the async
        # start/done ops of the grad/dispatch wire) outside quant_comm.py
        if (
            isinstance(fn, ast.Attribute)
            and fn.attr in _WIRE_COLLECTIVES
            and not self.wire_collective_owner
        ):
            self._flag(
                "collective-spelling", node,
                f"lax.{fn.attr}() outside tpukit/ops/quant_comm.py — the "
                f"wire collectives live in the bucket scheduler's home so "
                f"every launch carries the packed payload, closed-form "
                f"byte audit and overlap declaration (round 18); route "
                f"through the quant_comm wrappers (or carry a waiver "
                f"naming why this launch is not a grad/dispatch wire)",
            )
        self.generic_visit(node)


def lint_file(path: Path, rel: str | None = None) -> list[Violation]:
    """Lint one file; unparseable files report as a violation rather than
    crashing the sweep."""
    rel = rel or str(path)
    try:
        source = path.read_text()
        tree = ast.parse(source, filename=str(path))
    except (OSError, SyntaxError) as e:
        return [Violation("parse", rel, getattr(e, "lineno", 0) or 0,
                          f"could not parse: {e}")]
    # one-spelling owner functions, honored only in their home file — a
    # same-named function anywhere else must not self-exempt
    norm = rel.replace("\\", "/")
    owners = set()
    if norm.endswith("tpukit/fsio.py"):
        # THE rename sites (text + binary twins)
        owners.update(("atomic_write_text", "atomic_write_bytes"))
    if norm.endswith("tpukit/checkpoint.py"):
        owners.update(_RAW_IO_HELPERS)  # a helper may recurse on itself
    if norm.endswith("tpukit/serve/ledger.py"):
        owners.update(_LEDGER_IO_HELPERS)  # the ledger defines its helpers
    if norm.endswith("tpukit/sampling.py"):
        owners.add("_sample_next")
    if norm.endswith("tpukit/ops/pallas_attention.py"):
        owners.add("online_softmax_update")  # THE flash max/renorm update
    v = _Visitor(
        path, rel, source.splitlines(), frozenset(owners),
        wire_collective_owner=norm.endswith("tpukit/ops/quant_comm.py"),
        ops_kernel_file="tpukit/ops/" in norm,
        stdlib_only_file=(norm.endswith("tpukit/obs/trace.py")
                          or norm.endswith("tpukit/obs/metrics.py")),
    )
    v.visit(tree)
    return v.out


def lint_tree(root: Path) -> list[Violation]:
    out: list[Violation] = []
    seen: set[Path] = set()
    for pattern in SCAN_GLOBS:
        for path in sorted(root.glob(pattern)):
            if path in seen or not path.is_file():
                continue
            seen.add(path)
            out.extend(lint_file(path, str(path.relative_to(root))))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    ap.add_argument("--root", default=str(Path(__file__).resolve().parent.parent),
                    help="tree to lint (default: this repo)")
    ap.add_argument("paths", nargs="*",
                    help="specific files to lint instead of the tree sweep")
    args = ap.parse_args(argv)

    if args.paths:
        violations = []
        for p in args.paths:
            violations.extend(lint_file(Path(p)))
    else:
        violations = lint_tree(Path(args.root))

    for v in violations:
        print(v)
    if violations:
        print(f"lint_invariants: {len(violations)} violation(s)",
              file=sys.stderr)
        return 1
    print("lint_invariants: clean", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())

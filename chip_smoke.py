#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that tpukit still starts on the chip.

ONE process that stays on the TPU from its first JAX call to exit, drives
the two main paths through the entry points a user calls, and fails (exit
code != 0, last stdout line `"ok": false`) on the first thing that is wrong.
It refuses to run at all unless `jax.devices()[0].platform == "tpu"`.

    python chip_smoke.py              # one chip, ~GPT-small width
    python chip_smoke.py --multichip  # four chips: the sharded recipes only

Default phases (one JSON line each as it finishes: wall seconds, compile
seconds, persistent-cache hits/misses, what was checked):

  1. kernels     every Pallas kernel family runs COMPILED (`tpu_custom_call`
                 in the module) at GPT-small widths and agrees with its
                 plain-jnp reference — the references tests/ hold the same
                 kernels to in interpret mode.
  2. train       `main-single.py` main(argv) -> fit(): real loader,
                 prefetch, eval, generation, checkpoint write, at GPT-small
                 width on the offline corpus.
  3. full_width  the same model and batch geometry at the GPT-2 vocab
                 (50,257) through create_train_state + make_step_fns — the
                 fused head+CE kernel at its published width. A check, not
                 a timing.
  4. serve       `main-serve.py` main(argv) restoring phase 2's checkpoint:
                 ring cache (XLA attention), paged cache (on the chip its
                 decode tick reads the pool through the paged-attention
                 kernel: gpt.decode_read_in_kernel) and the same under the
                 on-device window (--fused_decode); the three greedy token
                 streams compared request by request.

`--multichip` runs ONLY the four-chip path and what it is compared with:
the full-width train step under DataParallel, FSDP, TensorParallel
(data=2 x model=2) and a 4-stage 1F1B Pipeline against the single-device
loss trajectory of the same seed and global batch.

The last stdout line is exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": N}}`.
Everything is written under `chip_smoke_out/` (plus the compile cache, placed
by tpukit/cache.py's rule, and the native tokenizer's build product).

The phases are functions of a `Sizes` record so the control flow can be
rehearsed on the CPU at toy sizes (tests/, interpret-mode kernels, virtual
devices); this COMMAND never runs there.
"""

from __future__ import annotations

import argparse
import dataclasses
import functools
import gc
import importlib.util
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"

# bf16 keeps 8 significand bits against float32's 24, so the tests' float32
# tolerances (1e-5 .. 5e-4) cannot carry over. A kernel output is compared
# with a float32 reference by its largest error relative to the reference's
# largest magnitude: 2e-2 forward (about five bf16 ulps of the largest
# value), 4e-2 for gradients (two chained bf16 matmuls). The paged kernel
# keeps tests/test_paged_attention.py's own bf16 bar, 5e-2.
FWD_TOL, GRAD_TOL, PAGED_TOL = 2e-2, 4e-2, 5e-2
# Sharded-vs-single loss trajectories: the CPU suite holds float32 losses to
# 1e-5 and bf16 eval losses to 1e-2 (tests/test_strategies.py). On the chip
# the train step computes in bf16 and the cross-chip reductions sum in another
# order; over four steps on four v5e chips the largest gaps measured were
# DDP 5.9e-4, FSDP 3.2e-4, TP 6.8e-4, 1F1B 2.8e-4 (losses 11.0 -> 9.8). The
# bar is widened from the float32 one only to half the suite's bf16 bar:
# |loss - single| <= 5e-3 at every step.
TRAJECTORY_TOL = 5e-3


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Everything that scales. The defaults are GPT-small (the r05 ladder's
    first rung) at a sequence long enough that `attention_impl="auto"`
    takes the flash kernel after prepare_batch's shift to S-1 tokens."""

    dim: int = 768
    heads: int = 12
    head_dim: int = 64
    layers: int = 12
    seq: int = 1024
    batch: int = 16
    vocab: int = 50257
    # phase 1
    flash_seqs: tuple = (1024, 2048)
    flash_batch: int = 2
    head_tokens: int = 4096
    moe_shapes: tuple = ((4096, 256, 1024, 8), (2048, 768, 3072, 2))  # M, D, F, E
    page: int = 16
    pages_per_slot: int = 16
    paged_slots: int = 8
    # phase 2: rows of the offline corpus -> rows / batch steps
    train_rows: int = 512
    learning_rate: float = 3e-4
    # phase 3 / multichip
    steps: int = 4
    # phase 4
    requests: int = 32
    slots: int = 8
    max_new_tokens: int = 20
    buckets: str = "16,32,64"


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


def check(condition, detail="") -> None:
    """`assert` that `python -O` cannot strip: a smoke with its checks
    compiled out would pass on anything."""
    if not condition:
        raise SmokeFailure(detail)


def emit(record: dict) -> None:
    print(json.dumps(record), flush=True)


def run_phase(name: str, fn, *args):
    """Run one phase — `fn` returns (what it checked, what later phases
    need) — and print its JSON line. No except: a phase that fails takes
    the run down with it."""
    from tpukit.cache import enable_compilation_cache

    stats = enable_compilation_cache()  # counts from here on; same placement
    t0 = time.perf_counter()
    checked, carry = fn(*args)
    cs = stats.stats()
    emit({
        "phase": name,
        "wall_s": round(time.perf_counter() - t0, 2),
        "compile_s": cs["compile_s"],
        "cache_hits": cs["hits"],
        "cache_misses": cs["misses"],
        "checked": checked,
    })
    return carry


def load_recipe(name: str):
    """Import a recipe file (`main-single.py` is not an importable name)."""
    spec = importlib.util.spec_from_file_location(
        name.replace("-", "_").removesuffix(".py"), ROOT / name
    )
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def rel_err(name: str, got, ref, tol: float) -> float:
    """Largest |got - ref| over largest |ref|; checks finite and <= tol."""
    import numpy as np

    got = np.asarray(got, np.float32)
    ref = np.asarray(ref, np.float32)
    check(got.shape == ref.shape, f"{name}: shape {got.shape} != {ref.shape}")
    check(np.isfinite(got).all(), f"{name}: non-finite values")
    err = float(np.max(np.abs(got - ref))) / max(float(np.max(np.abs(ref))), 1e-30)
    check(err <= tol, f"{name}: relative error {err:.3e} over tolerance {tol}")
    return round(err, 6)


def check_kernels(kernels: dict, expect: tuple) -> None:
    """Every kernel-name prefix in `expect` is a `tpu_custom_call` of the
    module `kernels` (obs.xla.kernel_calls) was read from — an interpreted
    kernel is plain HLO and is not there."""
    for prefix in expect:
        check(any(name.startswith(prefix) for name in kernels), (
            f"no compiled {prefix}* kernel in the module: {kernels}"
        ))


def compile_checked(fn, args, expect: tuple, compiled: bool):
    """Compile `fn` once and return the executable; with `compiled` (always,
    on the chip) it must hold the `expect`ed kernels."""
    import jax

    from tpukit.obs.xla import kernel_calls
    from tpukit.ops.pallas_attention import _interpret

    exe = jax.jit(fn).lower(*args).compile()
    if compiled:
        check(not _interpret(), "kernels are in interpret mode")
        check_kernels(kernel_calls(exe.as_text()), expect)
    return exe


# ---------------------------------------------------------------------------
# Phase 1: kernels vs their references
# ---------------------------------------------------------------------------


def phase_kernels(sz: Sizes, compiled: bool) -> tuple[dict, None]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpukit.ops import quant_comm
    from tpukit.ops.attention import causal_attention
    from tpukit.ops.fused_head_ce import fused_head_ce
    from tpukit.ops.layers import cross_entropy_sum, masked_accuracy
    from tpukit.ops.moe_gemm import _plan_rows, grouped_ffn
    from tpukit.ops.paged_attention import paged_attend, paged_attend_reference
    from tpukit.ops.pallas_attention import flash_causal_attention

    bf16, f32 = jnp.bfloat16, jnp.float32
    rng = np.random.RandomState(0)
    up = lambda *xs: [x.astype(f32) for x in xs]  # noqa: E731
    checked = {}

    # -- flash attention fwd + bwd, masked and not -------------------------
    scale = sz.head_dim**-0.5
    for seq in sz.flash_seqs:
        shape = (sz.flash_batch, sz.heads, seq, sz.head_dim)
        q, k, v, cot = (jnp.asarray(rng.randn(*shape), bf16) for _ in range(4))
        pad = np.zeros((sz.flash_batch, seq), bool)
        pad[0, seq - seq // 5:] = True  # row 0 has trailing padding
        for mask in (None, jnp.asarray(pad)):
            keep = 1.0 if mask is None else (~mask)[:, None, :, None]

            # the cotangent is an ARGUMENT, not a closed-over constant: XLA
            # embeds constants in the executable (12 MB of it at S=2048),
            # and the tool's compile cache is capped
            def run(attend, q, k, v, cot):
                def loss(q, k, v):
                    out = attend(q, k, v, scale=scale, pad_mask=mask)
                    # fully padded query rows are a documented divergence
                    # (pallas_attention.py) and carry no loss: leave them out
                    out = out * keep
                    return jnp.sum(out.astype(f32) * cot.astype(f32)), out

                (_, out), grads = jax.value_and_grad(
                    loss, argnums=(0, 1, 2), has_aux=True
                )(q, k, v)
                return out, grads

            out, grads = compile_checked(
                functools.partial(run, flash_causal_attention),
                (q, k, v, cot), ("flash_",), compiled,
            )(q, k, v, cot)
            with jax.default_matmul_precision("highest"):
                ref_out, ref_grads = jax.jit(functools.partial(
                    run, functools.partial(causal_attention, impl="xla"),
                ))(*up(q, k, v, cot))
            tag = f"flash S={seq} {'masked' if mask is not None else 'unmasked'}"
            checked[tag] = {
                "fwd": rel_err(tag, out, ref_out, FWD_TOL),
                **{
                    f"d{n}": rel_err(f"{tag} d{n}", g, r, GRAD_TOL)
                    for n, g, r in zip("qkv", grads, ref_grads)
                },
            }

    # -- fused head + cross-entropy: train (fwd+bwd) and eval --------------
    n, dim, vocab = sz.head_tokens, sz.dim, sz.vocab
    v_pad = -(-vocab // 128) * 128
    h = jnp.asarray(rng.randn(n, dim), bf16)
    w = jnp.asarray(rng.randn(dim, v_pad) * 0.05, bf16)
    tgt = rng.randint(0, vocab, n).astype(np.int32)
    tgt[::7] = -100  # ignored rows
    tgt = jnp.asarray(tgt)

    def fused_loss(h, w):
        s, c, _ = fused_head_ce(h, w, tgt, vocab)
        return s / jnp.maximum(c, 1.0)

    def unfused(h, w):
        logits = h @ w
        col = jax.lax.broadcasted_iota(jnp.int32, (v_pad,), 0)
        logits = jnp.where(col < vocab, logits, -1e9)
        s, c = cross_entropy_sum(logits, tgt)
        return s, c, masked_accuracy(logits, tgt)

    def unfused_loss(h, w):
        s, c, _ = unfused(h, w)
        return s / jnp.maximum(c, 1.0)

    loss, (dh, dw) = compile_checked(
        jax.value_and_grad(fused_loss, argnums=(0, 1)), (h, w),
        ("head_ce_fwd", "head_ce_bwd"), compiled,
    )(h, w)
    s_eval, c_eval, correct = compile_checked(
        lambda h, w: fused_head_ce(h, w, tgt, vocab, with_accuracy=True),
        (h, w), ("head_ce_fwd",), compiled,
    )(h, w)
    with jax.default_matmul_precision("highest"):
        ref_loss, (ref_dh, ref_dw) = jax.jit(
            jax.value_and_grad(unfused_loss, argnums=(0, 1))
        )(*up(h, w))
        ref_s, ref_c, ref_acc = jax.jit(unfused)(*up(h, w))
    check(float(c_eval) == float(ref_c), (float(c_eval), float(ref_c)))
    ref_correct = float(ref_acc) * float(ref_c) / 100.0
    # argmax over bf16-product logits may flip on near-ties: a few rows
    check(abs(float(correct) - ref_correct) <= max(2.0, 0.002 * n), (
        float(correct), ref_correct,
    ))
    check((np.asarray(dw, np.float32)[:, vocab:] == 0).all(), "pad columns")
    checked[f"head_ce N={n} dim={dim} V={vocab}"] = {
        "loss": rel_err("head_ce loss", loss, ref_loss, 1e-3),
        "eval_loss_sum": rel_err("head_ce eval", s_eval, ref_s, 1e-3),
        "dh": rel_err("head_ce dh", dh, ref_dh, GRAD_TOL),
        "dw": rel_err("head_ce dw", dw, ref_dw, GRAD_TOL),
        "correct": [float(correct), ref_correct],
    }

    # -- grouped-expert FFN fwd + bwd --------------------------------------
    for rows, d, f, e in sz.moe_shapes:
        _, m = _plan_rows(rows)
        xs = jnp.asarray(rng.randn(m, d), bf16)
        wu = jnp.asarray(rng.randn(e, d, f) * 0.05, bf16)
        bu = jnp.asarray(rng.randn(e, f) * 0.05, bf16)
        wd = jnp.asarray(rng.randn(e, f, d) * 0.05, bf16)
        bd = jnp.asarray(rng.randn(e, d) * 0.05, bf16)
        # uneven segments, one of them empty when there are experts to spare
        cuts = np.sort(rng.randint(0, m, e - 1))
        if e > 2:
            cuts[1] = cuts[0]
        offs = np.concatenate([[0], cuts, [m]]).astype(np.int32)
        cot = jnp.asarray(rng.randn(m, d), bf16)

        def segment_ref(xs, wu, bu, wd, bd):
            outs = []
            for i in range(e):
                seg = xs[int(offs[i]):int(offs[i + 1])]
                hid = jnp.maximum(seg @ wu[i] + bu[i], 0.0)
                # the kernel (like the einsum dispatches) rounds the hidden
                # activations to the compute dtype; without the same
                # rounding point, outputs within that rounding of zero land
                # on the other side of the second relu and the comparison
                # measures relu flips, not the kernel
                hid = hid.astype(bf16).astype(f32)
                outs.append(jnp.maximum(hid @ wd[i] + bd[i], 0.0))
            return jnp.concatenate(outs, axis=0)

        def run(ffn, cot, *bank):
            def loss(*bank):
                y = ffn(*bank)
                return jnp.sum(y.astype(f32) * cot.astype(f32)), y

            (_, y), grads = jax.value_and_grad(
                loss, argnums=(0, 1, 2, 3, 4), has_aux=True
            )(*bank)
            return y, grads

        bank = (xs, wu, bu, wd, bd)
        offsets = jnp.asarray(offs)
        y, grads = compile_checked(
            functools.partial(run, lambda *b: grouped_ffn(*b, offsets)),
            (cot, *bank), ("moe_ffn_fwd", "moe_ffn_bwd"), compiled,
        )(cot, *bank)
        with jax.default_matmul_precision("highest"):
            ref_y, ref_grads = jax.jit(
                functools.partial(run, segment_ref)
            )(*up(cot, *bank))
        tag = f"grouped_ffn M={m} D={d} F={f} E={e}"
        checked[tag] = {
            "fwd": rel_err(tag, y, ref_y, FWD_TOL),
            **{
                n: rel_err(f"{tag} {n}", g, r, GRAD_TOL)
                for n, g, r in zip(("dx", "dwu", "dbu", "dwd", "dbd"), grads, ref_grads)
            },
        }

    # -- paged decode attention: bf16 and int8 pools, read in place in the
    # stacked [L, NP, H, P, D] pools at a non-zero layer ---------------------
    hds, p, d, mp, slots = sz.heads, sz.page, sz.head_dim, sz.pages_per_slot, sz.paged_slots
    num_pages, layers, li = slots * mp + 1, 2, 1
    bt = jnp.asarray(
        rng.permutation(np.arange(1, num_pages)).reshape(slots, mp), jnp.int32
    )
    # cursors: 0 (fresh token only), a partly filled page, a full window
    start = jnp.asarray(
        [0, p + 3, mp * p - 1] + list(rng.randint(1, mp * p - 1, slots - 3)),
        jnp.int32,
    )
    qv, kn, vn = (jnp.asarray(rng.randn(slots, hds, d), bf16) for _ in range(3))
    raw_k = jnp.asarray(rng.randn(layers, num_pages, hds, p * d) * 0.5, f32)
    raw_v = jnp.asarray(rng.randn(layers, num_pages, hds, p * d) * 0.5, f32)
    pools = {"bf16": (raw_k.astype(bf16), raw_v.astype(bf16), None, None)}
    if (p * d) % quant_comm.DEFAULT_BLOCK == 0:
        (k8, sk), (v8, sv) = quant_comm.quantize_blocks(raw_k), quant_comm.quantize_blocks(raw_v)
        pools["int8"] = (k8, v8, sk, sv)
    at_layer = lambda fn: lambda pk, pv, sk, sv, *rest: fn(pk, pv, sk, sv, li, *rest)  # noqa: E731
    for kind, (pk, pv, sk, sv) in pools.items():
        pk, pv = (x.reshape(layers, num_pages, hds, p, d) for x in (pk, pv))
        operands = (pk, pv, sk, sv, bt, start, qv, kn, vn)
        out = compile_checked(at_layer(paged_attend), operands, ("paged_attend",), compiled)(*operands)
        ref = jax.jit(at_layer(paged_attend_reference))(*operands)
        tag = f"paged_attend {kind} H={hds} P={p} D={d} MP={mp}"
        checked[tag] = {"out": rel_err(tag, out, ref, PAGED_TOL)}
        # cursor 0: the softmax over ONE position returns v_new exactly
        np.testing.assert_array_equal(
            np.asarray(out[0], np.float32), np.asarray(vn[0], np.float32)
        )
    return checked, None


# ---------------------------------------------------------------------------
# Phase 2: the training recipe
# ---------------------------------------------------------------------------


def model_flags(sz: Sizes) -> list[str]:
    return [
        "--dim", str(sz.dim), "--heads", str(sz.heads),
        "--head_dim", str(sz.head_dim), "--num_layers", str(sz.layers),
        "--sequence_length", str(sz.seq),
    ]


def in_dir(path: Path, fn, *args):
    """Call `fn` with `path` as the cwd: the recipes write `checkpoints/`
    relative to it."""
    cwd = os.getcwd()
    os.chdir(path)
    try:
        return fn(*args)
    finally:
        os.chdir(cwd)


def phase_train(sz: Sizes, out: Path, compiled: bool) -> tuple[dict, tuple]:
    import numpy as np

    from tpukit import native

    log = out / "train.jsonl"
    argv = model_flags(sz) + [
        "--batch_size", str(sz.batch), "--epochs", "1",
        "--dataset_slice", str(sz.train_rows), "--num_workers", "0",
        "--learning_rate", str(sz.learning_rate), "--metrics_log", str(log),
    ]
    result = in_dir(out, load_recipe("main-single.py").main, argv)

    records = [json.loads(line) for line in log.read_text().splitlines()]
    losses = [r["loss"] for r in records if r.get("kind") == "train"]
    check(len(losses) >= 2, f"need >= 2 loss windows, got {losses}")
    check(np.isfinite(losses).all(), losses)
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    eval_loss = result.metrics["eval"]["loss"]
    check(np.isfinite(eval_loss), eval_loss)
    ckpt = Path(result.checkpoint_path)
    if not ckpt.is_absolute():
        ckpt = out / ckpt
    check(ckpt.exists(), ckpt)
    xla = {r["fn"]: r for r in records if r.get("kind") == "xla"}
    kernels = xla["train_step"].get("kernels") or {}
    if compiled:
        # the flash kernel AND the fused head, in the step fit() ran
        check_kernels(kernels, ("flash_", "head_ce_"))
    checked = {
        "vocab": int(result.config.vocab_size),
        "native_tokenizer": bool(native.is_available()),
        "steps": int(result.state.step),
        "loss_windows": [round(x, 4) for x in losses],
        "eval_loss": round(float(eval_loss), 4),
        "checkpoint": str(ckpt.relative_to(ROOT)) if ckpt.is_relative_to(ROOT) else str(ckpt),
        "checkpoint_bytes": ckpt.stat().st_size,
        "train_step_kernels": kernels,
        "eval_step_kernels": xla.get("eval_step", {}).get("kernels"),
        "fit_tokens_per_sec_per_chip": result.metrics["tokens_per_sec_per_chip"],
    }
    return checked, (result.state.params, result.config, ckpt)


# ---------------------------------------------------------------------------
# Phase 3 (+ multichip): the step fit() jits, at the published vocab
# ---------------------------------------------------------------------------


def full_width_cfg(sz: Sizes):
    import jax.numpy as jnp

    from tpukit.model import GPTConfig

    return GPTConfig(
        dim=sz.dim, head_dim=sz.head_dim, heads=sz.heads,
        num_layers=sz.layers, vocab_size=sz.vocab,
        max_position_embeddings=sz.seq, compute_dtype=jnp.bfloat16,
    )


def make_batch(rng, vocab: int, batch: int, seq: int):
    """Seeded (model_batch, targets) in the trainer's input format."""
    import numpy as np

    ids = rng.randint(0, vocab, size=(batch, seq)).astype(np.int32)
    model_batch = {
        "input_ids": ids,
        "position_ids": np.ascontiguousarray(
            np.broadcast_to(np.arange(seq, dtype=np.int32), ids.shape)
        ),
        "mask": np.zeros_like(ids, dtype=bool),
    }
    return model_batch, np.roll(ids, -1, axis=1).astype(np.int32)


def step_trajectory(sz: Sizes, strategy, compiled: bool, inspect=None,
                    expect=("flash_", "head_ce_")) -> dict:
    """`sz.steps` train steps on one seeded batch through create_train_state
    + make_step_fns (the functions fit() jits). Returns the loss trajectory
    and what `inspect(state, compiled_step)` reports. `expect`: kernel-name
    prefixes the compiled step must hold."""
    import jax
    import numpy as np

    from tpukit.obs.xla import kernel_calls
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    cfg = full_width_cfg(sz)
    strategy.validate_config(cfg)
    optimizer = make_optimizer(sz.learning_rate)
    init_fn = lambda rng: create_train_state(rng, cfg, optimizer, strategy)  # noqa: E731
    shapes = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    train_step, _, state_sharding = make_step_fns(cfg, optimizer, strategy, shapes)
    state = jax.jit(init_fn, out_shardings=state_sharding)(jax.random.PRNGKey(0))
    # prepare_batch's shift: S - 1 tokens per row
    model_batch, targets = make_batch(
        np.random.RandomState(0), cfg.vocab_size, sz.batch, sz.seq - 1
    )
    exe = train_step.lower(state, model_batch, targets).compile()
    text = exe.as_text()
    report = {"kernels": kernel_calls(text)}
    if compiled:
        check_kernels(report["kernels"], expect)
    if inspect is not None:
        report.update(inspect(state, text))
    losses = []
    for _ in range(sz.steps):
        state, loss = exe(state, model_batch, targets)
        losses.append(float(jax.block_until_ready(loss)))
    check(np.isfinite(losses).all(), losses)
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    report["losses"] = [round(x, 5) for x in losses]
    del state
    gc.collect()
    return report


def phase_full_width(sz: Sizes, compiled: bool) -> tuple[dict, None]:
    import jax

    from tpukit.shardings import SingleDevice

    report = step_trajectory(sz, SingleDevice(), compiled)
    stats = jax.devices()[0].memory_stats() or {}
    report["vocab"] = sz.vocab
    report["peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
    report["bytes_limit"] = stats.get("bytes_limit")
    return report, None


# ---------------------------------------------------------------------------
# Phase 4: the serving recipe
# ---------------------------------------------------------------------------


def phase_serve(sz: Sizes, out: Path, params, cfg, ckpt: Path) -> tuple[dict, None]:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from tpukit.model import gpt

    serve = load_recipe("main-serve.py")
    base = model_flags(sz) + [
        "--checkpoint", str(ckpt), "--requests", str(sz.requests),
        "--slots", str(sz.slots), "--max_new_tokens", str(sz.max_new_tokens),
        "--buckets", sz.buckets,
    ]
    caches = {
        "ring": [],
        "paged": ["--page_size", str(sz.page)],
        "fused": ["--page_size", str(sz.page), "--fused_decode"],
    }
    streams, checked = {}, {}
    for name, extra in caches.items():
        argv = base + extra + ["--metrics_log", str(out / f"serve_{name}.jsonl")]
        completions = in_dir(out, serve.main, argv)
        check(len(completions) == sz.requests, (name, len(completions)))
        reasons = sorted({c.reason for c in completions})
        check(set(reasons) <= {"eos", "length"}, (name, reasons))
        streams[name] = {c.rid: np.asarray(c.ids) for c in completions}
        checked[name] = {
            "completed": len(completions),
            "generated": int(sum(c.generated for c in completions)),
            "reasons": reasons,
        }

    # Identical streams is the repo's claim (README "token-for-token"). Where
    # two paths part, the position is admitted only if the two chosen tokens
    # are a tie at bf16 resolution: their float32 logits over the common
    # prefix differ by no more than one bf16 ulp plus the error the bf16
    # forward itself makes on those two logits there.
    cfg32 = cfg.replace(compute_dtype=jnp.float32)
    eps_bf16 = float(jnp.finfo(jnp.bfloat16).eps)

    @jax.jit
    def last_logits(ids):
        pos = jnp.arange(ids.shape[1], dtype=jnp.int32)[None]
        mask = jnp.zeros(ids.shape, bool)
        served = gpt.forward(params, cfg, ids, pos, mask)[0, -1]
        with jax.default_matmul_precision("highest"):
            exact = gpt.forward(params, cfg32, ids, pos, mask)[0, -1]
        return exact, served.astype(jnp.float32)

    for other in ("paged", "fused"):
        ties = []
        for rid, ref_ids in streams["ring"].items():
            ids = streams[other][rid]
            n = min(len(ids), len(ref_ids))
            differ = np.nonzero(ids[:n] != ref_ids[:n])[0]
            if differ.size == 0:
                check(len(ids) == len(ref_ids), (other, rid, len(ids), len(ref_ids)))
                continue
            t = int(differ[0])
            pair = [int(ref_ids[t]), int(ids[t])]
            exact, served = (
                np.asarray(x)[pair] for x in last_logits(jnp.asarray(ref_ids[None, :t]))
            )
            gap = float(abs(exact[0] - exact[1]))
            room = float(
                eps_bf16 * np.max(np.abs(exact)) + 2 * np.max(np.abs(served - exact))
            )
            check(gap <= room, (
                f"{other} vs ring, request {rid}, position {t}: tokens {pair} "
                f"have float32 logits {exact.tolist()}, gap {gap:.3e} > bf16 "
                f"rounding there {room:.3e}"
            ))
            ties.append({"rid": int(rid), "pos": t, "gap": round(gap, 6),
                         "room": round(room, 6)})
        checked[f"{other}_vs_ring"] = {
            "identical_requests": sz.requests - len(ties),
            "bf16_tie_positions": len(ties),
            "ties": ties,
        }
    return checked, None


# ---------------------------------------------------------------------------
# --multichip: the sharded recipes' strategies on four chips
# ---------------------------------------------------------------------------


def phase_multichip(sz: Sizes, compiled: bool, devices=None) -> tuple[dict, None]:
    """`devices`: the four to use (default: all of jax.devices(), which must
    then be four)."""
    import jax
    import numpy as np

    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import collective_bytes
    from tpukit.pipeline import Pipeline1F1B
    from tpukit.shardings import FSDP, DataParallel, SingleDevice, TensorParallel

    devices = list(devices if devices is not None else jax.devices())
    check(len(devices) == 4, f"--multichip needs 4 devices, found {len(devices)}")
    mesh = lambda axes: create_mesh(axes, devices=devices)  # noqa: E731

    def inspector(expect_ops: tuple, sharded: bool):
        def inspect(state, hlo_text):
            ops = collective_bytes(hlo_text)
            for op in expect_ops:
                check(ops.get(op, {}).get("count", 0) > 0, (op, ops))
            per_device = {d.id: 0 for d in devices}
            total = 0
            for leaf in jax.tree_util.tree_leaves(state.params):
                total += leaf.nbytes
                for shard in leaf.addressable_shards:
                    per_device[shard.device.id] += shard.data.nbytes
            held = list(per_device.values())
            check(min(held) > 0, f"a device holds no parameters: {per_device}")
            if sharded:  # spread over the chips, not a full copy on any
                check(max(held) < total, (per_device, total))
            else:
                check(min(held) == total, (per_device, total))
            return {
                "collectives": {k: v["count"] for k, v in ops.items() if v["count"]},
                "param_bytes_total": total,
                "param_bytes_per_device": per_device,
            }

        return inspect

    single = step_trajectory(sz, SingleDevice(mesh(None)), compiled)
    emit({"phase": "multichip.single", **single})
    # strategy -> (constructor, inspector, kernels its step must hold).
    # TensorParallel's vocab-sharded head is the GSPMD matmul and the
    # pipeline's head runs unfused inside its last stage: flash only there.
    fused, flash_only = ("flash_", "head_ce_"), ("flash_",)
    strategies = {
        "ddp": (lambda: DataParallel(mesh({"data": 4})),
                inspector(("all-reduce",), sharded=False), fused),
        "fsdp": (lambda: FSDP(mesh({"data": 4})),
                 inspector(("all-gather",), sharded=True), fused),
        "tp": (lambda: TensorParallel(mesh({"data": 2, "model": 2})),
               inspector(("all-reduce",), sharded=True), flash_only),
        "pipe_1f1b": (lambda: Pipeline1F1B(mesh({"stage": 4}),
                                           num_microbatches=4),
                      inspector(("collective-permute",), sharded=True),
                      flash_only),
    }
    gaps = {}
    for name, (make, inspect, expect) in strategies.items():
        report = step_trajectory(sz, make(), compiled, inspect, expect)
        gaps[name] = round(float(np.max(np.abs(
            np.subtract(report["losses"], single["losses"])
        ))), 5)
        # one line per strategy as it finishes: a later failure loses nothing
        emit({"phase": f"multichip.{name}",
              "max_loss_gap_vs_single": gaps[name], **report})
    # judged after all four ran, so a failure still reports every trajectory
    off = {n: g for n, g in gaps.items() if g > TRAJECTORY_TOL}
    check(not off, (
        f"loss trajectories leave the single-device one by more than "
        f"{TRAJECTORY_TOL}: {off} of {gaps}"
    ))
    return {"single_losses": single["losses"],
            "max_loss_gap_vs_single": gaps, "tolerance": TRAJECTORY_TOL}, None


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--multichip", action="store_true",
                    help="run ONLY the four-chip sharded-strategy phase")
    args = ap.parse_args(argv)

    import jax

    first = jax.devices()[0]
    device = {"platform": first.platform, "kind": first.device_kind,
              "count": len(jax.devices())}
    ok = False
    try:
        if first.platform != "tpu":
            print(f"chip_smoke: needs a TPU, jax found {device}", file=sys.stderr)
            return 2

        from tpukit.cache import enable_compilation_cache
        from tpukit.obs import peak_flops_per_chip

        peak = peak_flops_per_chip()  # raises on a TPU kind it does not know
        if first.device_kind == "TPU v5 lite":
            check(peak == 197e12, peak)
        cache = enable_compilation_cache()
        shutil.rmtree(OUT, ignore_errors=True)
        OUT.mkdir(parents=True)
        emit({"phase": "start", "device": device, "peak_bf16_flops": peak,
              "jax": jax.__version__, "cache_dir": cache.cache_dir,
              "cache_entries": cache.stats()["entries"]})

        sz = Sizes()
        if args.multichip:
            run_phase("multichip", phase_multichip, sz, True)
        else:
            run_phase("kernels", phase_kernels, sz, True)
            params, cfg, ckpt = run_phase("train", phase_train, sz, OUT, True)
            gc.collect()  # phase 2's optimizer state goes; its params stay
            run_phase("full_width", phase_full_width, sz, True)
            run_phase("serve", phase_serve, sz, OUT, params, cfg, ckpt)
        ok = True
    finally:
        # printed on EVERY way out, success or not — an exception still
        # propagates (non-zero exit) after this line
        emit({"ok": ok, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())

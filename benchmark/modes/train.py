"""Training mode: the cell's configuration under its strategy, fed by the
loader and prefetcher `fit()` uses, through the executable of
`make_step_fns`. Everything about the cell is in its configuration and
traffic files; nothing here names one.

`fit()` itself cannot be driven at a 50,257-entry vocabulary offline (it
takes the vocabulary from the tokenizer), so the loop below is the
benchmark's, around the same functions, with its own spans.
"""

from __future__ import annotations

import itertools
import time
import numpy as np

from benchmark import common, flops, stats, traffic_gen


def _global_norm(tree):
    import jax
    import jax.numpy as jnp

    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in jax.tree_util.tree_leaves(tree)))


def check_against_reference(ctx, cfg, strategy, params, param_sharding, ids, mask):
    """Loss (and, where the traffic file says so, the gradient's global norm)
    of the strategy's own loss path on a seeded sample, against the plain
    float32 reference on the same sample. Returns (ok, report)."""
    import jax

    from tpukit.batching import prepare_batch

    config, traffic = ctx["config"], ctx["traffic"]
    rows = traffic["check_rows_per_chip"] * ctx["chips"]
    batch, targets = prepare_batch(
        {"input_ids": ids[:rows], "attention_mask": mask[:rows]}, traffic["pad_id"])
    batch_sh, repl = strategy.batch_sharding(), strategy.replicated()
    batch = jax.device_put(batch, batch_sh)
    targets = jax.device_put(targets, batch_sh)
    with_grad = traffic["check"] == "loss and gradient norm"
    ref = common.load_by_name("reference", config["reference"], ctx["root"])
    sizes = common.reference_sizes(config)

    def system(p, b, t):
        if not with_grad:
            return strategy.loss_fn(p, cfg, b, t)[0], 0.0
        loss, grads = strategy.value_and_grad(p, cfg, b, t)
        return loss, _global_norm(grads)

    def reference(p, b, t):  # walks the layers on the host: jits its own pieces
        if not with_grad:
            return ref.loss(p, b["input_ids"], t, **sizes), 0.0
        return ref.loss_and_grad_norm(p, b["input_ids"], t, **sizes)

    system = jax.jit(system, in_shardings=(param_sharding, batch_sh, batch_sh),
                     out_shardings=(repl, repl))
    got = [float(x) for x in system(params, batch, targets)]
    want = [float(x) for x in reference(params, batch, targets)]
    tol = config["tolerance"]
    err_loss = abs(got[0] - want[0]) / abs(want[0])
    err_norm = abs(got[1] - want[1]) / abs(want[1]) if with_grad else 0.0
    report = {
        "check": traffic["check"], "rows": rows, "loss": got[0], "reference_loss": want[0],
        "loss_rel_err": err_loss, "loss_rel_tol": tol["train_loss_rel"],
        "grad_norm": got[1], "reference_grad_norm": want[1],
        "grad_norm_rel_err": err_norm, "grad_norm_rel_tol": tol["train_grad_norm_rel"],
    }
    ok = (np.isfinite(got).all() and err_loss <= tol["train_loss_rel"]
          and err_norm <= tol["train_grad_norm_rel"])
    return bool(ok), report


def run(ctx) -> dict:
    import jax

    from tpukit import shardings
    from tpukit.batching import prepare_batch
    from tpukit.data import ArrayDataset
    from tpukit.loader import DataLoader
    from tpukit.mesh import create_mesh
    from tpukit.obs.xla import collective_bytes, kernel_calls
    from tpukit.prefetch import HostPrefetcher
    from tpukit.train import create_train_state, make_global_batch, make_optimizer, make_step_fns

    config, traffic, chips, seed = ctx["config"], ctx["traffic"], ctx["chips"], ctx["seed"]
    devices = ctx["devices"]
    tracing = ctx["trace_dir"] is not None
    phase = common.Phases(ctx["t_process_start"])
    cfg = common.gpt_config(config)
    mesh = create_mesh(traffic["mesh"], devices=devices)
    strategy = getattr(shardings, traffic["strategy"])(mesh)
    strategy.validate_config(cfg)
    optimizer = make_optimizer(traffic["learning_rate"])
    key = common.prng_key(seed)

    # state made on the device(s) in one jitted call, at its final sharding
    init_fn = lambda rng: create_train_state(rng, cfg, optimizer, strategy)  # noqa: E731
    with phase("state_init"):
        shapes = jax.eval_shape(init_fn, key)
        train_step, _, state_sharding = make_step_fns(cfg, optimizer, strategy, shapes)
        state = jax.block_until_ready(jax.jit(init_fn, out_shardings=state_sharding)(key))

    with phase("data"):
        ids, mask = traffic_gen.train_rows(traffic, cfg.vocab_size, seed)
    global_rows = traffic["rows_per_chip"] * chips
    loader = DataLoader(ArrayDataset(ids, mask), batch_size=global_rows, shuffle=True,
                        seed=seed % (2**31), drop_last=True)
    batch_sh = strategy.batch_sharding()
    host_batch = strategy.host_batch_fn(cfg)

    def host_pipeline(raw):  # what fit()'s prefetch thread runs
        b, t = prepare_batch(raw, traffic["pad_id"])
        if host_batch is not None:
            b, t = host_batch(b, t)
        real = int((t != -100).sum())
        b, t = make_global_batch(batch_sh, b, t, place=True)
        return b, t, real

    def epochs():
        for epoch in itertools.count():
            loader.set_epoch(epoch)
            yield from loader

    prefetch = HostPrefetcher(epochs(), process=host_pipeline, depth=traffic["prefetch_depth"])
    try:
        batch, targets, real = next(prefetch)
        with phase("step_compile_or_fetch"):
            exe = train_step.lower(state, batch, targets).compile()
        mem = exe.memory_analysis()
        temp, args = getattr(mem, "temp_size_in_bytes", None), getattr(mem, "argument_size_in_bytes", None)
        # what the step needs on a chip: memory_stats()'s peak counter leaves the program's temp out
        compiler_bytes = (temp + args + mem.output_size_in_bytes - mem.alias_size_in_bytes) if temp is not None else None
        program = dict(compiler_temp_bytes=temp, compiler_argument_bytes=args, compiler_total_bytes=compiler_bytes,
                       strategy=strategy.describe(), global_rows=global_rows)
        if tracing:  # printing and parsing the HLO text takes a second: only the traced run counts kernels and collectives
            with phase("step_inspect"):
                text = exe.as_text()
                program.update(kernels=kernel_calls(text),
                               collectives={k: v for k, v in collective_bytes(text).items() if v})
                del text
        ctx["info"]("program", **program)

        with phase("reference_check"):
            ref_ok, ref_report = check_against_reference(
                ctx, cfg, strategy, state.params, state_sharding.params, ids, mask)
        ctx["info"]("reference_check", ok=ref_ok, **ref_report)

        with phase("warmup_steps"):
            for _ in range(traffic["warmup_steps"]):
                state, loss = exe(state, batch, targets)
                batch, targets, real = next(prefetch)
            jax.block_until_ready(loss)
        ctx["info"]("setup", compiles=ctx["compiles"].count, compile_or_fetch_s=ctx["compiles"].seconds,
                    **phase.seconds)

        window = min(ctx["seconds"], traffic["trace_seconds"]) if tracing else ctx["seconds"]
        compiles_before = ctx["compiles"].count
        annotate = jax.profiler.TraceAnnotation
        losses, tokens, done_t, waits = [], [], [], []
        with common.profiler_trace(ctx["trace_dir"]) as trace:
            t_start = time.perf_counter()
            prev = None
            while True:
                if losses:  # the first batch of the window is already in hand
                    with annotate("bench:next_batch"):
                        t0 = time.perf_counter()
                        batch, targets, real = next(prefetch)
                        waits.append(time.perf_counter() - t0)
                with annotate("bench:dispatch"):
                    state, loss = exe(state, batch, targets)
                losses.append(loss)
                tokens.append(real)
                if prev is not None:
                    # one step stays queued behind the running one, so the
                    # device never waits for the host's clock reading
                    with annotate("bench:wait_device"):
                        prev.block_until_ready()
                    done_t.append(time.perf_counter())
                    if done_t[-1] - t_start >= window:
                        break
                prev = loss
            loss.block_until_ready()
            done_t.append(time.perf_counter())
        compiled_in_window = ctx["compiles"].count - compiles_before
    finally:
        prefetch.close()

    values = np.asarray(jax.device_get(losses), np.float64)
    rate, counted, elapsed = stats.whole_step_rate(done_t, tokens, t_start, window)
    quarter = max(len(values) // 4, 1)
    fell = bool(values[-quarter:].mean() < values[:quarter].mean())
    finite = np.isfinite(values)
    ctx["info"]("window", steps_dispatched=len(values), steps_counted=counted, elapsed_s=elapsed,
                window_s=window, step_ms_median=float(np.median(np.diff([t_start] + done_t)) * 1e3),
                loss_first_quarter=float(values[:quarter].mean()),
                loss_last_quarter=float(values[-quarter:].mean()), loss_fell=fell,
                compiled_in_window=compiled_in_window,
                input_wait_ms_median=float(np.median(waits) * 1e3) if waits else None)

    seq = traffic["row_tokens"] - 1
    return {
        "correct": bool(ref_ok and finite.all() and fell and compiled_in_window == 0),
        "attempted": int(len(values)),
        "failed": int((~finite).sum()),
        "end_to_end": {
            "train_tokens_per_s_chip": rate / chips,
            "setup_s": t_start - ctx["t_process_start"],
        },
        "record": {
            "mode": "train", "cfg": cfg, "chips": chips, "trace": trace,
            "tokens_per_s_chip": rate / chips,
            "flops_per_token": flops.cfg_train_flops_per_token(cfg, seq),
            "input_wait_s": waits, "steps_counted": counted, "steps_dispatched": len(values),
            "rows_per_chip": traffic["rows_per_chip"], "seq": seq, "compiler_bytes": compiler_bytes,
            "host_spans": ("bench:next_batch", "bench:dispatch", "bench:wait_device"),
        },
    }

"""What only the chip's compiler can say, asked from the CPU sandbox.

Interpret mode runs a Pallas kernel as plain XLA, so it cannot see what
Mosaic refuses: a block whose last two dims are neither (8, 128)-divisible
nor whole, a relayout it has no rule for, a dynamic one-row store into a
packed tile, more VMEM than a core has. Two of this repo's four kernel
families passed every interpret-mode test and were refused by the v5e
compiler. These cases compile each family for a DESCRIBED `v5e:2x2` device
(jax.experimental.topologies — nothing runs, nothing is attached) at
GPT-small head and lane widths and assert the kernel is in the module as a
`tpu_custom_call`. Token counts are small: Mosaic's compile time follows the
tile, and the whole file must stay under half a minute.

A compile that passes is a compile, never a chip run — `python chip_smoke.py`
is the chip run.

The rest of the file pins the rules that keep a run from succeeding without
the device: one helper decides "is this a TPU", an unknown TPU has no peak,
the compile cache is placed from outside, one worker process per chip.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from tpukit.obs.xla import collective_bytes, kernel_calls
from tpukit.ops import pallas_attention

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BF16, F32, I32, I8 = jnp.bfloat16, jnp.float32, jnp.int32, jnp.int8
HEADS, HEAD_DIM, DIM, VOCAB = 12, 64, 768, 50257  # GPT-small, GPT-2 vocab


@pytest.fixture(scope="module")
def v5e():
    """The four described devices of a v5e:2x2 host, with the persistent
    compile cache off: a TPU executable written to it from here cannot be
    read back without a chip, and the next compile would warn."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as exc:  # no libtpu in this environment
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {exc}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield list(topo.devices)
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


def _flash(shard=None, masked=False):
    from tpukit.ops.pallas_attention import flash_causal_attention

    def fn(q, k, v, *mask):
        def loss(q, k, v):
            out = flash_causal_attention(
                q, k, v, scale=HEAD_DIM**-0.5, shard=shard,
                pad_mask=mask[0] if masked else None,
            )
            return jnp.sum(out.astype(F32))

        return jax.value_and_grad(loss, argnums=(0, 1, 2))(q, k, v)

    return fn


def _head_ce(train, shard=None):
    from tpukit.ops.fused_head_ce import fused_head_ce

    def fn(h, w, t):
        if not train:
            return fused_head_ce(h, w, t, VOCAB, with_accuracy=True, shard=shard)
        loss = lambda h, w: fused_head_ce(h, w, t, VOCAB, shard=shard)[0]  # noqa: E731
        return jax.value_and_grad(loss, argnums=(0, 1))(h, w)

    return fn


def _grouped_ffn(xs, wu, bu, wd, bd, offsets):
    from tpukit.ops.moe_gemm import grouped_ffn

    loss = lambda *bank: jnp.sum(grouped_ffn(*bank, offsets).astype(F32))  # noqa: E731
    return jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4))(xs, wu, bu, wd, bd)


def _paged(pool_k, pool_v, scale_k, scale_v, *slot):
    """The paged decode read on the stacked pools, at a non-zero layer."""
    from tpukit.ops.paged_attention import paged_attend

    return paged_attend(pool_k, pool_v, scale_k, scale_v, 1, *slot)


def _cases():
    """name -> (fn(mesh), [(shape, dtype, spec)], kernels expected). `spec`
    is the operand's PartitionSpec on the 2 x 2 (data, model) mesh; the
    single-device cases use one device and `P()` throughout."""
    b, s, n = 2, 1024, 512
    v_pad = -(-VOCAB // 128) * 128
    qkv = ((b, HEADS, s, HEAD_DIM), BF16)
    head = [((n, DIM), BF16, P()), ((DIM, v_pad), BF16, P()), ((n,), I32, P())]
    m, d, f, e = 128, 256, 1024, 8  # an 8-expert bank, one small row block
    bank = [((m, d), BF16), ((e, d, f), BF16), ((e, f), BF16),
            ((e, f, d), BF16), ((e, d), BF16), ((e + 1,), I32)]
    pages, page, mp, slots = 129, 16, 16, 8
    nb = page * HEAD_DIM // 256
    pool = lambda dt: [((2, pages, HEADS, page, HEAD_DIM), dt, P())] * 2  # noqa: E731
    slot = [((slots, mp), I32, P()), ((slots,), I32, P())] + [
        ((slots, HEADS, HEAD_DIM), BF16, P())] * 3
    scales = [((2, pages, HEADS, nb), F32, P())] * 2
    sharded = P("data", "model", None, None)
    return {
        "flash_unmasked": (lambda mesh: _flash(), [(*qkv, P())] * 3,
                           ("flash_fwd", "flash_bwd")),
        "flash_masked": (lambda mesh: _flash(masked=True),
                         [(*qkv, P())] * 3 + [((b, s), jnp.bool_, P())],
                         ("flash_fwd", "flash_bwd")),
        # under a GSPMD jit the kernels run per shard through an explicit
        # shard_map: libtpu cannot compile custom_partitioning
        "flash_sharded_data_x_model": (
            lambda mesh: _flash(shard=(mesh, "data", "model")),
            [(*qkv, sharded)] * 3, ("flash_fwd", "flash_bwd")),
        "head_ce_train": (lambda mesh: _head_ce(True), head,
                          ("head_ce_fwd", "head_ce_bwd")),
        "head_ce_eval": (lambda mesh: _head_ce(False), head, ("head_ce_fwd",)),
        "head_ce_train_sharded_tokens": (
            lambda mesh: _head_ce(True, shard=(mesh, ("data", "model"))),
            [(head[0][0], BF16, P(("data", "model"), None)), head[1],
             (head[2][0], I32, P(("data", "model")))],
            ("head_ce_fwd", "head_ce_bwd")),
        "grouped_ffn": (lambda mesh: _grouped_ffn, [(*x, P()) for x in bank],
                        ("moe_ffn_fwd", "moe_ffn_bwd")),
        "paged_attend_bf16": (
            lambda mesh: lambda pk, pv, *rest: _paged(pk, pv, None, None, *rest),
            pool(BF16) + slot, ("paged_attend",)),
        "paged_attend_int8": (lambda mesh: _paged, pool(I8) + scales + slot,
                              ("paged_attend",)),
    }


@pytest.mark.parametrize("name", list(_cases()))
def test_v5e_compiles(v5e, monkeypatch, name):
    make_fn, operands, expect = _cases()[name]
    # steer the kernels to the chip's compiler: THE one helper, no option
    monkeypatch.setattr(pallas_attention, "on_tpu_backend", lambda: True)
    if any(spec != P() for _, _, spec in operands):
        mesh = Mesh(np.array(v5e).reshape(2, 2), ("data", "model"))
        place = lambda spec: NamedSharding(mesh, spec)  # noqa: E731
    else:
        mesh, one = None, SingleDeviceSharding(v5e[0])
        place = lambda spec: one  # noqa: E731
    args = [
        jax.ShapeDtypeStruct(shape, dtype, sharding=place(spec))
        for shape, dtype, spec in operands
    ]
    compiled = jax.jit(make_fn(mesh)).lower(*args).compile()
    kernels = kernel_calls(compiled.as_text())
    assert set(expect) <= set(kernels), kernels


def _small_cfg(**kw):
    from tpukit.model import gpt

    kw.setdefault("num_layers", 2)
    return gpt.GPTConfig(dim=DIM, heads=HEADS, head_dim=HEAD_DIM,
                         vocab_size=VOCAB, max_position_embeddings=1024,
                         compute_dtype=BF16, vocab_pad_multiple=128, **kw)


def _scoped_train_step(devices, num_layers=2):
    """The trainer's own step (GPT-small layers, unrolled, flash + fused
    head+CE) lowered for one described chip: every kernel sits under the
    `loss` scope and a model scope."""
    from tpukit import shardings
    from tpukit.mesh import create_mesh
    from tpukit.train import create_train_state, make_optimizer, make_step_fns

    cfg = _small_cfg(attention_impl="flash", num_layers=num_layers)
    strategy = shardings.SingleDevice(create_mesh(None, devices=devices[:1]))
    opt = make_optimizer(3e-4)
    shapes = jax.eval_shape(lambda: create_train_state(jax.random.PRNGKey(0), cfg, opt, strategy))
    step, _, state_sh = make_step_fns(cfg, opt, strategy, shapes)
    bsh = strategy.batch_sharding()
    arr = lambda dt: jax.ShapeDtypeStruct((2, 1023), dt, sharding=bsh)  # noqa: E731
    state = jax.tree.map(lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh), shapes, state_sh)
    batch = {"input_ids": arr(I32), "position_ids": arr(I32), "mask": arr(jnp.bool_)}
    return step.lower(state, batch, arr(I32))


def _paged_decode_quantum(devices, kv_dtype="bf16"):
    """The serve engine's paged decode quantum (four ticks) at GPT-small
    widths, lowered for one described chip. Returns the lowering, the
    pool's shape `(L, NP, H, P, D)` and the block tables' `(N, MP)`."""
    from tpukit.model import gpt
    from tpukit.serve import decode, paged

    cfg = _small_cfg()
    one = SingleDeviceSharding(devices[0])
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    n, page, per_slot = 8, 16, 16
    params = on(jax.eval_shape(lambda: gpt.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: paged.init_paged_cache(cfg, n * per_slot + 1, page, per_slot, n, kv_dtype)))
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    lowered = decode.decode_step.lower(
        params, cfg, sds((n, page * per_slot), I32), cache, sds((n,), I32), sds((n,), jnp.bool_),
        sds((n,), I32), sds((n, 2), jnp.uint32), 0, 0.0, 0, None, steps=4)
    return lowered, cache["k"].shape, cache["bt"].shape


def _scoped_decode_quantum(devices):
    """The decode quantum as the chip runs it: `paged_attend` sits under
    decode/attn."""
    return _paged_decode_quantum(devices)[0]


def _made(text):
    """`(op, result type)` of every instruction of a module text."""
    import re

    return [(m.group(2), m.group(1)) for m in re.finditer(
        r"= ([a-z0-9]+\[[\d,]*\])\S* ([a-z][a-z\-]*)\(", text)]


@pytest.mark.parametrize("kv_dtype,xla", [("bf16", "bf16"), ("f32", "f32"), ("int8", "s8")])
def test_v5e_paged_decode_quantum_reads_and_writes_the_stack_where_it_lies(v5e, monkeypatch, kv_dtype, xla):
    """What the chip's compiler makes of the DEFAULT paged decode quantum
    (the chip's selection: `on_tpu_backend` true). Float pools are READ in
    place: one `paged_attend` call a layer on the stack, no gather of the
    `[N, MP, H, P, D]` view, and the kernel's position-major view of the
    stack is a bitcast of the loop's carry, never a copy. Every pool is
    WRITTEN in place: each layer's K and V land in the stacked pool `[L, NP,
    H, P, D]` through ONE scatter whose operand is the stack itself (2 x L
    of them, on the loop's carry), no layer's pool is sliced out of the
    stack, the stack is never rebuilt with dynamic-update-slices, and the
    only whole-pool copies are the four at the loop's edges (in and out, K
    and V: ROADMAP S1 (c)). An int8 pool keeps the XLA read
    (`gpt.decode_read_in_kernel`): its view is gathered and no kernel runs."""
    monkeypatch.setattr(pallas_attention, "on_tpu_backend", lambda: True)
    lowered, pool, (n, mp) = _paged_decode_quantum(v5e, kv_dtype)
    text = lowered.compile().as_text()
    layers, _, heads, page, head_dim = pool
    dims = lambda shape: xla + "[" + ",".join(map(str, shape)) + "]"  # noqa: E731
    stack, layer_pools = dims(pool), {dims((1, *pool[1:])), dims(pool[1:])}
    view = dims((n, mp, heads, page, head_dim))
    made = _made(text)
    count = lambda op, shapes: sum(1 for o, s in made if o == op and s in shapes)  # noqa: E731
    assert count("scatter", {stack}) == 2 * layers
    assert count("scatter", layer_pools) == 0
    assert count("slice", layer_pools) == count("dynamic-slice", layer_pools) == 0
    assert count("dynamic-update-slice", {stack}) == count("concatenate", {stack}) == 0
    if kv_dtype == "int8":
        assert kernel_calls(text) == {} and count("gather", {view}) == 2 * layers
        return
    assert kernel_calls(text) == {"paged_attend": layers}
    assert count("gather", {view}) == 0 and not any(s == view for _, s in made)
    by_position = dims((layers, pool[1], page, heads, head_dim))
    assert count("bitcast", {by_position}) > 0
    assert count("transpose", {by_position}) == count("copy", {by_position}) == count("fusion", {by_position}) == 0
    assert count("copy", {stack}) <= 4


def test_v5e_latent_family_decode_quantum_and_prefill_chunk_compile_at_published_widths(v5e):
    """The latent family's serve programs at the dots3-note-prev
    configuration's real widths (8 slots of 2,048 tokens: the pools are
    small, the weights and every width are the cell's), for one described
    chip: the v5e compiler takes the sorted top-k, the per-query row gather
    from the page pool, the ring's wrapped page indices and the grouped
    matmuls over the held experts (XLA's ragged dot, a Mosaic kernel of the
    compiler's own), and both programs fit the chip."""
    import json

    from tpukit.model import latent
    from tpukit.serve import decode

    with open(os.path.join(REPO, "benchmark", "configs", "dots3-note-prev.json")) as f:
        cfg = latent.config_from_hf(json.load(f))
    one = SingleDeviceSharding(v5e[0])
    on = lambda tree: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one), tree)
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    n, page, per_slot, chunk = 8, 16, 128, 128
    kinds = latent.page_kinds(cfg, page, "bf16")
    pages = {k.table: n * k.pages_for(page * per_slot, page) + 1 for k in kinds}
    params = on(jax.eval_shape(lambda: latent.init_params(jax.random.PRNGKey(0), cfg)))
    cache = on(jax.eval_shape(lambda: latent.init_paged_cache(cfg, pages, page, per_slot, n, "bf16")))
    state = (sds((n, page * per_slot), I32), cache, sds((n,), I32), sds((n,), jnp.bool_), sds((n,), I32),
             sds((n, 2), jnp.uint32))
    programs = {
        "decode": decode.decode_step.lower(params, cfg, *state, 0, 0.0, 0, None, steps=2),
        "prefill": decode.prefill_chunk_paged.lower(
            params, cfg, *state, sds((2,), I32), sds((2, chunk), I32), sds((2,), I32), sds((2,), jnp.bool_),
            sds((2,), I32), sds((2,), I32), sds((2, 2), jnp.uint32)),
    }
    for name, lowered in programs.items():
        compiled = lowered.compile()
        text = compiled.as_text()
        assert "ragged-dot" in text, name  # the grouped matmul is the compiler's kernel, not a dense expansion
        assert " sort(" in text and "gather(" in text, name
        m = compiled.memory_analysis()
        total = m.temp_size_in_bytes + m.argument_size_in_bytes + m.output_size_in_bytes - m.alias_size_in_bytes
        assert m.argument_size_in_bytes > 6e9 and total < 15.75e9, (name, total)  # the weights are real, and it fits


@pytest.mark.parametrize("name,lower,expect,scope_of", [
    ("train_step", _scoped_train_step,
     {"flash_fwd": 2, "flash_bwd": 2, "head_ce_fwd": 1, "head_ce_bwd": 1},
     {"flash_fwd": "loss/attn", "flash_bwd": "loss/attn", "head_ce_fwd": "loss", "head_ce_bwd": "loss"}),
    ("decode_step", _scoped_decode_quantum, {"paged_attend": 2},
     {"paged_attend": "decode/attn"}),
])
def test_v5e_named_scopes_leave_kernel_names_alone(v5e, monkeypatch, name, lower, expect, scope_of):
    """The benchmark finds kernels in the trace and the HLO by instruction
    name. With the model's `jax.named_scope`s on, the kernels compiled for
    the described v5e keep their names and counts, and `instruction_scopes`
    places each call under the program's own names."""
    from tpukit.obs.xla import instruction_scopes

    monkeypatch.setattr(pallas_attention, "on_tpu_backend", lambda: True)
    text = lower(v5e).compile().as_text()
    assert kernel_calls(text) == expect
    scopes = instruction_scopes(text)
    for kernel, scope in scope_of.items():
        calls = {k: v for k, v in scopes.items() if kernel_calls(
            f'%{k} = custom_call_target="tpu_custom_call"') == {kernel: 1}}
        assert len(calls) == expect[kernel] and set(calls.values()) == {scope}, calls
    if name == "train_step":
        assert {"optimizer", "loss/embed", "loss/ln", "loss/ffn"} <= set(scopes.values())


def test_v5e_unrolled_step_lowers_each_flash_kernel_once(v5e, monkeypatch):
    """Set-up is held by a count, never by a wall time: a four-layer
    UNROLLED step traces each flash kernel body once and lowers each
    per-shard program into ONE private function that every layer calls
    (lowered anew per layer, the walked kernels doubled a 24-layer step's
    set-up), while the compiled module still holds one `flash_fwd` and one
    `flash_bwd` custom call a layer under their names."""
    import collections
    import re

    layers = 4
    monkeypatch.setattr(pallas_attention, "on_tpu_backend", lambda: True)
    traced = collections.Counter()
    for name in ("_fwd_kernel", "_bwd_kernel"):
        body = getattr(pallas_attention, name)

        def counted(*refs, _body=body, _name=name, **static):
            traced[_name] += 1
            return _body(*refs, **static)

        monkeypatch.setattr(pallas_attention, name, counted)
    for program in (pallas_attention._fwd4_impl, pallas_attention._bwd4_impl):
        program.clear_cache()  # an earlier test's trace of these shapes would read 0
    lowered = _scoped_train_step(v5e, num_layers=layers)
    assert traced == {"_fwd_kernel": 1, "_bwd_kernel": 1}
    text = lowered.as_text()
    for program in ("_fwd4_impl", "_bwd4_impl"):
        assert len(re.findall(rf"func\.func private @{program}\b", text)) == 1, program
        assert len(re.findall(rf"call @{program}\b", text)) == layers, program
    assert text.count("tpu_custom_call") == 4  # flash_fwd, flash_bwd, head_ce_fwd, head_ce_bwd: once each
    kernels = kernel_calls(lowered.compile().as_text())
    assert kernels == {"flash_fwd": layers, "flash_bwd": layers, "head_ce_fwd": 1, "head_ce_bwd": 1}


# ---------------------------------------------------------------------------
# Nothing hides the device
# ---------------------------------------------------------------------------


class _Device:
    def __init__(self, platform):
        self.platform = platform


def test_interpret_mode_is_cpu_only(monkeypatch):
    assert pallas_attention._interpret() is True  # the CPU test backend
    monkeypatch.setattr(jax, "devices", lambda: [_Device("tpu")])
    assert pallas_attention.on_tpu_backend()
    assert pallas_attention._interpret() is False
    assert pallas_attention.tpu_compiler_params("parallel") is not None
    monkeypatch.setattr(jax, "devices", lambda: [_Device("gpu")])
    with pytest.raises(RuntimeError, match="neither"):
        pallas_attention._interpret()


@pytest.mark.parametrize(
    "kind,peak",
    [("TPU v5 lite", 197e12), ("cpu", None), ("TPU v5 lite pod", ValueError),
     ("TPU v9", ValueError)],
)
def test_peak_flops_keys_on_exact_device_kind(kind, peak):
    from tpukit.obs import peak_flops_per_chip

    if peak is ValueError:
        with pytest.raises(ValueError, match="no peak FLOP/s on record"):
            peak_flops_per_chip(kind)
    else:
        assert peak_flops_per_chip(kind) == peak


def test_tpu_module_text_parses():
    """A TPU module prints tiled layouts; the HLO IR and the kernel census
    must read through them (both found nothing before)."""
    text = """\
ENTRY %main.1 (p: f32[512,512]) -> f32[512,512] {
  %p = f32[512,512]{1,0:T(8,128)} parameter(0)
  %jvp_flash_fwd_.3 = (bf16[24,1024,64]{2,1,0:T(8,128)(2,1)S(1)}, f32[24,1,1024]{2,1,0:T(1,128)}) custom-call(%p), custom_call_target="tpu_custom_call", backend_config={"x":1}
  %head_ce_bwd = f32[8,8]{1,0:T(8,128)} custom-call(%p), custom_call_target="tpu_custom_call"
  ROOT %all-reduce = f32[512,512]{1,0:T(8,128)} all-reduce(%p), channel_id=1, replica_groups=[1,4]<=[4], to_apply=%add
}
"""
    assert kernel_calls(text) == {"flash_fwd": 1, "head_ce_bwd": 1}
    assert collective_bytes(text) == {
        "all-reduce": {"count": 1, "bytes": 512 * 512 * 4}
    }


@pytest.fixture()
def cache_config():
    """Hand the suite back the cache configuration it came with."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_compilation_cache_dir
    yield
    if jax.config.jax_compilation_cache_dir != prev:
        jax.config.update("jax_compilation_cache_dir", prev)
        cc.reset_cache()


@pytest.mark.parametrize("env_dir", ["/somewhere/outside", None],
                         ids=["env_set", "env_unset"])
def test_compile_cache_is_placed_from_outside(
    monkeypatch, tmp_path, cache_config, env_dir
):
    from tpukit import cache

    assigned = []
    update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda name, val: (assigned.append(name), update(name, val)),
    )
    monkeypatch.chdir(tmp_path)  # the rule must not look at the cwd
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
        stats = cache.enable_compilation_cache()
        # jax read the variable itself: tpukit assigns no directory
        assert "jax_compilation_cache_dir" not in assigned
        assert stats.cache_dir == env_dir
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        stats = cache.enable_compilation_cache()
        assert stats.cache_dir == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == stats.cache_dir
    assert "jax_persistent_cache_min_compile_time_secs" in assigned
    assert not (tmp_path / ".jax_cache").exists()


def test_fleet_workers_are_bound_one_per_chip():
    from tpukit.serve import worker_chip_env

    envs = [worker_chip_env(i, 4, 4) for i in range(4)]
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    assert all(e["TPU_PROCESS_BOUNDS"] == "1,1,1" for e in envs)
    assert worker_chip_env(1, 2, 0) == {}  # no TPU on the host: nothing to bind
    with pytest.raises(ValueError, match="needs 2 chips, this host has 1"):
        worker_chip_env(0, 2, 1)

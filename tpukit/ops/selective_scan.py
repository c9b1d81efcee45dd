"""The selective state-space scan of a Mamba-1 mixer (Gu & Dao,
arXiv:2312.00752) with a float32 state: the causal depthwise convolution
before it, the scan over a prefill chunk (a Pallas TPU kernel,
`selective_scan`), and the one-token update of a decode tick.

Per channel `e` of the inner width E and state index `n` of N, with `A [N, E]`
negative, the recurrence is

    h_t = exp(delta_t A) * h_{t-1} + (delta_t * u_t) B_t      [N, E]
    y_t = sum_n h_t[n] C_t[n] + D * u_t                         [E]

`delta [.., E]` is already `softplus(...)`; `B`, `C [.., N]`. The state is
held `[N, E]` a lane: the TPU tiles an array's last two axes by (8, 128), so
E (5,120 at the published width) lies on the lanes and N (16) on the
sublanes, where `[E, N]` would pad N to 128.

A chunk's positions that are not `keep` (its padding, and a prompt's last
token, which the engine's first decode tick forwards again) take no part in
the state or the convolution's state: their outputs are computed, and the
state is left where the last kept token put it, whatever the padding.

The chunk's scan (`scan_chunk`) is one kernel call. Grid `(lanes, channel
blocks, token blocks)`: a step runs one lane's 1,024 channels through a
block of up to 256 tokens, the tokens in order, and the state stays in VMEM
from the chunk's first token to its last. `u` and `delta` come in, and `y`
goes out, as whole (8, 128) tiles of eight tokens; in VMEM a token's 1,024
channels become one `[8, 128]` tile, as does each state row `h[n]` (16 tiles,
the token loop's carry, beside the 16 of `A`), so every step of the
recurrence is whole-tile vector work. `B_t[n]`, `C_t[n]` and
`keep` are scalars read from SMEM. `reference_scan_chunk`, a `lax.scan`
over the tokens with the state in its carry, is the reference the tests
hold the kernel to; nothing else calls it. On the CPU the kernel runs in
Pallas interpret mode (`pallas_attention._interpret`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from tpukit.ops.pallas_attention import _interpret, tpu_compiler_params

SUB = 8  # sublanes of an f32 tile
CHANNEL_BLOCK = 1024  # channels a grid step: one f32 tile a token
TOKEN_BLOCK = 256  # at most this many tokens a grid step


@jax.named_scope("mamba_conv")
def conv_chunk(conv_state, x, w, bias, n_keep):
    """Causal depthwise convolution of a chunk: `x [B, T, E]` after the
    `K - 1` inputs before it, `conv_state [B, K - 1, E]`; `w [K, E]`, tap `K -
    1` on the current input. Returns `(out [B, T, E] float32, the state after
    the lane's first `n_keep [B]` inputs)`."""
    k, t = w.shape[0], x.shape[1]
    full = jnp.concatenate([conv_state.astype(x.dtype), x], axis=1)  # [B, K - 1 + T, E]
    wf = w.astype(jnp.float32)
    out = sum(full[:, i: i + t].astype(jnp.float32) * wf[i] for i in range(k)) + bias.astype(jnp.float32)
    take = n_keep[:, None] + jnp.arange(k - 1, dtype=n_keep.dtype)[None, :]  # the K - 1 inputs up to the last kept
    return out, jnp.take_along_axis(full, take[:, :, None], axis=1).astype(conv_state.dtype)


@jax.named_scope("mamba_conv")
def conv_step(conv_state, x, w, bias):
    """One token `x [B, E]` after `conv_state [B, K - 1, E]`: `(out [B, E]
    float32, the state with `x` in and the oldest input out)`."""
    full = jnp.concatenate([conv_state.astype(x.dtype), x[:, None]], axis=1)  # [B, K, E]
    out = jnp.sum(full.astype(jnp.float32) * w.astype(jnp.float32)[None], axis=1) + bias.astype(jnp.float32)
    return out, full[:, 1:].astype(conv_state.dtype)


def _update(h, u, delta, b, a):
    """One step of the recurrence for `[B, E]` inputs, `h [B, N, E]`."""
    return jnp.exp(delta[:, None, :] * a) * h + (delta * u)[:, None, :] * b[:, :, None]


def _read(h, c):
    """`sum_n h[n] C[n]`: elementwise in float32 (an einsum over N = 16 would
    be a matmul pass at the default precision)."""
    return jnp.sum(h * c[:, :, None], axis=1)


def _scan_kernel(s_ref, state_ref, u_ref, delta_ref, a_ref, y_ref, out_ref, h_ref):
    """One grid step `(lane, channel block, token block)` of `W` channels:
    `u_ref`, `delta_ref`, `y_ref [1, TB, W]`, `state_ref`, `out_ref [1, N,
    W]`, `a_ref [N, W]`; `s_ref` in SMEM holds the block's `B [TB, N]`, `C
    [TB, N]` and `keep [TB]` (`_scalars`). Inside, a token's `W` channels
    are one `[8, W / 8]` tile: `h_ref [N, 8, W / 8]` holds the state from one
    token block to the next."""
    n_state, tb, w = a_ref.shape[0], u_ref.shape[1], u_ref.shape[2]
    c_at, keep_at = tb * n_state, 2 * tb * n_state
    group = math.gcd(tb, SUB)  # tokens moved a trip: whole (8, 128) tiles of the blocks
    tile = lambda z: z.reshape(z.shape[:-1] + (SUB, w // SUB))  # noqa: E731

    @pl.when(pl.program_id(2) == 0)
    def _():
        h_ref[...] = tile(state_ref[0])

    a = tile(a_ref[...])

    def tick(t, u, dt, h):
        du, y, kept = dt * u, None, s_ref[keep_at + t] != 0
        out = []
        for n in range(n_state):
            new = jnp.exp(dt * a[n]) * h[n] + du * s_ref[t * n_state + n]
            y_n = new * s_ref[c_at + t * n_state + n]
            y = y_n if y is None else y + y_n
            out.append(jnp.where(kept, new, h[n]))
        return y, tuple(out)

    def trip(i, h):  # Mosaic unrolls a loop whole or not at all: `group` ticks a trip by hand
        rows = pl.ds(pl.multiple_of(i * group, group), group)
        u, dt, ys = tile(u_ref[0, rows]), tile(delta_ref[0, rows]), []
        for j in range(group):
            y, h = tick(i * group + j, u[j], dt[j], h)
            ys.append(y)
        y_ref[0, rows] = jnp.stack(ys).reshape(group, w)
        return h

    h = jax.lax.fori_loop(0, tb // group, trip, tuple(h_ref[n] for n in range(n_state)))
    for n in range(n_state):
        h_ref[n] = h[n]
    out_ref[0] = h_ref[...].reshape(n_state, w)


def _scalars(b, c, keep, steps: int):
    """`B`, `C [L, T, N]` and `keep [L, T]` as one float32 row a grid step's
    `(lane, token block)`, `[B, C, keep]`, each row padded to a multiple of
    1,024 (a one-dimensional SMEM block is whole tiles of the array's
    layout), flattened."""
    rows = lambda z: z.astype(jnp.float32).reshape(keep.shape[0] * steps, -1)  # noqa: E731
    packed = jnp.concatenate([rows(b), rows(c), rows(keep)], axis=1)
    width = -(-packed.shape[1] // 1024) * 1024
    return jnp.pad(packed, ((0, 0), (0, width - packed.shape[1]))).reshape(-1)


# The kernel's call is jitted at module level, so that a program whose Mamba
# layers run in several loops (jamba2-3b: three, between its two attention
# layers) traces it once and lowers it once, into one private function each
# loop calls. What a trace depends on besides the operands is a STATIC
# argument: the blocks and the kernel mode.


@functools.partial(jax.jit, static_argnames=("tb", "w", "interpret"))
def _selective_scan(scalars, state, u, delta, a, *, tb: int, w: int, interpret: bool):
    """The kernel over `state [L, N, E]`, `u`, `delta [L, T, E]`, `a [N, E]`
    float32 and `_scalars`' rows, in blocks of `tb` tokens and `w` channels.
    Returns `(y [L, T, E], state)`."""
    lanes, t, e = u.shape
    n, steps = a.shape[0], t // tb
    tokens = pl.BlockSpec((1, tb, w), lambda i, j, k: (i, k, j))
    held = pl.BlockSpec((1, n, w), lambda i, j, k: (i, 0, j))
    elems = lanes * t * n * e
    return pl.pallas_call(
        _scan_kernel,
        grid=(lanes, e // w, steps),
        in_specs=[pl.BlockSpec((scalars.size // (lanes * steps),), lambda i, j, k: (i * steps + k,),
                               memory_space=pltpu.SMEM),
                  held, tokens, tokens, pl.BlockSpec((n, w), lambda i, j, k: (0, j))],
        out_specs=[tokens, held],
        out_shape=[jax.ShapeDtypeStruct(u.shape, jnp.float32), jax.ShapeDtypeStruct(state.shape, jnp.float32)],
        scratch_shapes=[pltpu.VMEM((n, SUB, w // SUB), jnp.float32)],
        compiler_params=tpu_compiler_params("parallel", "parallel", "arbitrary"),
        cost_estimate=pl.CostEstimate(flops=7 * elems, transcendentals=elems,
                                      bytes_accessed=4 * (3 * u.size + 2 * state.size + scalars.size + a.size)),
        interpret=interpret,
        name="selective_scan",
    )(scalars, state, u, delta, a)


@jax.named_scope("ssm_scan")
def scan_chunk(state, u, delta, b, c, a, d, keep):
    """A prefill chunk: `state [B, N, E]` float32, `u`, `delta [B, T, E]`,
    `b`, `c [B, T, N]`, `a [N, E]`, `d [E]`, `keep [B, T]` (False where a
    position's step stays out of the state), E a multiple of 8. One
    `selective_scan` kernel call. Returns `(state after the chunk, y [B, T,
    E])`, all float32."""
    t, e = u.shape[1:]
    if e % SUB:
        raise ValueError(f"the scan's inner width {e} is not a multiple of {SUB}")
    tb = math.gcd(t, TOKEN_BLOCK)
    f32 = lambda z: z.astype(jnp.float32)  # noqa: E731
    y, state = _selective_scan(_scalars(b, c, keep, t // tb), f32(state), f32(u), f32(delta), f32(a), tb=tb,
                               w=CHANNEL_BLOCK if e % CHANNEL_BLOCK == 0 else e, interpret=_interpret())
    return state, y + d * u


def reference_scan_chunk(state, u, delta, b, c, a, d, keep):
    """`scan_chunk` as an XLA `lax.scan` over the chunk's tokens, the state
    in the carry: the reference the kernel's tests compare against."""

    def tick(h, xs):
        u_t, dt_t, b_t, c_t, keep_t = xs
        new = _update(h, u_t, dt_t, b_t, a)
        return jnp.where(keep_t[:, None, None], new, h), _read(new, c_t)

    by_time = lambda z: jnp.moveaxis(z, 1, 0)  # noqa: E731
    state, ys = jax.lax.scan(tick, state, (by_time(u), by_time(delta), by_time(b), by_time(c), by_time(keep)))
    return state, jnp.moveaxis(ys, 0, 1) + d * u


@jax.named_scope("ssm_step")
def step(state, u, delta, b, c, a, d):
    """A decode tick: `state [B, N, E]`, `u`, `delta [B, E]`, `b`, `c [B,
    N]`. Returns `(state, y [B, E])`, float32."""
    state = _update(state, u, delta, b, a)
    return state, _read(state, c) + d * u


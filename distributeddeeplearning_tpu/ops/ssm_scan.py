"""A state-space layer's recurrence over one chunk of rows, as a Pallas kernel.

The selective scan of a Mamba layer carries a state ``s`` of ``[d_state,
d_inner]`` through the rows of a sequence::

    s_t = exp(dt_t * A) * s_{t-1} + (dt_t * x_t) B_t      (outer product)
    y_t = sum_n s_t[n] C_t[n] + D * x_t

with ``dt``, ``x`` [T, d_inner], ``B``, ``C`` [T, d_state], ``A`` [d_state,
d_inner] and ``D`` [d_inner].  Everything is laid out state-major: the
``d_inner`` channels are the lanes, the ``d_state`` states the sublanes, so the
state of a block of channels is a few vector registers and the contraction
with ``C_t`` is a reduction over sublanes, with no matrix unit.

:func:`ssm_scan_chunk` runs it as one ``pallas_call`` whose grid walks blocks
of channels (``"parallel"``: each block's recurrence is its own); the rows are
walked inside the kernel, eight at a time, with the state held in registers
from the first row to the last, so each operand is read from HBM once and the
``[T, d_state, d_inner]`` products never exist.  A row whose ``dt`` is 0 leaves
the state as it was (``exp(0) = 1`` and it adds nothing): the caller marks the
rows that pad a chunk so, and the wrapper pads the rows to a multiple of eight
the same way.  :func:`ssm_scan_reference` is the same recurrence as a plain
``lax.scan`` over the rows: the kernel's pin.

:func:`ssm_decode_step` takes one row of every lane of a batch (a decode
step) from the states ``[B, d_state, d_inner]``, and reads and writes the
state of the live lanes only: its grid walks the lanes in the order
:func:`live_lanes` gives (the live ones first, as scalar prefetch), a step
past the last live lane maps its state block to that lane's, so it fetches
nothing and writes nothing back, and the state is updated in place, so a
lane the kernel never visits keeps its bytes.  :func:`ssm_step_reference` is
the same step in XLA over every lane: its pin.

Off the TPU both kernels run in interpret mode, whenever ``ops.flash_decode``
does (its ``_use_interpret`` decides for all).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from distributeddeeplearning_tpu.ops import flash_decode as _fd

#: channels a grid step takes (the lanes of the state it keeps in registers)
BLOCK = 512
#: rows a step of the kernel's loop takes (a sublane tile of float32)
ROWS = 8


def _kernel(dt_ref, x_ref, b_ref, c_ref, a_ref, d_ref, s0_ref, y_ref, s_ref):
    """One block of channels over every row: ``dt_ref``, ``x_ref``, ``y_ref``
    [T, block]; ``b_ref``, ``c_ref`` [T, N]; ``a_ref``, ``s0_ref``, ``s_ref``
    [N, block]; ``d_ref`` [1, block]."""
    n = a_ref.shape[0]
    a = a_ref[...]
    d = d_ref[...]
    # row i of B (lanes) to a column (sublanes): the one-hot rows of the
    # identity pick it out with a reduction over lanes
    eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
           == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)).astype(jnp.float32)
    row_of = jax.lax.broadcasted_iota(jnp.int32, (ROWS, 1), 0)

    def rows(g, s):
        t = pl.multiple_of(g * ROWS, ROWS)
        dt = dt_ref[pl.ds(t, ROWS), :]
        x = x_ref[pl.ds(t, ROWS), :]
        b = b_ref[pl.ds(t, ROWS), :]
        c = c_ref[pl.ds(t, ROWS), :]
        u = dt * x
        y = d * x
        for i in range(ROWS):
            b_col = jnp.sum(eye * b[i:i + 1, :], axis=1, keepdims=True)
            c_col = jnp.sum(eye * c[i:i + 1, :], axis=1, keepdims=True)
            s = jnp.exp(dt[i:i + 1, :] * a) * s + u[i:i + 1, :] * b_col
            y = y + jnp.where(row_of == i,
                              jnp.sum(s * c_col, axis=0, keepdims=True), 0.0)
        y_ref[pl.ds(t, ROWS), :] = y
        return s

    s_ref[...] = jax.lax.fori_loop(0, dt_ref.shape[0] // ROWS, rows,
                                   s0_ref[...])


def ssm_scan_chunk(dt, x, b, c, a, d, s0, *, block: int = BLOCK):
    """The recurrence over ``T`` rows from the state ``s0`` [N, d_inner]:
    ``dt``, ``x`` [T, d_inner], ``b``, ``c`` [T, N], ``a`` [N, d_inner],
    ``d`` [d_inner], all float32.  Returns ``(y [T, d_inner], s_T [N,
    d_inner])``.  The kernel is named ``ssm_scan_chunk_<dtype>`` in a
    trace."""
    T, di = dt.shape
    n = a.shape[0]
    f32 = jnp.float32
    dt, x, b, c, a, s0 = (v.astype(f32) for v in (dt, x, b, c, a, s0))
    pad = -T % ROWS
    if pad:
        # rows of dt = 0 leave the state as it was
        dt, x, b, c = (jnp.pad(v, ((0, pad), (0, 0))) for v in (dt, x, b, c))
    rows = T + pad
    block = block if di % block == 0 else di
    interpret = _fd._use_interpret()
    by_channel = pl.BlockSpec((rows, block), lambda j: (0, j))
    by_row = pl.BlockSpec((rows, n), lambda j: (0, 0))
    state = pl.BlockSpec((n, block), lambda j: (0, j))
    y, s = pl.pallas_call(
        _kernel,
        grid=(di // block,),
        in_specs=[by_channel, by_channel, by_row, by_row, state,
                  pl.BlockSpec((1, block), lambda j: (0, j)), state],
        out_specs=[by_channel, state],
        out_shape=[jax.ShapeDtypeStruct((rows, di), f32),
                   jax.ShapeDtypeStruct((n, di), f32)],
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
        interpret=interpret,
        name=f"ssm_scan_chunk_{jnp.dtype(f32).name}",
    )(dt, x, b, c, a, d.astype(f32).reshape(1, di), s0)
    return y[:T], s


@jax.jit
def ssm_scan_reference(dt, x, b, c, a, d, s0):
    """The same recurrence as a plain ``lax.scan`` over the rows, in float32
    (the kernel's pin)."""
    f32 = jnp.float32

    def step(s, row):
        dt_t, x_t, b_t, c_t = row
        s = jnp.exp(dt_t[None, :] * a) * s + (dt_t * x_t)[None, :] * b_t[:, None]
        return s, (s * c_t[:, None]).sum(0) + d * x_t

    a, d = a.astype(f32), d.astype(f32)
    s, y = jax.lax.scan(step, s0.astype(f32),
                        tuple(v.astype(f32) for v in (dt, x, b, c)))
    return y, s


def live_lanes(live):
    """``(order [B], count [1])`` int32 of a ``live`` [B] bool: the live
    lanes first, each group in lane order, and how many are live."""
    order = jnp.argsort(~live, stable=True).astype(jnp.int32)
    return order, live.sum(dtype=jnp.int32).reshape(1)


def _step_kernel(order_ref, count_ref, dt_ref, x_ref, b_ref, c_ref, a_ref,
                 d_ref, s_ref, y_ref, s_out_ref):
    """Grid step ``i`` takes lane ``order[i]`` while ``i < count``:
    ``dt_ref``, ``x_ref``, ``y_ref`` [B, d_inner] and ``b_ref``, ``c_ref``
    [B, N] whole; ``s_ref``, ``s_out_ref`` the lane's [N, d_inner]; ``a_ref``
    [N, d_inner]; ``d_ref`` [1, d_inner]."""
    i, count = pl.program_id(0), count_ref[0]

    @pl.when(i == 0)
    def _():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when((i == 0) & (count == 0))
    def _():
        # no live lane: the block goes back as it came
        s_out_ref[...] = s_ref[...]

    @pl.when(i < count)
    def _():
        lane = order_ref[i]
        n = a_ref.shape[0]
        eye = (jax.lax.broadcasted_iota(jnp.int32, (n, n), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)
               ).astype(jnp.float32)
        dt = dt_ref[pl.ds(lane, 1), :]
        x = x_ref[pl.ds(lane, 1), :]
        b_col = jnp.sum(eye * b_ref[pl.ds(lane, 1), :], axis=1, keepdims=True)
        c_col = jnp.sum(eye * c_ref[pl.ds(lane, 1), :], axis=1, keepdims=True)
        s = jnp.exp(dt * a_ref[...]) * s_ref[...] + (dt * x) * b_col
        s_out_ref[...] = s
        y_ref[pl.ds(lane, 1), :] = (jnp.sum(s * c_col, axis=0, keepdims=True)
                                    + d_ref[...] * x)


def ssm_decode_step(dt, x, b, c, a, d, s, lanes):
    """One step of the recurrence for every live lane: ``dt``, ``x`` [B,
    d_inner], ``b``, ``c`` [B, N], ``a`` [N, d_inner], ``d`` [d_inner],
    ``s`` [B, N, d_inner], all float32, and ``lanes`` = :func:`live_lanes`.
    Returns ``(y [B, d_inner], s')``: a lane that is not live has a ``y`` of
    0 and its state as it was, to the bit.  ``s`` is updated in place (its
    buffer is the result's where the caller donates it).  The kernel is
    named ``ssm_decode_step_<dtype>`` in a trace."""
    B, di = dt.shape
    n = a.shape[0]
    f32 = jnp.float32
    order, count = lanes

    def lane_block(i, order, count):
        # a step past the last live lane stays on that lane's block
        return (order[jnp.minimum(i, jnp.maximum(count[0] - 1, 0))], 0, 0)

    whole = lambda shape: pl.BlockSpec(shape, lambda i, order, count: (0, 0))
    state = pl.BlockSpec((None, n, di), lane_block)
    interpret = _fd._use_interpret()
    y, s = pl.pallas_call(
        _step_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[whole((B, di)), whole((B, di)), whole((B, n)),
                      whole((B, n)), whole((n, di)), whole((1, di)), state],
            out_specs=[whole((B, di)), state],
        ),
        out_shape=[jax.ShapeDtypeStruct((B, di), f32),
                   jax.ShapeDtypeStruct((B, n, di), f32)],
        # operand 8 (after the two prefetched scalars) is the state
        input_output_aliases={8: 1},
        compiler_params=None if interpret else pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name=f"ssm_decode_step_{jnp.dtype(f32).name}",
    )(order, count, *(v.astype(f32) for v in (dt, x, b, c, a)),
      d.astype(f32).reshape(1, di), s.astype(f32))
    return y, s


@jax.jit
def ssm_step_reference(dt, x, b, c, a, d, s, live):
    """The same step in XLA over every lane (the kernel's pin): each lane's
    state [B, N, d_inner] moves on by one, and a lane that is not ``live``
    keeps its own; ``y`` is every lane's."""
    new = (jnp.exp(dt[:, None, :] * a) * s
           + (dt * x)[:, None, :] * b[:, :, None])
    return ((new * c[:, :, None]).sum(1) + d * x,
            jnp.where(live[:, None, None], new, s))

"""State-space (Mamba-2 / SSD) primitives for serving: a recurrent
state a SLOT, advanced a token at a time by a Pallas kernel and a window
at a time by the chunked scan.

One head of a layer keeps ``S`` (P x N, float32). With ``dt`` the
token's step (after its softplus), ``A < 0`` the head's decay, ``x``
(P,) its input and ``B``, ``C`` (N,) its group's projections:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t + D x_t

- :func:`ssm_state_step` — ONE token a row, the states kept in a table
  indexed by slot: the Pallas kernel ``ssm_state_step`` reads a row's
  state tile where it lies, applies the recurrence and writes it back IN
  PLACE (``input_output_aliases``); a row with nothing to advance is
  pointed at the table's last row, the scratch row, so no slot's state
  is touched for it. It is where a decode step reads and writes every
  live slot's state, so it has the roofline. Off the TPU the same
  update through XLA (``interpret=True``: the kernel in the interpreter,
  for the tests).
- :func:`ssd_chunk_scan` — a window of L tokens a row by the chunked
  (SSD) form in plain XLA einsums: inside a row the quadratic masked
  form, between rows the state recurrence — a row may CONTINUE the row
  before it (the engine deals one prompt's consecutive chunks to the
  rows of one prefill call) or start from a state handed in. A token
  whose ``dt`` is 0 advances nothing: that is how padding is told. A
  kernel for it is a later PR's (PERF.md section 7).
- :func:`causal_conv` — the depthwise causal convolution in front of the
  recurrence, over a window or a single token, with the ``K - 1`` inputs
  before the window handed in and those after its last REAL token handed
  back.
"""

from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from rafiki_tpu.ops.attention import _resolve_interpret
from rafiki_tpu.ops.common import use_xla_fallback

HIGHEST = jax.lax.Precision.HIGHEST
#: bytes of one state tile (a block of heads x P x N float32) of the step
#: kernel; the pipeline holds two going in and two coming out
STATE_TILE_BYTES = 4 * 1024 * 1024
VMEM_LIMIT_BYTES = 48 * 1024 * 1024


def heads_per_block(h: int, p: int, n: int, per_group: int) -> int:
    """Heads of one grid step of the step kernel: the most whole groups
    whose states fit ``STATE_TILE_BYTES``, dividing ``h``."""
    best = per_group
    for hb in range(per_group, h + 1, per_group):
        if h % hb == 0 and hb * p * n * 4 <= STATE_TILE_BYTES:
            best = hb
    return best


def _step_kernel(slot_ref, fresh_ref, da_ref, dtx_ref, b_ref, c_ref, s_ref,
                 y_ref, s_out_ref, *, hb: int, per_group: int):
    from jax.experimental import pallas as pl

    del slot_ref  # the index maps read it
    fresh = fresh_ref[pl.program_id(0)] > 0
    da, dtx = da_ref[...], dtx_ref[...]          # (1, hb), (P, hb)
    b_all, c_all = b_ref[...], c_ref[...]        # (groups, N) each
    lane = jax.lax.broadcasted_iota(jnp.int32, dtx.shape, 1)
    y = jnp.zeros_like(dtx)
    for i in range(hb):  # static: a head's column is a lane of the tiles
        g = i // per_group
        s = jnp.where(fresh, 0.0, s_ref[i])      # (P, N)
        s = s * da[:, i:i + 1] + dtx[:, i:i + 1] * b_all[g:g + 1, :]
        s_out_ref[i] = s
        col = jnp.sum(s * c_all[g:g + 1, :], axis=1, keepdims=True)
        y = jnp.where(lane == i, col, y)
    y_ref[...] = y


def ssm_state_step(state: jnp.ndarray, slots: jnp.ndarray,
                   advance: jnp.ndarray, fresh: jnp.ndarray,
                   x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                   interpret: Optional[bool] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token a row through the recurrence, in place.

    - ``state``: (slots + 1, H, P, N) float32, the last row scratch.
    - ``slots``: (R,) int32, the slot of each row; ``advance`` (R,) bool,
      the rows whose state moves (the others read and write the scratch
      row); ``fresh`` (R,) bool, rows that start from a zero state
      whatever their slot held.
    - ``x`` (R, H, P), ``dt`` (R, H) after its softplus, ``a`` (H,)
      negative, ``b`` / ``c`` (R, G, N) (head ``h`` uses group ``h //
      (H / G)``), ``d`` (H,).

    Returns ``(y, state)``: ``y`` (R, H, P) float32.
    """
    n_rows, h, p = x.shape
    g, n = b.shape[1:]
    per_group = h // g
    scratch = state.shape[0] - 1
    slots = jnp.where(advance, slots, scratch).astype(jnp.int32)
    x, dt = x.astype(jnp.float32), dt.astype(jnp.float32)
    b, c = b.astype(jnp.float32), c.astype(jnp.float32)
    da = jnp.exp(dt * a)                                     # (R, H)
    dtx = dt[..., None] * x                                  # (R, H, P)
    skip = d.astype(jnp.float32)[:, None] * x
    if use_xla_fallback(interpret):
        s = jnp.where(fresh[:, None, None, None], 0.0, state[slots])
        bh, ch = (jnp.repeat(v, per_group, axis=1) for v in (b, c))
        s = s * da[..., None, None] + dtx[..., None] * bh[:, :, None, :]
        y = jnp.einsum("rhpn,rhn->rhp", s, ch, precision=HIGHEST)
        return y + skip, state.at[slots].set(s)

    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    hb = heads_per_block(h, p, n, per_group)
    blocks, gb = h // hb, hb // per_group

    def row_map(r, j, *_):  # operands laid out (R, blocks, ..., hb)
        return (r, j, 0, 0)

    def state_map(r, j, slot, fresh):
        return (slot[r], j, 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_rows, blocks),
        in_specs=[
            pl.BlockSpec((None, None, 1, hb), row_map),
            pl.BlockSpec((None, None, p, hb), row_map),
            pl.BlockSpec((None, None, gb, n), row_map),
            pl.BlockSpec((None, None, gb, n), row_map),
            pl.BlockSpec((None, hb, p, n), state_map)],
        out_specs=[
            pl.BlockSpec((None, None, p, hb), row_map),
            pl.BlockSpec((None, hb, p, n), state_map)],
    )
    y, state = pl.pallas_call(
        functools.partial(_step_kernel, hb=hb, per_group=per_group),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((n_rows, blocks, p, hb),
                                        jnp.float32),
                   jax.ShapeDtypeStruct(state.shape, state.dtype)],
        # operand 6 (after the two prefetched vectors) is the state
        input_output_aliases={6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=_resolve_interpret(interpret),
        name="ssm_state_step",  # what a profile calls the kernel
    )(slots, fresh.astype(jnp.int32),
      da.reshape(n_rows, blocks, 1, hb),
      dtx.reshape(n_rows, blocks, hb, p).transpose(0, 1, 3, 2),
      b.reshape(n_rows, blocks, gb, n), c.reshape(n_rows, blocks, gb, n),
      state)
    y = y.transpose(0, 1, 3, 2).reshape(n_rows, h, p)
    return y + skip, state


def ssd_chunk_scan(x: jnp.ndarray, dt: jnp.ndarray, a: jnp.ndarray,
                   b: jnp.ndarray, c: jnp.ndarray, d: jnp.ndarray,
                   init: jnp.ndarray, chained: jnp.ndarray
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Windows of L tokens through the recurrence, a row = one chunk.

    - ``x`` (R, L, H, P), ``dt`` (R, L, H) after its softplus and 0 at a
      token that advances nothing, ``a`` (H,) negative, ``b`` / ``c``
      (R, L, G, N), ``d`` (H,).
    - ``init`` (R, H, P, N) float32: the state row ``r`` starts from —
      unless ``chained[r]`` (R,) bool, which starts it from the state
      row ``r - 1`` ended with (``chained[0]`` is not read).

    Returns ``(y, final)``: ``y`` (R, L, H, P) float32 and each row's
    state after its last token, (R, H, P, N) float32. Float32 throughout
    with every product at ``highest``: the state is what carries a long
    sequence, and one rounding of it to bfloat16 stays for good.
    """
    n_rows, length, h, p = x.shape
    g, n = b.shape[2:]
    k = h // g
    f32 = jnp.float32
    dt = dt.astype(f32)
    xg = x.astype(f32).reshape(n_rows, length, g, k, p)
    b, c = b.astype(f32), c.astype(f32)
    cum = jnp.cumsum(dt * a, axis=1)                         # (R, L, H)
    # inside a row: y_t = sum_{s <= t} exp(cum_t - cum_s) (C_t.B_s) dt_s x_s
    seen = jnp.tril(jnp.ones((length, length), bool))
    seg = cum[:, :, None, :] - cum[:, None, :, :]            # (R, t, s, H)
    decay = jnp.exp(jnp.where(seen[None, :, :, None], seg, -jnp.inf))
    scores = jnp.einsum("rtgn,rsgn->rtsg", c, b, precision=HIGHEST)
    m = (decay * dt[:, None, :, :]).reshape(
        n_rows, length, length, g, k) * scores[..., None]
    y = jnp.einsum("rtsgk,rsgkp->rtgkp", m, xg, precision=HIGHEST)
    # what a row adds to the state, and what it leaves of the state before
    w = (jnp.exp(cum[:, -1:, :] - cum) * dt).reshape(n_rows, length, g, k)
    added = jnp.einsum("rlgk,rlgkp,rlgn->rgkpn", w, xg, b,
                       precision=HIGHEST).reshape(n_rows, h, p, n)
    kept = jnp.exp(cum[:, -1, :])                            # (R, H)

    def row(prev, xs):
        chain, start, keep, add = xs
        start = jnp.where(chain, prev, start)
        end = keep[:, None, None] * start + add
        return end, (start, end)

    _, (start, final) = jax.lax.scan(
        row, jnp.zeros_like(init[0]),
        (chained.at[0].set(False), init.astype(f32), kept, added))
    # the state a row started from, seen by each of its tokens
    carried = jnp.einsum(
        "rlgn,rgkpn->rlgkp", c, start.reshape(n_rows, g, k, p, n),
        precision=HIGHEST) * jnp.exp(cum).reshape(
            n_rows, length, g, k)[..., None]
    y = (y + carried).reshape(n_rows, length, h, p)
    return y + d.astype(f32)[:, None] * x.astype(f32), final


def causal_conv(x: jnp.ndarray, tail: jnp.ndarray, w: jnp.ndarray,
                bias: jnp.ndarray, n_real: jnp.ndarray
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Depthwise causal convolution of width K over a window (L tokens a
    row) or a single token (L = 1). ``x`` (R, L, C); ``tail`` (R, K - 1,
    C), the inputs before the window; ``w`` (K, C), ``w[K - 1]`` on the
    token itself; ``bias`` (C,); ``n_real`` (R,) int, the row's real
    tokens (the rest is padding). Returns ``(out, tail)``: ``out`` (R, L,
    C) float32 (what it feeds is a float32 recurrence: the caller rounds
    it, or does not), and the K - 1 inputs up to the row's last REAL
    token — the tail handed in where it has none."""
    width, length = w.shape[0], x.shape[1]
    full = jnp.concatenate([tail.astype(x.dtype), x], axis=1)
    w32 = w.astype(jnp.float32)
    out = sum(full[:, j:j + length].astype(jnp.float32) * w32[j]
              for j in range(width)) + bias.astype(jnp.float32)
    new_tail = jax.vmap(lambda f, n: jax.lax.dynamic_slice_in_dim(
        f, n, width - 1, axis=0))(full, n_real.astype(jnp.int32))
    return out, new_tail.astype(tail.dtype)

"""Pallas kernel: batched integer column transforms for the typed
parameter-column codecs (DESIGN.md §12).

One launch takes a batch of integer columns — padded into a ``(R, C)``
int32 grid with per-row lengths — and produces, per row, the typed
codec's transform in one branch-free pass:

- ``NUMERIC``      (frame-of-reference): ``v - ref`` (``ref`` = row min,
  host-provided — the encoder needs it for the descriptor anyway);
- ``MONOTONE_INT`` (delta): ``t[0] = 0``, ``t[i] = v[i] - v[i-1]``;
- ``TIMESTAMP``    (delta-of-delta): first differences with ``d[0] = 0``,
  then ``zigzag(d[i] - d[i-1])``.

The mode is data (one int32 per row), not a static argument, so one
compiled executable serves every mix of column types; with the pow-2
shape bucketing in ``ops.delta_zigzag`` a streaming session reuses a
handful of executables across all its chunks (``jitcache`` counts the
traces). Output rows are exactly ``repro.core.coltypes.transform_ints``
for values below ``coltypes.KERNEL_SAFE`` (|v| < 2**28, so second
differences and their zigzag cannot overflow the int32/uint32 lanes —
wider columns take the host's arbitrary-precision path). Positions at or
beyond a row's length are 0.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .jitcache import record_trace

# mode ids — must equal the repro.core.coltypes type ids
MONOTONE_INT = 1
TIMESTAMP = 2
NUMERIC = 3

RN = 8      # rows (columns-under-transform) per tile
CW = 2048   # positions per tile; a longer row spans several tiles


def _colcodec_kernel(vals_ref, prev_ref, lens_ref, mode_ref, ref_ref, out_ref):
    v = vals_ref[...]                    # (RN, CW) int32
    lens = lens_ref[...]                 # (RN, 1)
    mode = mode_ref[...]
    refv = ref_ref[...]
    rn, cw = v.shape
    j = pl.program_id(1)

    pos = j * cw + jax.lax.broadcasted_iota(jnp.int32, (rn, cw), 1)
    in_len = pos < lens
    vm = jnp.where(in_len, v, 0)

    # the tile extended by the two values before it (the previous tile's
    # last two; the first tile reads itself there, and the ``epos > 0``
    # select below zeroes what they would contribute)
    ext = jnp.concatenate([prev_ref[...][:, cw - 2:], vm], axis=1)   # (RN, CW+2)
    epos = j * cw - 2 + jax.lax.broadcasted_iota(jnp.int32, (rn, cw + 2), 1)
    # first differences with t[0] = 0 (the first value rides in the
    # descriptor, not the payload); valid from extended index 1 on
    shifted = jnp.concatenate([jnp.zeros((rn, 1), jnp.int32), ext[:, :-1]], axis=1)
    d_ext = jnp.where(epos > 0, ext - shifted, 0)
    d = d_ext[:, 2:]
    # second differences (dd[0] = 0, dd[1] = d[1]) + zigzag
    dd = d - d_ext[:, 1:-1]
    zz = (dd << 1) ^ (dd >> 31)

    fo = vm - refv
    out = jnp.where(mode == NUMERIC, fo, jnp.where(mode == MONOTONE_INT, d, zz))
    out_ref[...] = jnp.where(in_len, out, 0).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def colcodec_transform(
    vals: jnp.ndarray,
    lens: jnp.ndarray,
    mode: jnp.ndarray,
    ref: jnp.ndarray,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """(R, C) int32 + per-row len/mode/ref -> (R, C) uint32 transforms."""
    record_trace("colcodec_transform")
    r, width = vals.shape
    r_pad = -r % RN
    cw = width if width <= CW else CW
    c_pad = -width % cw
    vals_p = jnp.pad(vals, ((0, r_pad), (0, c_pad)))

    def col(a):
        return jnp.pad(a, ((0, r_pad),)).reshape(-1, 1)

    def row(i, j):
        return (i, 0)

    return pl.pallas_call(
        _colcodec_kernel,
        out_shape=jax.ShapeDtypeStruct(vals_p.shape, jnp.uint32),
        grid=((r + r_pad) // RN, (width + c_pad) // cw),
        in_specs=[
            pl.BlockSpec((RN, cw), lambda i, j: (i, j)),
            pl.BlockSpec((RN, cw), lambda i, j: (i, jnp.maximum(j - 1, 0))),
            pl.BlockSpec((RN, 1), row),
            pl.BlockSpec((RN, 1), row),
            pl.BlockSpec((RN, 1), row),
        ],
        out_specs=pl.BlockSpec((RN, cw), lambda i, j: (i, j)),
        interpret=interpret,
    )(vals_p, vals_p, col(lens), col(mode), col(ref))[:r, :width]

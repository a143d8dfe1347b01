"""Pallas kernel: the compressed-domain scan inner loop (DESIGN.md §14).

The aggregation operators (``repro.core.query``: count_by_template,
top_k, time_histogram) evaluate over *distinct* decoded rows with
per-distinct multiplicities — the hot loop is a weighted histogram of an
inverse index: ``out[b] = sum(weights[i] for i where inv[i] == b)``.

One launch takes the inverse index and weights tiled over ``RN``-row
blocks and accumulates into ``(1, DB)`` int32 output blocks via a
broadcast-iota one-hot compare — branch-free, no scatter. The bin axis
is the outer grid axis, so VMEM stays bounded however many bins there
are. Rows are padded with ``inv = -1`` (matches no bin) and
``weight = 0``; the bin axis is bucketed to a power of two by
``ops.distinct_counts``. Output is
bit-identical to the numpy ``np.add.at`` host twin (int32 accumulation
on every tier — parity-tested kernel == ref == host).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .jitcache import record_trace

RN = 256   # rows of the inverse index per tile
DB = 2048  # bins per output tile; more bins add a grid axis


def _distinct_counts_kernel(inv_ref, w_ref, out_ref):
    @pl.when(pl.program_id(1) == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    inv = inv_ref[...]                   # (RN, 1)
    w = w_ref[...]
    db = out_ref.shape[1]
    cols = pl.program_id(0) * db + jax.lax.broadcasted_iota(jnp.int32, (inv.shape[0], db), 1)
    hit = inv == cols                    # one-hot per row; -1 pad hits nothing
    out_ref[...] += jnp.sum(jnp.where(hit, w, 0), axis=0, keepdims=True)


@functools.partial(jax.jit, static_argnames=("n_bins", "interpret"))
def distinct_counts(
    inv: jnp.ndarray,
    weights: jnp.ndarray,
    *,
    n_bins: int,
    interpret: bool,
) -> jnp.ndarray:
    """(N,) int32 inverse index + (N,) int32 weights -> (1, n_bins) int32
    weighted bin counts. ``inv`` rows outside [0, n_bins) contribute 0."""
    record_trace("distinct_counts")
    n = inv.shape[0]
    rn = min(RN, n + -n % 8)  # a short index takes one 8-aligned tile
    r_pad = -n % rn
    db = n_bins if n_bins <= DB else DB
    d_pad = -n_bins % db
    inv_p = jnp.pad(inv, ((0, r_pad),), constant_values=-1).reshape(-1, 1)
    w_p = jnp.pad(weights, ((0, r_pad),)).reshape(-1, 1)
    return pl.pallas_call(
        _distinct_counts_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_bins + d_pad), jnp.int32),
        grid=((n_bins + d_pad) // db, (n + r_pad) // rn),
        in_specs=[
            pl.BlockSpec((rn, 1), lambda d, i: (i, 0)),
            pl.BlockSpec((rn, 1), lambda d, i: (i, 0)),
        ],
        out_specs=pl.BlockSpec((1, db), lambda d, i: (0, d)),
        interpret=interpret,
    )(inv_p, w_p)[:, :n_bins]

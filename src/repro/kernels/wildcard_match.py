"""Pallas kernel: batched wildcard-template matching (logzip's matcher).

The paper's prefix tree compares one log against all templates in one
pass on a CPU. The TPU-native equivalent (DESIGN.md §2) is the dense
reachability DP over (template-block x log-block) tiles:

    col[i] <- prev[i-1] & (log_i == t_j)     (literal t_j)
    col[i] <- OR_{i'<i} prev[i']             (t_j == '*', absorbs >= 1)

The kernel carries the DP columns of BK templates for BN lines at once
as one ``(T+1, BK, BN)`` int32 tile and advances every template by one
token per step: each step is a VPU update over the whole tile. Lines sit
on the lanes and templates on the sublanes, so one ``(BK, BN)`` slab per
log position is a whole number of vregs, and the position axis is the
leading (untiled) one: shifting along it only renames vregs. The star's
prefix-OR is built from log-step shifted ORs (Mosaic has no ``cumsum``);
it costs several times a literal step, so it runs only on the steps
where some template of the tile has a '*' (flags per template tile,
prefetched to SMEM with the tile's step count: the loop stops at the
tile's longest template, or at the line tile's longest line, since a
template of m units needs m tokens, so a tile of padding on either axis
runs no DP step; it still pays its block copy and the final reduction).
A stack trace's template is hundreds of literal frames
with a few stars. Template token ``j`` is read from the
ref (``tmpl_ref[j]``, a ``(BK, 1)`` column), never by slicing a loaded
value. Templates shorter than Tt freeze their column via the
``j < t_len`` select; a ``t_len < 0`` sentinel (padding rows,
over-length templates from ``ops.pack_templates``) matches nothing.

PAD tokens (id 0) can never equal a template literal (ids >= 2), so no
per-position masking is needed: correctness only requires reading the
column at exactly i = len(log).

The kernel writes a lane-dense ``(K, N)`` int32 matrix; the wrapper
returns its transpose as int8 {0,1} (TPU has no bool memory type).

VMEM per program (BN=128, BK=16): the column tile is (T+1) x 8 KiB,
about 1 MiB at T=128 and 2 MiB at T=256, plus a few temporaries of the
same size and the double-buffered (Tt, BK, 1) template block — independent
of the number of templates, which the grid tiles. Multi-line records reach
T=512, where BK=16 runs out of a v5e core's scoped VMEM: there a tile
holds BK=8 templates (``block_k``), 2 MiB a column tile again.
``tests/test_tpu_compile.py`` compiles every token bucket for a v5e core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PAD_ID = 0
STAR_ID = 1

BN = 128  # lines per tile (lanes)
BK = 16   # templates per tile (sublanes), up to 256 log tokens


def block_k(t: int) -> int:
    """Templates per tile for a log width of ``t`` tokens: half as many
    past 256, so that the column tile keeps to 2 MiB of VMEM."""
    return BK if t <= 256 else BK // 2


def _match_kernel(steps_ref, star_ref, longest_ref, logs_ref, lens_ref, tmpl_ref, tlen_ref,
                  out_ref):
    logs = logs_ref[...]            # (T, BN) token position x line
    lens = lens_ref[...]            # (1, BN)
    tlens = tlen_ref[...]           # (BK, 1)
    t, bn = logs.shape
    bk = tlens.shape[0]
    kt = pl.program_id(1)
    # a template of m units needs at least m tokens: the tile stops at its
    # longest line too, so a tile of padding lines takes no step
    longest = longest_ref[pl.program_id(0)]

    def shift(x, s):                # x[i - s] along the position axis, 0 below
        return jnp.concatenate([jnp.zeros((s,) + x.shape[1:], x.dtype), x[:-s]], axis=0)

    def literal(col, tj):           # advance where the log token equals tj
        lit = (logs[:, None, :] == tj).astype(jnp.int32)          # (T, BK, BN)
        return jnp.concatenate([jnp.zeros((1, bk, bn), jnp.int32), col[:-1] * lit], axis=0)

    def with_star(col, tj):         # some template of the tile has '*' here
        # star: prefix-OR then shift right by one (absorbs >= 1 token)
        run = col
        s = 1
        while s <= t:
            run = run | shift(run, s)
            s *= 2
        return jnp.where(tj == STAR_ID, shift(run, 1), literal(col, tj))

    def per_token(j, col):          # col: (T+1, BK, BN) int32 reachability
        tj = tmpl_ref[j][None]      # (1, BK, 1)
        new = jax.lax.cond(star_ref[kt, j] != 0, with_star, literal, col, tj)
        return jnp.where(j < tlens[None], new, col)               # template still has tokens

    pos = jax.lax.broadcasted_iota(jnp.int32, (t + 1, bk, bn), 0)
    col = jax.lax.fori_loop(0, jnp.minimum(steps_ref[kt], longest), per_token,
                            (pos == 0).astype(jnp.int32))

    hit = jnp.sum(jnp.where(pos == lens[None], col, 0), axis=0)  # col[i = len(log)]
    hit = hit * (lens <= t).astype(jnp.int32)                    # truncated lines: no match
    # sentinel templates, and those longer than every line: no match
    out_ref[...] = hit * ((tlens >= 0) & (tlens <= longest)).astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def wildcard_match(
    logs: jnp.ndarray,
    lens: jnp.ndarray,
    templates: jnp.ndarray,
    t_lens: jnp.ndarray,
    *,
    interpret: bool,
) -> jnp.ndarray:
    """(N,T),(N,) x (K,Tt),(K,) int32 -> (N, K) int8 {0,1} match matrix.

    Templates with ``t_len < 0`` (grid padding, over-length sentinels
    from ``ops.pack_templates``) match nothing.
    """
    from .jitcache import record_trace

    record_trace("wildcard_match")
    n, t = logs.shape
    k, tt = templates.shape
    bk = block_k(t)
    n_pad = -n % BN
    k_pad = -k % bk
    logs_t = jnp.pad(logs, ((0, n_pad), (0, 0))).T                      # (T, Np)
    lens_p = jnp.pad(lens, ((0, n_pad),)).reshape(1, -1)                # (1, Np)
    tmpl_p = jnp.pad(templates, ((0, k_pad), (0, 0)))                   # (Kp, Tt)
    tmpl_t = tmpl_p.T[:, :, None]                                       # (Tt, Kp, 1)
    tlen_p = jnp.pad(t_lens.reshape(-1), ((0, k_pad),), constant_values=-1)
    # per template tile, in SMEM: the steps its longest template takes,
    # and which steps hold a '*' in any of its templates (only those pay
    # for the prefix-OR; a stack trace's template is literal frames)
    tiles = (k + k_pad) // bk
    steps = jnp.minimum(jnp.max(tlen_p.reshape(tiles, bk), axis=1), tt).astype(jnp.int32)
    star = jnp.any((tmpl_p == STAR_ID).reshape(tiles, bk, tt), axis=1).astype(jnp.int32)
    # per line tile: its longest line
    longest = jnp.max(lens_p.reshape(-1, BN), axis=1).astype(jnp.int32)
    out = pl.pallas_call(
        _match_kernel,
        out_shape=jax.ShapeDtypeStruct((k + k_pad, n + n_pad), jnp.int32),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=((n + n_pad) // BN, tiles),
            in_specs=[
                pl.BlockSpec((t, BN), lambda i, j, *_: (0, i)),
                pl.BlockSpec((1, BN), lambda i, j, *_: (0, i)),
                pl.BlockSpec((tt, bk, 1), lambda i, j, *_: (0, j, 0)),
                pl.BlockSpec((bk, 1), lambda i, j, *_: (j, 0)),
            ],
            out_specs=pl.BlockSpec((bk, BN), lambda i, j, *_: (j, i)),
        ),
        interpret=interpret,
    )(steps, star, longest, logs_t, lens_p, tmpl_t, tlen_p.reshape(-1, 1))
    return out[:k, :n].T.astype(jnp.int8)

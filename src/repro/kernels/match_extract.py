"""Pallas kernel: fused wildcard match + parameter-span extraction
(DESIGN.md §10.2).

One pass over a ``(BN, T)`` token tile against all K templates returns,
per line, the lowest-id matching template AND the token span each ``'*'``
absorbed — collapsing the host's ``ise.match -> spans`` stage pair into
a single launch. Per template the kernel runs the reachability DP of
``repro.kernels.wildcard_match`` but keeps every DP column in a VMEM
scratch ``(BN, Tt+1, T+1)``, then walks it backwards: at template
position j a star's span end is the running cursor ``i`` and its start
the largest ``i' <= i-1`` with ``M[i', j-1]`` — identical tie-break to
``core.match.extract_spans_dp`` (later stars take the shortest span).
Lowest-id-wins selection is a running ``best``/``spans`` select as the
template loop ascends, so the template axis never materializes an
(N, K) matrix.

Templates with ``t_len < 0`` (grid padding, over-length sentinels from
``ops.pack_templates``) match nothing. Over-length *lines*
(``len > T``) are masked on the host (`ops.match_extract`), where the
true unpadded width is known.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .jitcache import record_trace

PAD_ID = 0
STAR_ID = 1

BN = 64  # lines per tile (bounds the (BN, Tt+1, T+1) DP scratch)


def _me_kernel(logs_ref, lens_ref, tmpl_ref, tlen_ref, srank_ref,
               assign_ref, spans_ref):
    logs = logs_ref[...]              # (BN, T) int32
    lens = lens_ref[...][:, 0]        # (BN,)
    tmpl = tmpl_ref[...]              # (K, Tt)
    tlens = tlen_ref[...][:, 0]       # (K,)
    srank = srank_ref[...]            # (K, Tt) stars among tokens [0, j]
    bn, t = logs.shape
    k, tt = tmpl.shape
    n_slots = spans_ref.shape[1] // 2

    pos = jax.lax.broadcasted_iota(jnp.int32, (bn, t + 1), 1)
    col0 = (pos == 0).astype(jnp.int32)
    lens_c = jnp.minimum(lens, t)
    slot_iota = jax.lax.broadcasted_iota(jnp.int32, (bn, n_slots), 1)

    def per_template(ki, carry):
        best, sp_start, sp_end = carry
        row = tmpl[ki]                                   # (Tt,)
        tl = tlens[ki]

        # ---- forward DP, all columns kept: M[:, j, :] after j tokens
        def fwd(j, state):
            col, m = state
            tj = row[j]
            is_star = tj == STAR_ID
            run = jnp.minimum(jnp.cumsum(col, axis=1), 1)
            zero = jnp.zeros((bn, 1), col.dtype)
            star_col = jnp.concatenate([zero, run[:, :-1]], axis=1)
            lit = (logs == tj).astype(col.dtype)
            lit_col = jnp.concatenate([zero, col[:, :-1] * lit], axis=1)
            new = jnp.where(is_star, star_col, lit_col)
            new = jnp.where(j < tl, new, col)
            m = jax.lax.dynamic_update_slice(
                m, new.astype(jnp.int8)[:, None, :], (0, j + 1, 0))
            return new, m

        m0 = jnp.zeros((bn, tt + 1, t + 1), jnp.int8)
        m0 = m0.at[:, 0, :].set(col0.astype(jnp.int8))
        colf, m = jax.lax.fori_loop(0, tt, fwd, (col0, m0))

        hit = (colf * (pos == lens_c[:, None]).astype(jnp.int32)).sum(axis=1)
        hit = hit * (tl >= 0).astype(jnp.int32)
        hit = hit.astype(jnp.bool_)

        # ---- backward walk: spans for THIS template
        def bwd(step, state):
            i, ss, se = state
            j = tl - step                                # tl .. 1
            active = j >= 1
            tok = row[jnp.maximum(j - 1, 0)]
            is_star = active & (tok == STAR_ID)
            mj = m[:, jnp.maximum(j - 1, 0), :].astype(jnp.int32)  # (BN, T+1)
            gate = mj * (pos <= (i - 1)[:, None]).astype(jnp.int32)
            ip = jnp.max(gate * pos, axis=1)             # largest reachable i'
            si = srank[ki, jnp.maximum(j - 1, 0)] - 1    # star slot of token j
            upd = is_star & (slot_iota == si)            # (BN, n_slots) one-hot
            ss = jnp.where(upd, ip[:, None], ss)
            se = jnp.where(upd, i[:, None], se)
            i_new = jnp.where(is_star, ip, i - 1)
            i = jnp.where(active, i_new, i)
            return i, ss, se

        ss0 = jnp.zeros((bn, n_slots), jnp.int32)
        se0 = jnp.zeros((bn, n_slots), jnp.int32)
        _, ss, se = jax.lax.fori_loop(0, tt, bwd, (lens_c.astype(jnp.int32), ss0, se0))

        take = hit & (best < 0)
        best = jnp.where(take, ki, best)
        sp_start = jnp.where(take[:, None], ss, sp_start)
        sp_end = jnp.where(take[:, None], se, sp_end)
        return best, sp_start, sp_end

    best0 = jnp.full((bn,), -1, jnp.int32)
    z = jnp.zeros((bn, n_slots), jnp.int32)
    best, ss, se = jax.lax.fori_loop(0, k, per_template, (best0, z, z))
    assign_ref[...] = best[:, None]
    spans_ref[...] = jnp.concatenate([ss, se], axis=1)


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def match_extract(
    logs: jnp.ndarray,
    lens: jnp.ndarray,
    templates: jnp.ndarray,
    t_lens: jnp.ndarray,
    *,
    n_slots: int,
    interpret: bool,
):
    """-> (assign (N,) int32 lowest matching template id or -1,
    spans (N, n_slots, 2) int32 [start, end) per star slot).

    Spans rows are meaningful for the assigned template's first
    ``n_stars`` slots; unused slots stay 0. Lines with ``len > T`` are
    NOT masked here (the caller knows the true width; see
    ``ops.match_extract``).
    """
    record_trace("match_extract")
    n, t = logs.shape
    k, tt = templates.shape
    n_pad = -n % BN
    logs_p = jnp.pad(logs, ((0, n_pad), (0, 0)))
    lens_p = jnp.pad(lens, ((0, n_pad),)).reshape(-1, 1)
    # star rank: stars among template tokens [0, j] (for slot lookup)
    srank = jnp.cumsum((templates == STAR_ID).astype(jnp.int32), axis=1)
    assign, spans = pl.pallas_call(
        _me_kernel,
        out_shape=(
            jax.ShapeDtypeStruct((n + n_pad, 1), jnp.int32),
            jax.ShapeDtypeStruct((n + n_pad, 2 * n_slots), jnp.int32),
        ),
        grid=((n + n_pad) // BN,),
        in_specs=[
            pl.BlockSpec((BN, t), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((k, tt), lambda i: (0, 0)),
            pl.BlockSpec((k, 1), lambda i: (0, 0)),
            pl.BlockSpec((k, tt), lambda i: (0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((BN, 2 * n_slots), lambda i: (i, 0)),
        ],
        interpret=interpret,
    )(logs_p, lens_p, templates, t_lens.reshape(-1, 1), srank)
    assign = assign[:n, 0]
    spans = spans[:n]
    return assign, jnp.stack([spans[:, :n_slots], spans[:, n_slots:]], axis=2)

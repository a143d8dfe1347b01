"""Pallas kernel: phi(m, t) = common-token count, log-block x template-block.

This is the inner loop of logzip's fine-grained clustering (paper §III-C:
"The time-consuming step is the computation of similarity between the
given log and each template of existing clusters"). On TPU we tile
(BN logs x T tokens) and (BK templates x Tt tokens) into VMEM and produce
a (BN, BK) count tile. Grid = (N/BN, K/BK); tiles are independent ->
embarrassingly parallel, matching the paper's parallelism claim.

Token-presence bitset formulation (DESIGN.md §2.2): instead of carrying a
boolean presence tile and re-broadcasting a (BN, BK, Tt) compare per log
token, the kernel loops over the Tt *template* tokens and accumulates,
per (log, template) pair, a bitset over log positions — W = T/32 int32
lanes, i.e. a 32x denser carried state than the one-byte-per-position
presence matrix. Each step packs its (BN, BK, T) compare into the bitset
with a shift-and-sum (distinct bits -> sum == or); the final count is a
branch-free SWAR popcount (pure ``bitwise_and``/shift/multiply) of the
bitset AND the valid-log-token bitset. Duplicate log tokens count once
per occurrence, PAD/STAR tokens neither count nor match — exactly
``ref.simcount_ref``.

VMEM per program (BN=128, BK=32, T=Tt=128):
  logs 64 KiB + templates 16 KiB + bitset (128x32x4 int32) 64 KiB + one
  (BN, BK, T) compare tile 2 MiB — comfortably inside ~16 MiB/core.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

PAD_ID = 0
STAR_ID = 1

BN = 128  # logs per tile
BK = 32   # templates per tile


def _popcount32(x: jnp.ndarray) -> jnp.ndarray:
    """SWAR popcount of a uint32 array (bitwise ops only)."""
    c1 = np.uint32(0x55555555)
    c2 = np.uint32(0x33333333)
    c4 = np.uint32(0x0F0F0F0F)
    m = np.uint32(0x01010101)
    x = x - ((x >> 1) & c1)
    x = (x & c2) + ((x >> 2) & c2)
    x = (x + (x >> 4)) & c4
    return (x * m) >> 24  # byte-sum lands in the top byte (mod-2^32 wrap is exact)


def _pack_bits(mask: jnp.ndarray) -> jnp.ndarray:
    """(..., W*32) bool -> (..., W) uint32 bitset (bit b of word w = pos 32w+b)."""
    r = mask.reshape(mask.shape[:-1] + (-1, 32)).astype(jnp.uint32)
    lane = jax.lax.broadcasted_iota(jnp.uint32, r.shape, r.ndim - 1)
    return jnp.sum(r << lane, axis=-1, dtype=jnp.uint32)


def _simcount_kernel(logs_ref, tmpl_ref, out_ref):
    logs = logs_ref[...]          # (BN, T), T % 32 == 0 (host pads)
    tmpl = tmpl_ref[...]          # (BK, Tt)
    bn, t = logs.shape
    bk, tt = tmpl.shape

    def body(j, hitbits):         # hitbits: (BN, BK, T/32) uint32
        tj = tmpl[:, j]                                     # (BK,)
        tvalid = (tj != PAD_ID) & (tj != STAR_ID)           # (BK,)
        eq = (logs[:, None, :] == tj[None, :, None]) & tvalid[None, :, None]
        return hitbits | _pack_bits(eq)

    w = t // 32
    hitbits = jax.lax.fori_loop(
        0, tt, body, jnp.zeros((bn, bk, w), jnp.uint32)
    )
    ok = (logs != PAD_ID) & (logs != STAR_ID)               # (BN, T)
    okbits = _pack_bits(ok)                                 # (BN, W)
    counts = _popcount32(hitbits & okbits[:, None, :]).sum(axis=2)
    out_ref[...] = counts.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def simcount(logs: jnp.ndarray, templates: jnp.ndarray, *, interpret: bool) -> jnp.ndarray:
    """(N, T) x (K, Tt) int32 -> (N, K) int32 common-token counts."""
    n, t = logs.shape
    k, tt = templates.shape
    n_pad = -n % BN
    k_pad = -k % BK
    t_pad = -t % 32  # bitset lanes need T % 32 == 0; PAD tokens never count
    logs_p = jnp.pad(logs, ((0, n_pad), (0, t_pad)))
    tmpl_p = jnp.pad(templates, ((0, k_pad), (0, 0)))
    out = pl.pallas_call(
        _simcount_kernel,
        out_shape=jax.ShapeDtypeStruct((n + n_pad, k + k_pad), jnp.int32),
        grid=((n + n_pad) // BN, (k + k_pad) // BK),
        in_specs=[
            pl.BlockSpec((BN, t + t_pad), lambda i, j: (i, 0)),
            pl.BlockSpec((BK, tt), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BN, BK), lambda i, j: (i, j)),
        interpret=interpret,
    )(logs_p, tmpl_p)
    return out[:n, :k]

"""Pallas kernel: byte-level tokenizer / rolling hasher (DESIGN.md §10.1).

Lines land on device as padded ``(N, B)`` uint8 blocks. One branch-free
pass over the byte grid emits everything the host needs to build the
token-id matrix without running a regex per line:

- ``mask``   (N, B) int8 — 1 on token bytes (non-delimiter, in-length);
- ``starts`` (N, B) int8 — 1 on the first byte of each token (the
  token-boundary bitmask);
- ``pref1``/``pref2`` (N, B) uint32 — inclusive prefix sums of the
  position-weighted byte polynomial ``(byte+1) * P**pos`` under two
  independent multipliers.

A token spanning bytes ``[s, e)`` then hashes to
``(pref[e-1] - pref[s-1]) * P**-s`` (two gathers on the host) — the same
position-independent rolling-hash construction as
``repro.core.textops.SegmentHasher``, in 2x uint32 lanes instead of one
uint64 (TPUs have no 64-bit integer units). The host ``Vocab`` interns
only the hashes it has not seen, so device->host traffic is masks +
hashes, never token strings.

The delimiter set is static (baked into the compiled kernel as a chain
of byte compares); the power tables are data-independent inputs so one
compiled executable serves every chunk of a bucketed width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from .jitcache import record_trace

# independent odd multipliers for the two uint32 hash lanes
P1 = 0x01000193
P2 = 0x00085EBD

BN = 256  # lines per tile


def hash_powers(b: int) -> tuple:
    """Host-side (P**i, P**-i mod 2**32) tables for both lanes, i < b."""
    import numpy as np

    out = []
    for p in (P1, P2):
        pinv = pow(p, -1, 1 << 32)
        pw = np.empty(b, np.uint64)
        ipw = np.empty(b, np.uint64)
        pw[0] = ipw[0] = 1
        for i in range(1, b):
            pw[i] = (pw[i - 1] * p) & 0xFFFFFFFF
            ipw[i] = (ipw[i - 1] * pinv) & 0xFFFFFFFF
        out.append((pw.astype(np.uint32), ipw.astype(np.uint32)))
    return tuple(out)


def _prefix_sum(x: jnp.ndarray) -> jnp.ndarray:
    """Inclusive prefix sum along axis 1 by log-step shifted adds
    (Mosaic does not lower ``cumsum``); exact in wrapping uint32."""
    s = 1
    while s < x.shape[1]:
        x = x + jnp.concatenate([jnp.zeros((x.shape[0], s), x.dtype), x[:, :-s]], axis=1)
        s *= 2
    return x


def _tokenize_kernel(delims: tuple, bytes_ref, lens_ref, pw1_ref, pw2_ref,
                     mask_ref, starts_ref, pref1_ref, pref2_ref):
    b = bytes_ref[...]              # (BN, B) uint8 (int32-widened below)
    lens = lens_ref[...][:, 0]      # (BN,)
    bi = b.astype(jnp.int32)
    bn, width = b.shape

    pos = jax.lax.broadcasted_iota(jnp.int32, (bn, width), 1)
    in_len = pos < lens[:, None]
    is_delim = jnp.zeros((bn, width), jnp.bool_)
    for d in delims:                # static byte set -> unrolled compares
        is_delim = is_delim | (bi == d)
    tok = jnp.where(in_len & ~is_delim, 1, 0)   # int32: Mosaic shifts no i1 vectors
    prev = jnp.concatenate([jnp.zeros((bn, 1), jnp.int32), tok[:, :-1]], axis=1)
    starts = tok * (1 - prev)

    toki = tok.astype(jnp.uint32)
    for pw_ref, pref_ref in ((pw1_ref, pref1_ref), (pw2_ref, pref2_ref)):
        pw = pw_ref[...]            # (1, B) uint32
        w = (bi.astype(jnp.uint32) + 1) * pw * toki
        pref_ref[...] = _prefix_sum(w)
    mask_ref[...] = tok.astype(jnp.int8)
    starts_ref[...] = starts.astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("delims", "interpret"))
def tokenize_hash(
    blocks: jnp.ndarray,
    lens: jnp.ndarray,
    pw1: jnp.ndarray,
    pw2: jnp.ndarray,
    *,
    delims: tuple,
    interpret: bool,
):
    """(N, B) uint8 blocks -> (mask, starts, pref1, pref2); see module
    docstring for the layout contract."""
    record_trace("tokenize_hash")
    n, width = blocks.shape
    n_pad = -n % BN
    blocks_p = jnp.pad(blocks, ((0, n_pad), (0, 0)))
    lens_p = jnp.pad(lens, ((0, n_pad),)).reshape(-1, 1)
    kernel = functools.partial(_tokenize_kernel, delims)
    out_shapes = (
        jax.ShapeDtypeStruct((n + n_pad, width), jnp.int8),
        jax.ShapeDtypeStruct((n + n_pad, width), jnp.int8),
        jax.ShapeDtypeStruct((n + n_pad, width), jnp.uint32),
        jax.ShapeDtypeStruct((n + n_pad, width), jnp.uint32),
    )
    outs = pl.pallas_call(
        kernel,
        out_shape=out_shapes,
        grid=((n + n_pad) // BN,),
        in_specs=[
            pl.BlockSpec((BN, width), lambda i: (i, 0)),
            pl.BlockSpec((BN, 1), lambda i: (i, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
            pl.BlockSpec((1, width), lambda i: (0, 0)),
        ],
        out_specs=[pl.BlockSpec((BN, width), lambda i: (i, 0)) for _ in range(4)],
        interpret=interpret,
    )(blocks_p, lens_p, pw1.reshape(1, -1), pw2.reshape(1, -1))
    return tuple(o[:n] for o in outs)

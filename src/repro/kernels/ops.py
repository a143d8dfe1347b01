"""jit'd wrappers + host/pod conveniences for the logzip kernels.

The platform picks the path: on a TPU backend the Pallas kernels run
compiled, anywhere else (the CPU test suite) they run in Pallas
interpret mode. Nothing needs setting either way; ``python chip_smoke.py``
drives the main path on a chip and checks it end to end.

Each main-path wrapper (``wildcard_match``, ``delta_zigzag``,
``distinct_counts``) runs whole in a ``dispatch.<kernel>`` span of
``repro.core.timing``: padding, transfer, launch, the device-side trim
and the copy back, so a compile on the way (the trim's, per unpadded
shape) lands as ``dispatch.<kernel>.compile`` in the caller's sink.

``wildcard_match_sharded`` is the pod-scale matcher: logs sharded over
the mesh ``data`` axis, templates replicated — zero-collective data
parallelism (the paper's "highly parallel matching" mapped onto a pod).
"""

from __future__ import annotations

import functools
import logging

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P

from . import ref
from repro.core.textops import first_occurrence_unique, runs_of
from repro.core.timing import spanned

from .colcodec import colcodec_transform as _colcodec_transform
from .jitcache import bucket, bucket_stats, record_call, reset_counters  # noqa: F401 (re-exported)
from .match_extract import match_extract as _match_extract
from .scan import distinct_counts as _scan_distinct_counts
from .simcount import simcount as _simcount
from .tokenize import hash_powers, tokenize_hash
from .wildcard_match import STAR_ID
from .wildcard_match import wildcard_match as _wildcard_match


@functools.cache
def platform() -> str:
    """The JAX backend the kernels run on, resolved at the first kernel
    call (never at import, so importing this module touches no device)."""
    return jax.default_backend()


def on_tpu() -> bool:
    """Whether the kernels run compiled on a TPU. This is the default of
    every path selector that chooses between a kernel and its host twin
    (``ISEConfig.use_kernel``, ``distinct_counts(prefer_host=)``)."""
    return platform() == "tpu"


def interpret() -> bool:
    """Pallas interpret mode: everywhere but on a TPU."""
    return not on_tpu()


# ------------------------------------------ backend fallback (DESIGN §13)
#
# Every kernel entry point dispatches down a kernel -> ref -> host chain:
# the Pallas kernel first, the pure-jnp oracle if the kernel fails to
# compile or run, and a numpy twin if jnp itself is unusable. A failed
# tier is demoted for the rest of the process (no per-call retry storm)
# and the demotion is logged once, structured, via the
# ``repro.kernels.ops`` logger. ``backend_report()`` says which tier each
# op is running on. On a TPU nothing is demoted: a kernel that fails
# raises, so a device run can never silently be a host run.

_LOG = logging.getLogger("repro.kernels.ops")
_DEMOTED: dict[str, int] = {}  # op -> first chain tier still trusted
_FALLBACKS: dict[str, list[dict]] = {}  # op -> demotion events


def _dispatch(op: str, *args, **kw):
    chain = _CHAINS[op]
    if on_tpu():
        return chain[0][1](*args, **kw)
    err: Exception | None = None
    for i in range(_DEMOTED.get(op, 0), len(chain)):
        backend, fn = chain[i]
        try:
            return fn(*args, **kw)
        except Exception as e:  # demote this tier and try the next
            err = e
            _DEMOTED[op] = i + 1
            nxt = chain[i + 1][0] if i + 1 < len(chain) else None
            event = {"op": op, "backend": backend, "fallback": nxt,
                     "interpret": interpret(),
                     "error": f"{type(e).__name__}: {e}"}
            _FALLBACKS.setdefault(op, []).append(event)
            if nxt is not None:
                _LOG.warning(
                    "kernel backend %r failed for op %r, falling back to %r "
                    "(one-time, sticky): %s", backend, op, nxt, event["error"])
    raise err


def backend_report() -> dict:
    """{op: {backend, interpret, fallbacks}} for every kernel op — the
    tier the next call will run on plus any demotion events so far."""
    out = {}
    for op, chain in _CHAINS.items():
        tier = min(_DEMOTED.get(op, 0), len(chain) - 1)
        out[op] = {"backend": chain[tier][0], "interpret": interpret(),
                   "fallbacks": list(_FALLBACKS.get(op, []))}
    return out


def reset_backend_state() -> None:
    """Forget demotions (tests; or after fixing the environment)."""
    _DEMOTED.clear()
    _FALLBACKS.clear()


# numpy twins of the jnp oracles in ``ref`` — the last-resort tier when
# neither the Pallas kernel nor jnp evaluation is usable

def _simcount_host(logs, templates):
    logs = np.asarray(logs, np.int32)
    templates = np.asarray(templates, np.int32)
    lv = (logs != ref.PAD_ID) & (logs != STAR_ID)
    tv = (templates != ref.PAD_ID) & (templates != STAR_ID)
    eq = logs[:, None, :, None] == templates[None, :, None, :]
    eq = eq & lv[:, None, :, None] & tv[None, :, None, :]
    return eq.any(axis=3).sum(axis=2).astype(np.int32)


def _wildcard_match_np(logs, lens, templates, t_lens):
    logs = np.asarray(logs, np.int32)
    lens = np.asarray(lens, np.int32)
    templates = np.asarray(templates, np.int32)
    t_lens = np.asarray(t_lens, np.int32)
    n, t = logs.shape
    k, tt = templates.shape
    col = np.zeros((n, k, t + 1), bool)
    col[:, :, 0] = True
    for j in range(tt):
        tj = templates[:, j]
        run = np.cumsum(col, axis=2) > 0
        star_col = np.concatenate([np.zeros((n, k, 1), bool), run[:, :, :-1]], axis=2)
        lit_hit = logs[:, None, :] == tj[None, :, None]
        lit_col = np.concatenate(
            [np.zeros((n, k, 1), bool), col[:, :, :-1] & lit_hit], axis=2)
        new = np.where((tj == STAR_ID)[None, :, None], star_col, lit_col)
        col = np.where((j < t_lens)[None, :, None], new, col)
    idx = np.clip(lens, 0, t)
    matched = col[np.arange(n)[:, None], np.arange(k)[None, :], idx[:, None]]
    return matched & (lens <= t)[:, None] & (t_lens >= 0)[None, :]


def _tokenize_hash_host(blocks, lens, pw1, pw2, *, delims):
    blocks = np.asarray(blocks)
    n, b = blocks.shape
    bi = blocks.astype(np.int32)
    in_len = np.arange(b)[None, :] < np.asarray(lens)[:, None]
    tok = in_len & ~np.isin(bi, np.asarray(delims, np.int32))
    prev = np.concatenate([np.zeros((n, 1), bool), tok[:, :-1]], axis=1)
    starts = tok & ~prev
    prefs = []
    for pw in (pw1, pw2):
        w = (bi.astype(np.uint32) + 1) * np.asarray(pw)[None, :] * tok.astype(np.uint32)
        prefs.append(np.cumsum(w, axis=1, dtype=np.uint32))
    return tok.astype(np.int8), starts.astype(np.int8), prefs[0], prefs[1]


def _colcodec_transform_host(vals, lens, mode, ref_row):
    vals = np.asarray(vals, np.int32)
    r, width = vals.shape
    pos = np.arange(width)[None, :]
    in_len = pos < np.asarray(lens)[:, None]
    vm = np.where(in_len, vals, 0).astype(np.int32)
    prev = np.concatenate([np.zeros((r, 1), np.int32), vm[:, :-1]], axis=1)
    d = np.where(pos > 0, vm - prev, 0).astype(np.int32)
    dprev = np.concatenate([np.zeros((r, 1), np.int32), d[:, :-1]], axis=1)
    dd = (d - dprev).astype(np.int32)
    zz = np.left_shift(dd, 1) ^ np.right_shift(dd, 31)
    fo = vm - np.asarray(ref_row, np.int32)[:, None]
    mode = np.asarray(mode)
    out = np.where((mode == 3)[:, None], fo,
                   np.where((mode == 1)[:, None], d, zz))
    return np.where(in_len, out, 0).astype(np.uint32)


def _distinct_counts_host(inv, weights, n_bins: int) -> np.ndarray:
    """numpy twin of ``scan.distinct_counts``: int32 ``np.add.at``
    scatter (NOT ``np.bincount(weights=...)``, whose float64 accumulator
    would break bit-identity with the int32 kernel lanes)."""
    inv = np.asarray(inv, np.int64)
    w = np.asarray(weights, np.int32)
    out = np.zeros(n_bins, np.int32)
    valid = (inv >= 0) & (inv < n_bins)
    np.add.at(out, inv[valid], w[valid])
    return out


_CHAINS: dict[str, tuple] = {
    "simcount": (
        ("kernel", lambda lg, tp: _simcount(lg, tp, interpret=interpret())),
        ("ref", lambda lg, tp: ref.simcount_ref(lg, tp)),
        ("host", lambda lg, tp: _simcount_host(lg, tp)),
    ),
    "wildcard_match": (
        ("kernel", lambda *a: _wildcard_match(*a, interpret=interpret())),
        ("ref", lambda *a: ref.wildcard_match_ref(*a)),
        ("host", lambda *a: _wildcard_match_np(*a)),
    ),
    "match_extract": (
        ("kernel", lambda *a, n_slots: _match_extract(
            *a, n_slots=n_slots, interpret=interpret())),
        # the jnp tier for the fused op IS the host anchor matcher
        ("host", lambda *a, n_slots: ref.match_extract_ref(*a, n_slots=n_slots)),
    ),
    "tokenize_hash": (
        ("kernel", lambda *a, delims: tokenize_hash(
            *a, delims=delims, interpret=interpret())),
        ("ref", lambda *a, delims: ref.tokenize_hash_ref(*a, delims)),
        ("host", lambda *a, delims: _tokenize_hash_host(*a, delims=delims)),
    ),
    "colcodec_transform": (
        ("kernel", lambda *a: _colcodec_transform(*a, interpret=interpret())),
        ("ref", lambda *a: ref.colcodec_transform_ref(*a)),
        ("host", lambda *a: _colcodec_transform_host(*a)),
    ),
    "distinct_counts": (
        ("kernel", lambda iv, w, d: _scan_distinct_counts(
            iv, w, n_bins=d, interpret=interpret())[0]),
        ("ref", lambda iv, w, d: ref.distinct_counts_ref(iv, w, d)),
        ("host", lambda iv, w, d: _distinct_counts_host(
            np.asarray(iv), np.asarray(w), d)),
    ),
}


def simcount(logs, templates):
    """(N, T) x (K, Tt) int32 -> (N, K) int32 common-token counts."""
    return _dispatch("simcount", jnp.asarray(logs, jnp.int32),
                     jnp.asarray(templates, jnp.int32))


def _pad_to(arr: np.ndarray, shape: tuple, fill=0) -> np.ndarray:
    pads = [(0, s - d) for d, s in zip(arr.shape, shape)]
    if not any(p[1] for p in pads):
        return arr
    return np.pad(arr, pads, constant_values=fill)


@spanned("dispatch.wildcard_match")
def wildcard_match(logs, lens, templates, t_lens, *, use_buckets: bool = True) -> jnp.ndarray:
    """-> (N, K) bool match matrix.

    With ``use_buckets`` (default) every dynamic dimension is padded up
    to a power-of-two bucket before hitting the jitted kernel, so
    streaming chunks with drifting shapes reuse one compiled executable
    per bucket (zero re-traces after warmup — ``jitcache.TRACE_COUNTS``
    records the actual trace count). Padding is sliced/masked back out:
    results are bit-identical to the unbucketed call.
    """
    logs = np.asarray(logs, np.int32)
    lens_np = np.asarray(lens, np.int32)
    templates = np.asarray(templates, np.int32)
    t_lens_np = np.asarray(t_lens, np.int32)
    n, t = logs.shape
    k, tt = templates.shape
    if use_buckets:
        # floors absorb the normal drift of a streaming session (token
        # width wobbling per chunk, the template store creeping past a
        # power of two) so warm sessions never leave their bucket
        nb, tb = bucket(n, 256), bucket(t, 32)
        kb, ttb = bucket(k, 16), bucket(tt, 16)
        record_call("wildcard_match", (nb, tb, kb, ttb))
        out = _dispatch(
            "wildcard_match",
            jnp.asarray(_pad_to(logs, (nb, tb))),
            jnp.asarray(_pad_to(lens_np, (nb,))),
            jnp.asarray(_pad_to(templates, (kb, ttb))),
            jnp.asarray(np.pad(t_lens_np, (0, kb - k), constant_values=-1)),
        )[:n, :k]
        # the padded width tb would let stars absorb PAD columns of lines
        # whose true length exceeds t: re-apply the host's truncation rule
        return np.asarray(out).astype(bool) & (lens_np <= t)[:, None]
    out = _dispatch(
        "wildcard_match",
        jnp.asarray(logs), jnp.asarray(lens_np), jnp.asarray(templates),
        jnp.asarray(t_lens_np),
    )
    return np.asarray(out).astype(bool)


def pack_templates(templates: list[np.ndarray], t_max: int | None = None) -> tuple[np.ndarray, np.ndarray]:
    """Pad a ragged template list into (K, Tt) + (K,) length arrays.

    A template longer than ``t_max`` cannot be represented in Tt slots;
    silently truncating its tokens while recording the full length would
    make the kernel match a *prefix* the host matcher never would. Such
    templates get the ``t_len = -1`` sentinel instead: the kernel (and
    ``ref.wildcard_match_ref``) treat them as matching nothing, which is
    consistent with the host whenever ``t_max >= logs.shape[1]`` (a
    template with more units than the log budget can never match).
    """
    if not templates:
        return np.zeros((0, 1), np.int32), np.zeros((0,), np.int32)
    tt = t_max or max(len(t) for t in templates)
    k = len(templates)
    mat = np.zeros((k, tt), np.int32)
    lens = np.zeros((k,), np.int32)
    for i, t in enumerate(templates):
        if len(t) > tt:
            mat[i] = t[:tt]
            lens[i] = -1  # over-length sentinel: matches nothing
        else:
            lens[i] = len(t)
            mat[i, : len(t)] = t
    return mat, lens


def wildcard_match_host(ids: np.ndarray, lens: np.ndarray, templates: list[np.ndarray]) -> np.ndarray:
    """numpy in/out convenience used by ``core.match.match_first``."""
    tmpl, tlens = pack_templates(templates)
    if tmpl.shape[0] == 0:
        return np.zeros((ids.shape[0], 0), bool)
    return np.asarray(wildcard_match(ids, lens, tmpl, tlens))


def match_first_bucketed(ids: np.ndarray, lens: np.ndarray, templates: list[np.ndarray]) -> np.ndarray:
    """Lowest-id matching template per line via the Pallas kernel, with
    first-token bucketing (the trie's root-level pruning) wired into the
    kernel path: instead of one dense N x K launch, templates are grouped
    by their first literal token and each bucket's kernel only sees the
    lines that start with that token. Star-first templates run against
    all lines. -> (N,) int32 assignment, -1 = none.
    """
    n = ids.shape[0]
    n_tpl = len(templates)
    best = np.full((n,), n_tpl, np.int64)  # sentinel: no match
    if n == 0 or n_tpl == 0:
        return np.full((n,), -1, np.int32)

    buckets: dict[int, list[int]] = {}
    star_bucket: list[int] = []
    for k, tpl in enumerate(templates):
        if len(tpl) == 0:
            continue  # empty templates match nothing (host semantics)
        if int(tpl[0]) == STAR_ID:
            star_bucket.append(k)
        else:
            buckets.setdefault(int(tpl[0]), []).append(k)

    def run(line_sel: np.ndarray, tidx: list[int]) -> None:
        sub = wildcard_match_host(ids[line_sel], lens[line_sel], [templates[k] for k in tidx])
        any_m = sub.any(axis=1)
        if not any_m.any():
            return
        # tidx is ascending, argmax picks the first True -> lowest id in bucket
        cand = np.asarray(tidx, np.int64)[sub.argmax(axis=1)]
        rows = line_sel[any_m]
        best[rows] = np.minimum(best[rows], cand[any_m])

    first_tok = ids[:, 0] if ids.shape[1] else np.zeros((n,), np.int32)
    for f, tidx in buckets.items():
        sel = np.nonzero(first_tok == f)[0]
        if len(sel):
            run(sel, tidx)
    if star_bucket:
        run(np.arange(n), star_bucket)
    return np.where(best < n_tpl, best, -1).astype(np.int32)


_SHARDED_CACHE: dict[tuple, object] = {}


def wildcard_match_sharded(logs, lens, templates, t_lens, mesh: Mesh, axis: str = "data"):
    """Pod-scale matching: logs sharded over ``axis``, templates replicated.

    Pure data parallelism — the compiled module contains no collectives
    (asserted in tests), which is the point: matching scales linearly
    with chips, as the paper's multi-worker experiment scales with cores.

    The shard_map'd callable is cached per (mesh, axis): building it
    fresh each call made every invocation re-trace even on identical
    shapes (``tests/test_jitcache.py`` pins the trace count at 1 across
    repeated same-shape calls).
    """
    from .jitcache import record_trace

    key = (mesh, axis)
    fn = _SHARDED_CACHE.get(key)
    if fn is None:
        def local(lg, ln, tp, tl):
            record_trace("wildcard_match_sharded")
            return _wildcard_match(lg, ln[:, 0], tp, tl, interpret=interpret())

        fn = jax.jit(jax.shard_map(
            local,
            mesh=mesh,
            in_specs=(P(axis, None), P(axis, None), P(None, None), P(None, None)),
            out_specs=P(axis, None),
            check_vma=False,
        ))
        _SHARDED_CACHE[key] = fn
    return fn(
        jnp.asarray(logs, jnp.int32),
        jnp.asarray(lens, jnp.int32).reshape(-1, 1),
        jnp.asarray(templates, jnp.int32),
        jnp.asarray(t_lens, jnp.int32).reshape(-1, 1),
    ).astype(bool)


# ------------------------------------------- fused match+extract (device)

def match_extract(ids: np.ndarray, lens: np.ndarray, templates: list[np.ndarray],
                  *, use_buckets: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Fused kernel path: one launch -> (assign (N,) int32 lowest-id
    matching template or -1, spans (N, n_slots, 2) int32).

    numpy in/out convenience over ``kernels.match_extract``; shapes are
    bucketed like ``wildcard_match``. Over-length lines are masked here
    (where the true width is known) rather than in the kernel.
    """
    ids = np.asarray(ids, np.int32)
    lens_np = np.asarray(lens, np.int32)
    n, t = ids.shape
    tmpl, tlens = pack_templates(templates)
    n_slots = max([1] + [int((np.asarray(tp) == STAR_ID).sum()) for tp in templates])
    if tmpl.shape[0] == 0 or n == 0:
        return np.full(n, -1, np.int32), np.zeros((n, n_slots, 2), np.int32)
    k, tt = tmpl.shape
    if use_buckets:
        nb, tb = bucket(n, 64), bucket(t, 32)
        kb, ttb = bucket(k, 16), bucket(tt, 16)
        record_call("match_extract", (nb, tb, kb, ttb))
        ids_p, lens_p = _pad_to(ids, (nb, tb)), _pad_to(lens_np, (nb,))
        tmpl_p = _pad_to(tmpl, (kb, ttb))
        tlens_p = np.pad(tlens, (0, kb - k), constant_values=-1)
    else:
        ids_p, lens_p, tmpl_p, tlens_p = ids, lens_np, tmpl, tlens
    assign, spans = _dispatch(
        "match_extract",
        jnp.asarray(ids_p), jnp.asarray(lens_p), jnp.asarray(tmpl_p),
        jnp.asarray(tlens_p), n_slots=n_slots)
    assign = np.asarray(assign[:n]).copy()
    spans = np.asarray(spans[:n]).copy()
    assign[lens_np > t] = -1  # truncated lines never match (host rule)
    return assign, spans


# ------------------------------------------ typed column codecs (device)

@spanned("dispatch.colcodec_transform")
def delta_zigzag(vals: np.ndarray, lens: np.ndarray, mode: np.ndarray,
                 *, use_buckets: bool = True) -> np.ndarray:
    """Batched typed-column transform (DESIGN.md §12): (R, C) int32
    columns + per-row length and mode (1 = delta, 2 = zigzag
    delta-of-delta, 3 = frame-of-reference) -> (R, C) uint32 payload
    values, exactly ``coltypes.transform_ints`` per row.

    The frame-of-reference row minimum is computed here (over the valid
    prefix) and handed to the kernel as data. Shapes are bucketed to
    powers of two so the streaming encode path reuses one executable per
    bucket; callers gate magnitudes with ``coltypes.KERNEL_SAFE``.
    """
    vals = np.asarray(vals, np.int32)
    lens_np = np.asarray(lens, np.int32)
    mode_np = np.asarray(mode, np.int32)
    r, width = vals.shape
    if r == 0:
        return np.zeros((0, width), np.uint32)
    pos_ok = np.arange(width)[None, :] < lens_np[:, None]
    ref = np.where(pos_ok, vals, np.iinfo(np.int32).max).min(axis=1)
    ref = np.where((mode_np == 3) & (lens_np > 0), ref, 0).astype(np.int32)
    if use_buckets:
        rb, cb = bucket(r, 8), bucket(width, 128)
        record_call("delta_zigzag", (rb, cb))
        out = _dispatch(
            "colcodec_transform",
            jnp.asarray(_pad_to(vals, (rb, cb))),
            jnp.asarray(_pad_to(lens_np, (rb,))),
            jnp.asarray(_pad_to(mode_np, (rb,))),
            jnp.asarray(_pad_to(ref, (rb,))),
        )[:r, :width]
    else:
        out = _dispatch(
            "colcodec_transform",
            jnp.asarray(vals), jnp.asarray(lens_np), jnp.asarray(mode_np),
            jnp.asarray(ref))
    return np.asarray(out)


# ----------------------------------------- compressed-domain scan (device)

@spanned("dispatch.distinct_counts")
def distinct_counts(inv, n_bins: int, weights=None, *,
                    prefer_host: bool | None = None) -> np.ndarray:
    """Weighted histogram of a distinct-row inverse index (DESIGN.md
    §14): ``out[b] = sum(weights[i] for inv[i] == b)`` -> (n_bins,) int32.
    ``weights=None`` counts occurrences. Bit-identical on every tier.

    ``prefer_host`` defaults to ``not on_tpu()`` — benchmark honesty: in
    interpret mode the Pallas grid loop is pure-Python-slow, and routing
    the aggregation wall clock through it would report numbers that are
    neither host nor accelerator performance. On a TPU the kernel path is
    the default; tests force ``prefer_host=False`` to exercise the full
    dispatch chain.
    """
    inv_np = np.asarray(inv, np.int64)
    n = inv_np.shape[0]
    w_np = np.ones(n, np.int32) if weights is None \
        else np.asarray(weights, np.int32)
    if prefer_host is None:
        prefer_host = not on_tpu()
    if prefer_host or n == 0 or n_bins == 0:
        return _distinct_counts_host(inv_np, w_np, n_bins)
    nb, db = bucket(n, 256), bucket(n_bins, 128)
    record_call("distinct_counts", (nb, db))
    inv_p = np.pad(inv_np.astype(np.int32), (0, nb - n), constant_values=-1)
    w_p = np.pad(w_np, (0, nb - n))
    out = _dispatch("distinct_counts", jnp.asarray(inv_p), jnp.asarray(w_p), db)
    return np.asarray(out)[:n_bins].astype(np.int32)


# --------------------------------------------- byte tokenizer (device)

DEFAULT_DELIMITERS = " \t,;:="


def pack_lines(lines: list[str], *, use_buckets: bool = True) -> tuple[np.ndarray, np.ndarray, list[bytes]]:
    """utf-8 encode + pad lines into a (N, B) uint8 block.

    With ``use_buckets`` BOTH axes are bucketed — padding the row count
    here (outside the jit boundary) is what lets drifting batch sizes
    share one compiled tokenizer executable; the kernel's own padding
    happens inside the traced function, where it cannot help the cache.
    Padded rows have length 0 and emit no tokens, so callers may simply
    ignore rows >= len(lines).
    """
    enc = [l.encode("utf-8", "surrogateescape") for l in lines]
    n = len(enc)
    blens = np.fromiter((len(e) for e in enc), np.int32, n)
    # +1 guarantees >= one trailing pad byte per row, so token runs never
    # merge across rows when host code scans the flattened mask
    width = int(blens.max(initial=1)) + 1
    rows = n
    if use_buckets:
        width = bucket(width, 64)
        rows = bucket(n, 256)
        blens = np.pad(blens, (0, rows - n))
    blocks = np.zeros((rows, width), np.uint8)
    for i, e in enumerate(enc):
        blocks[i, : len(e)] = np.frombuffer(e, np.uint8)
    return blocks, blens, enc


def device_tokenize(lines: list[str], delimiters: str = DEFAULT_DELIMITERS):
    """Kernel-backed ``tokenize`` over a batch -> [(tokens, delims), ...].

    Runs the byte tokenizer kernel for the boundary masks, then slices
    token/delimiter strings on the host. ``reassemble`` of each result is
    byte-identical to the input line (property-tested), and tokens agree
    with ``core.tokenizer.tokenize`` for ASCII delimiter sets.
    """
    if not lines:
        return []
    blocks, blens, enc = pack_lines(lines)
    record_call("tokenize_hash", blocks.shape)
    pws = hash_powers(blocks.shape[1])
    delims = tuple(ord(c) for c in delimiters)
    mask, starts, _, _ = _dispatch(
        "tokenize_hash",
        jnp.asarray(blocks), jnp.asarray(blens),
        jnp.asarray(pws[0][0]), jnp.asarray(pws[1][0]), delims=delims)
    mask = np.asarray(mask, bool)
    out = []
    for i, e in enumerate(enc):
        ts, te = runs_of(mask[i, : len(e)])
        toks = [e[s:t2].decode("utf-8", "surrogateescape") for s, t2 in zip(ts, te)]
        bounds = np.concatenate([[0], np.stack([ts, te], 1).ravel(), [len(e)]]) \
            if len(ts) else np.array([0, len(e)])
        dl = [e[bounds[2 * j]:bounds[2 * j + 1]].decode("utf-8", "surrogateescape")
              for j in range(len(ts) + 1)]
        out.append((toks, dl))
    return out


def device_encode_batch(contents: list[str], vocab, max_len: int,
                        delimiters: str = DEFAULT_DELIMITERS,
                        *, tight: bool = True) -> tuple[np.ndarray, np.ndarray]:
    """Kernel-backed twin of ``Vocab.encode_batch``: tokenize + hash on
    device, intern only unseen 64-bit (2x uint32) hashes on the host.

    -> (ids (N, W) int32, lens (N,) int32), equal to the host path on a
    same-state vocab (property-tested).
    """
    n = len(contents)
    if n == 0:
        return np.zeros((0, 1), np.int32), np.zeros(0, np.int32)
    blocks, blens, enc = pack_lines(contents)
    width_b = blocks.shape[1]
    record_call("tokenize_hash", blocks.shape)
    pws = hash_powers(width_b)
    delims = tuple(ord(c) for c in delimiters)
    mask, starts, pref1, pref2 = _dispatch(
        "tokenize_hash",
        jnp.asarray(blocks), jnp.asarray(blens),
        jnp.asarray(pws[0][0]), jnp.asarray(pws[1][0]), delims=delims)
    mask = np.asarray(mask, bool)
    starts_m = np.asarray(starts, bool)
    pref1 = np.asarray(pref1)
    pref2 = np.asarray(pref2)

    rows, scol = np.nonzero(starts_m)             # token starts, row-major
    # token ends from the flattened mask (rows never merge: pack_lines
    # guarantees a trailing pad byte per row)
    ecol = runs_of(mask.ravel())[1] - rows * mask.shape[1]
    lens = np.bincount(rows, minlength=n).astype(np.int32)
    width = max(1, min(max_len, int(lens.max(initial=1)))) if tight else max_len
    col = np.arange(len(rows)) - np.concatenate([[0], np.cumsum(lens)])[rows]
    keep = col < width
    rows, scol, ecol, col = rows[keep], scol[keep], ecol[keep], col[keep]

    def lane(pref, pw_inv):
        lo = np.where(scol > 0,
                      pref[rows, np.maximum(scol - 1, 0)], np.uint32(0))
        return (pref[rows, ecol - 1] - lo) * pw_inv[scol]
    h = lane(pref1, pws[0][1]).astype(np.uint64) << np.uint64(32)
    h |= lane(pref2, pws[1][1]).astype(np.uint64)
    tok_of, fo = first_occurrence_unique(h)
    table = [enc[rows[i]][scol[i]:ecol[i]].decode("utf-8", "surrogateescape")
             for i in fo.tolist()]
    vid = np.fromiter((vocab.id(t) for t in table), np.int32, len(table)) \
        if table else np.zeros(0, np.int32)
    ids = np.zeros((n, width), np.int32)
    ids[rows, col] = vid[tok_of]
    return ids, lens


# re-export oracles for tests
simcount_ref = ref.simcount_ref
wildcard_match_ref = ref.wildcard_match_ref
match_extract_ref = ref.match_extract_ref
tokenize_hash_ref = ref.tokenize_hash_ref
colcodec_transform_ref = ref.colcodec_transform_ref
distinct_counts_ref = ref.distinct_counts_ref

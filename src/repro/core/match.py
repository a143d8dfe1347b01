"""Batched wildcard-template matching (paper §III-D), TPU-adapted.

The paper walks a prefix tree per log line. On a TPU we want a dense,
branch-free formulation: for one template ``t_1..t_m`` and a line
``x_1..x_n`` define the reachability DP

    M[i, 0] = (i == 0)
    M[i, j] = M[i-1, j-1] and (x_i == t_j)            if t_j literal
    M[i, j] = OR_{i' < i} M[i', j-1]                  if t_j == '*'
              (= shift1(cummax(M[:, j-1])))           ('*' absorbs >= 1)

and the line matches iff ``M[n, m]``. Each template column is one
vectorized update over a whole *block of lines*, so the work is
(lines x template positions) vector ops — this is exactly what
``repro.kernels.wildcard_match`` tiles onto VMEM. The numpy path here is
the host fallback and the oracle for the Pallas kernel.

Matching only needs the *final* DP column, so ``match_one_template``
carries a rolling (N, T+1) column instead of materializing the full
(N, T+1, m+1) tensor; the full tensor is only built for the span
backtrack in ``extract_spans``.

Parameter spans are recovered by a vectorized backtrack (later stars take
the shortest span; any valid alignment is lossless — the tie-break only
fixes determinism).

``match_first`` assigns each line the lowest-id matching template —
the production-canonical assignment. On the host, first-token bucketing
(the trie's root-level pruning) cuts the candidate template set per
line; the kernel path matches the dense grid in one launch
(``ops.match_first_dense``). Exact duplicate (ids, len) rows are
collapsed before either runs — matching is deterministic per row, so
the result is identical, but real logs are dominated by repeats and
only pay for distinct lines.

Rows are matched at the width of their own token bucket (``width_groups``):
one multi-line record of 400 tokens must not make every one-line record
of its chunk pay for a 512-token DP. Rows wider than ``NARROW_TOKENS``
run in the ``ise.match.wide`` span.
"""

from __future__ import annotations

import contextlib

import numpy as np

from .timing import span
from .tokenizer import STAR_ID

CHUNK = 4096  # lines per DP chunk (bounds the M tensor)
DEDUP_MIN_LINES = 512  # below this the np.unique sort costs more than it saves
NARROW_TOKENS = 128  # the narrowest token bucket: one-line records fit it


def width_groups(lens: np.ndarray, width: int) -> list[tuple[int, np.ndarray]]:
    """Rows by token bucket: ``[(w, rows), ...]`` with ``w`` the tight
    width of ``rows`` (the longest of them), for buckets of
    ``NARROW_TOKENS``, then doubling. Rows longer than ``width`` were
    truncated (over the token budget): they match nothing and are in
    no group."""
    lens = np.asarray(lens, np.int64)
    fit = lens <= width
    # 0 up to NARROW_TOKENS tokens, then 1, 2, ... per doubling
    over = np.maximum(lens, NARROW_TOKENS + 1) - 1
    bucket = np.where(lens <= NARROW_TOKENS, 0,
                      np.floor(np.log2(over)).astype(np.int64) - (NARROW_TOKENS.bit_length() - 2))
    out = []
    for b in np.unique(bucket[fit]).tolist():
        rows = np.flatnonzero(fit & (bucket == b))
        out.append((max(1, int(lens[rows].max())), rows))
    return out


def _dp_columns(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """All DP columns for one template over a chunk of lines.

    ids: (N, T) int32, lens: (N,), template: (m,) id seq (no PAD).
    Returns M: (N, T+1, m+1) bool. Only used by the span backtrack —
    matching uses the rolling-column variant below.
    """
    n, t = ids.shape
    m = len(template)
    M = np.zeros((n, t + 1, m + 1), dtype=bool)
    M[:, 0, 0] = True
    pos = np.arange(1, t + 1)
    valid = pos[None, :] <= lens[:, None]  # (N, T) position i exists
    for j in range(1, m + 1):
        tj = int(template[j - 1])
        prev = M[:, :, j - 1]
        if tj == STAR_ID:
            # OR over strict prefix: shift-by-1 of running-OR
            run = np.logical_or.accumulate(prev, axis=1)
            M[:, 1:, j] = run[:, :-1]
        else:
            M[:, 1:, j] = prev[:, :-1] & (ids == tj)
        M[:, 1:, j] &= valid
    return M


def _final_col(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """Final DP column (N, T+1) after consuming the whole template.

    Rolling-column version of ``_dp_columns`` — O(N*T) live memory
    instead of O(N*T*m)."""
    n, t = ids.shape
    col = np.zeros((n, t + 1), dtype=bool)
    col[:, 0] = True
    valid = np.arange(1, t + 1)[None, :] <= lens[:, None]
    for tj in template:
        tj = int(tj)
        new = np.zeros_like(col)
        if tj == STAR_ID:
            run = np.logical_or.accumulate(col, axis=1)
            new[:, 1:] = run[:, :-1]
        else:
            new[:, 1:] = col[:, :-1] & (ids == tj)
        new[:, 1:] &= valid
        col = new
    return col


def match_one_template_dp(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """(N,) bool via the rolling-column DP — the oracle for the fused
    anchor path below (and the shape the Pallas kernel reproduces)."""
    out = np.zeros((ids.shape[0],), bool)
    t = ids.shape[1]
    lens_c = np.minimum(lens, t)
    for s in range(0, ids.shape[0], CHUNK):
        sl = slice(s, min(s + CHUNK, ids.shape[0]))
        col = _final_col(ids[sl], lens_c[sl], template)
        out[sl] = col[np.arange(sl.stop - sl.start), lens_c[sl]]
    # over-length lines never match (their tail was truncated)
    out &= lens <= t
    return out


# ------------------------------------------------- fused anchor matching
#
# A template is literal runs anchored around stars:
#
#     P *1 L1 *2 L2 ... *k S      (prefix P, mids L1..Lk-1, suffix S)
#
# Matching and span extraction reduce to run placement (DESIGN.md §10):
# the DP's reachability set after "P *1 L1 ... Lj" has a closed form —
# an occurrence of Lj ending at e is reachable iff e >= minreach_j,
# where minreach_j is the LEFTMOST valid end (each star absorbs >= 1).
# A forward pass computes the minreach chain (match test), a backward
# pass takes the RIGHTMOST valid occurrence below the running cursor —
# exactly the DP backtrack's "largest i' <= i-1" tie-break, so spans are
# bit-identical to ``extract_spans_dp``. Cost: O(N * T * sum |runs|)
# vectorized compares instead of the O(N * T * m) DP with its (N, T, m)
# backtrack tensor, fusing match + span extraction into one pass.


def template_units(template: np.ndarray) -> tuple[np.ndarray, list[np.ndarray], np.ndarray, int]:
    """Decompose into (prefix, mids, suffix, n_stars); literal runs are
    id arrays (mids possibly empty for consecutive stars)."""
    arr = np.asarray(template)
    stars = np.flatnonzero(arr == STAR_ID)
    if len(stars) == 0:
        return arr, [], arr[:0], 0
    prefix = arr[: stars[0]]
    suffix = arr[stars[-1] + 1:]
    mids = [arr[stars[i] + 1: stars[i + 1]] for i in range(len(stars) - 1)]
    return prefix, mids, suffix, len(stars)


def _occ_ends(ids: np.ndarray, lit: np.ndarray) -> np.ndarray:
    """(N, T+1) bool: does an occurrence of literal run ``lit`` END at
    position e (tokens [e-|lit|, e) equal lit). Empty runs occur at
    every position. PAD can never equal a literal, so occurrences are
    automatically confined to the line's real tokens."""
    n, t = ids.shape
    L = len(lit)
    occ = np.zeros((n, t + 1), bool)
    if L == 0:
        occ[:] = True
        return occ
    if L > t:
        return occ
    if L > 16 and n * (t - L + 1) * L <= 1 << 22:
        # a long run (a stack trace's frames): one compare over the
        # (N, T-L+1, L) window view instead of L Python-level passes
        win = np.lib.stride_tricks.sliding_window_view(ids, L, axis=1)
        occ[:, L:] = (win == np.asarray(lit, ids.dtype)).all(axis=2)
        return occ
    acc = ids[:, :t - L + 1] == int(lit[0])
    for k in range(1, L):
        acc = acc & (ids[:, k:t - L + 1 + k] == int(lit[k]))
    occ[:, L:] = acc
    return occ


def match_extract_one(
    ids: np.ndarray,
    lens: np.ndarray,
    template: np.ndarray,
    *,
    want_spans: bool = False,
) -> tuple[np.ndarray, np.ndarray | None]:
    """Fused match + parameter-span extraction for one template.

    -> (ok (N,) bool, spans (N, n_stars, 2) int32 or None). Spans rows
    are only meaningful where ``ok``; bit-identical to
    ``match_one_template_dp`` / ``extract_spans_dp``.
    """
    n, t = ids.shape
    prefix, mids, suffix, k = template_units(np.asarray(template))
    m = len(template)
    spans = np.zeros((n, k, 2), np.int32) if want_spans else None
    p, q = len(prefix), len(suffix)
    min_len = (m - k) + k  # literals + one token per star
    if n == 0 or min_len > t or (k == 0 and m > t):
        return np.zeros(n, bool), spans

    lens64 = lens.astype(np.int64)
    ok = lens64 <= t
    if k == 0:
        ok &= lens64 == m
        if m:
            ok &= (ids[:, :m] == np.asarray(template)[None, :]).all(axis=1)
        return ok, spans

    ok &= lens64 >= min_len
    if p:
        ok &= (ids[:, :p] == prefix[None, :]).all(axis=1)
    if q:
        # suffix at positions [len-q, len) — clip gathers for short lines
        # (those rows are already False via the min_len check)
        base = np.maximum(lens64 - q, 0)[:, None] + np.arange(q)[None, :]
        ok &= (np.take_along_axis(ids, np.minimum(base, t - 1), axis=1)
               == suffix[None, :]).all(axis=1)

    pos = np.arange(t + 1)
    # forward: leftmost valid end of each mid run (the reachability frontier)
    minr = np.full(n, p, np.int64)
    occs = []
    for lit in mids:
        occ = _occ_ends(ids, lit)
        occs.append(occ)
        gate = occ & (pos[None, :] >= (minr + 1 + len(lit))[:, None])
        has = gate.any(axis=1)
        ok &= has
        minr = np.where(has, gate.argmax(axis=1), t)  # first True
    ok &= minr <= lens64 - q - 1

    if want_spans and ok.any():
        i = lens64 - q  # cursor: end of the current star's span
        for j in range(k - 1, -1, -1):
            if j == 0:
                e = np.full(n, p, np.int64)
            else:
                occ = occs[j - 1]
                gate = occ & (pos[None, :] <= (i - 1)[:, None])
                e = t - np.argmax(gate[:, ::-1], axis=1)  # last True
            spans[:, j, 0] = e
            spans[:, j, 1] = i
            i = e - (len(mids[j - 1]) if j else 0)
    return ok, spans


def match_one_template(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """(N,) bool: does each line match this template (fused anchor path)."""
    return match_extract_one(ids, lens, template)[0]


def match_first(
    ids: np.ndarray,
    lens: np.ndarray,
    templates: list[np.ndarray],
    use_kernel: bool | None = False,
    dedup: bool = True,
) -> np.ndarray:
    """Assign each line the lowest-id matching template (-1 = none).

    On the numpy path templates are bucketed by first token (literal or
    '*') like the trie root, so each line only runs the DP against
    plausible candidates; the kernel path makes one launch of all lines
    against all templates per token bucket. With ``dedup`` (default)
    duplicate (ids, len) rows are matched once and the assignment is
    broadcast back — bit-identical results, and the DP only pays for
    distinct lines. ``use_kernel=None`` runs the Pallas
    kernel path on a TPU backend and the numpy path elsewhere. Wider
    than ``NARROW_TOKENS``, each token bucket (``width_groups``) is
    matched at its own width against the templates that fit it (a
    template of m units needs at least m tokens).
    """
    n = ids.shape[0]
    if not templates or n == 0:
        return np.full((n,), -1, np.int32)
    if ids.shape[1] <= NARROW_TOKENS:
        return _match_first(ids, lens, templates, use_kernel, dedup)
    assign = np.full((n,), -1, np.int32)
    t_len = np.array([len(t) for t in templates])
    for w, rows in width_groups(lens, ids.shape[1]):
        fit = np.flatnonzero(t_len <= w)
        if not len(fit):
            continue
        with span("ise.match.wide") if w > NARROW_TOKENS else contextlib.nullcontext():
            a = _match_first(ids[rows, :w], lens[rows], [templates[k] for k in fit],
                             use_kernel, dedup)
        assign[rows] = np.where(a >= 0, fit[np.maximum(a, 0)], -1)
    return assign


def _match_first(ids, lens, templates, use_kernel, dedup) -> np.ndarray:
    n = ids.shape[0]
    assign = np.full((n,), -1, np.int32)
    if dedup and n >= DEDUP_MIN_LINES:
        # memcmp-sort on a void view of the packed rows — much cheaper
        # than np.unique(axis=0)'s per-column lexsort; only the grouping
        # matters (matching is deterministic per row), not the order
        key = np.ascontiguousarray(np.column_stack([lens.astype(np.int32), ids]))
        rows = key.view(np.dtype((np.void, key.shape[1] * key.itemsize))).ravel()
        _, first, inv = np.unique(rows, return_index=True, return_inverse=True)
        if len(first) < n:
            sub = _match_first(ids[first], lens[first], templates, use_kernel, False)
            return sub[inv].astype(np.int32)

    if use_kernel is not False:
        from repro.kernels import ops as kops

        if use_kernel or kops.on_tpu():
            return kops.match_first_dense(ids, lens, templates)

    first_tok = ids[:, 0]
    for k, tpl in enumerate(templates):
        if len(tpl) == 0:
            continue
        todo = assign < 0
        if int(tpl[0]) != STAR_ID:
            todo &= first_tok == int(tpl[0])
        if not todo.any():
            continue
        idx = np.nonzero(todo)[0]
        ok = match_one_template(ids[idx], lens[idx], tpl)
        assign[idx[ok]] = k
    return assign


def extract_spans(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """Parameter spans for lines *known to match* ``template``.

    Returns spans (N, n_stars, 2) int32 — token ranges [s, e) absorbed
    by each '*' in template order, via the fused anchor pass
    (bit-identical to the DP backtrack in ``extract_spans_dp``).
    """
    return match_extract_one(ids, lens, template, want_spans=True)[1]


def extract_spans_dp(ids: np.ndarray, lens: np.ndarray, template: np.ndarray) -> np.ndarray:
    """DP-backtrack oracle for ``extract_spans`` (full M tensor)."""
    n, t = ids.shape
    m = len(template)
    stars = [j for j in range(m) if int(template[j]) == STAR_ID]
    spans = np.zeros((n, len(stars), 2), dtype=np.int32)
    if n == 0 or not stars:
        return spans
    for s0 in range(0, n, CHUNK):
        sl = slice(s0, min(s0 + CHUNK, n))
        M = _dp_columns(ids[sl], lens[sl], template)
        i = lens[sl].astype(np.int64).copy()  # current log position per line
        star_i = len(stars) - 1
        pos = np.arange(t + 1)
        for j in range(m, 0, -1):
            if int(template[j - 1]) != STAR_ID:
                i -= 1
                continue
            # largest i' <= i-1 with M[i', j-1] true
            mask = M[:, :, j - 1] & (pos[None, :] <= (i - 1)[:, None])
            ip = t - np.argmax(mask[:, ::-1], axis=1)
            spans[sl, star_i, 0] = ip
            spans[sl, star_i, 1] = i
            i = ip
            star_i -= 1
    return spans

"""Throughput of the logzip hot-spot kernels (interpret mode on CPU — the
numbers calibrate RELATIVE costs; absolute TPU throughput needs hardware).

Compares: python trie, numpy DP matcher, Pallas wildcard_match
(interpret), and numpy vs Pallas simcount, on a realistic template mix.
"""

from __future__ import annotations

import time


from repro.core.match import match_first
from repro.core.tokenizer import Vocab, tokenize
from repro.core.trie import PrefixTree
from repro.data.loggen import generate_lines
from repro.kernels import ops


def _prep(n_lines=20000):
    v = Vocab()
    lines = generate_lines("Spark", n_lines, seed=3)
    toks = [tokenize(l.split(": ", 1)[-1])[0] for l in lines]
    ids, lens = v.encode_batch(toks, 48)
    # build templates from a sample via ISE
    from repro.core.ise import ISEConfig, iterative_structure_extraction

    res = iterative_structure_extraction(ids[:4000], lens[:4000], vocab_size=len(v),
                                         cfg=ISEConfig(min_sample=300))
    return ids, lens, res.templates


def run(n_lines=20000) -> list[dict]:
    ids, lens, templates = _prep(n_lines)
    rows = []

    t0 = time.time()
    tree = PrefixTree()
    for i, t in enumerate(templates):
        tree.insert(t, i)
    a_trie, _ = tree.match_batch(ids, lens)
    rows.append({"impl": "trie (python)", "lines_per_s": len(ids) / (time.time() - t0)})

    t0 = time.time()
    a_np = match_first(ids, lens, templates, use_kernel=False)
    rows.append({"impl": "DP matcher (numpy)", "lines_per_s": len(ids) / (time.time() - t0)})

    t0 = time.time()
    a_k = match_first(ids, lens, templates, use_kernel=True)
    rows.append({"impl": "wildcard_match (pallas interpret)", "lines_per_s": len(ids) / (time.time() - t0)})

    assert ((a_np >= 0) == (a_trie >= 0)).all()
    assert (a_np == a_k).all()

    tm, tl = ops.pack_templates(templates)
    t0 = time.time()
    ops.simcount(ids[:8192], tm).block_until_ready()
    rows.append({"impl": "simcount (pallas interpret)", "lines_per_s": 8192 / (time.time() - t0)})
    rows.extend(run_fused_kernels(n_lines))
    return rows


def run_fused_kernels(n_lines=20000) -> list[dict]:
    """Microbenchmarks for the ISSUE 3 device kernels vs their host
    references: the byte tokenizer/hasher and the fused match+extract
    pass, reported as bytes/sec over the raw input they consume."""
    import jax.numpy as jnp

    from repro.core.tokenizer import Vocab, tokenize_batch
    from repro.kernels.tokenize import hash_powers, tokenize_hash

    lines = [l.split(": ", 1)[-1] for l in generate_lines("Spark", n_lines, seed=3)]
    raw_bytes = sum(len(l.encode("utf-8", "surrogateescape")) for l in lines)
    rows: list[dict] = []

    # --- tokenizer: host vectorized grid vs device kernel
    t0 = time.time()
    tokenize_batch(lines, Vocab(), 48)
    host_s = time.time() - t0
    rows.append({"impl": "tokenize_batch (host numpy)",
                 "bytes_per_s": raw_bytes / host_s, "lines_per_s": n_lines / host_s})

    blocks, blens, _ = ops.pack_lines(lines)
    pws = hash_powers(blocks.shape[1])
    delims = tuple(ord(c) for c in " \t,;:=")
    args = (jnp.asarray(blocks), jnp.asarray(blens),
            jnp.asarray(pws[0][0]), jnp.asarray(pws[1][0]))
    tokenize_hash(*args, delims=delims, interpret=ops.interpret())  # warm the jit cache
    t0 = time.time()
    out = tokenize_hash(*args, delims=delims, interpret=ops.interpret())
    out[0].block_until_ready()
    dev_s = time.time() - t0
    rows.append({"impl": "tokenize_hash (pallas interpret)",
                 "bytes_per_s": raw_bytes / dev_s, "lines_per_s": n_lines / dev_s})

    # --- fused match+extract: host anchor pass vs device kernel
    v = Vocab()
    grid = tokenize_batch(lines, v, 48)
    from repro.core.ise import ISEConfig, iterative_structure_extraction
    from repro.core.match import extract_spans, match_first

    res = iterative_structure_extraction(grid.ids[:4000], grid.lens[:4000],
                                         vocab_size=len(v),
                                         cfg=ISEConfig(min_sample=300))
    t0 = time.time()
    a = match_first(grid.ids, grid.lens, res.templates, use_kernel=False)
    for g in sorted(set(a[a >= 0].tolist())):
        rws = (a == g).nonzero()[0]
        extract_spans(grid.ids[rws], grid.lens[rws], res.templates[g])
    host_s = time.time() - t0
    rows.append({"impl": "match+extract (host fused anchors)",
                 "bytes_per_s": raw_bytes / host_s, "lines_per_s": n_lines / host_s})

    sub = min(n_lines, 4096)  # interpret mode: keep the device pass bounded
    # warm at the SAME shape bucket as the timed call, or the timing
    # window would include a full re-trace
    ops.match_extract(grid.ids[:sub], grid.lens[:sub], res.templates)
    t0 = time.time()
    ops.match_extract(grid.ids[:sub], grid.lens[:sub], res.templates)
    dev_s = time.time() - t0
    frac = sub / n_lines
    rows.append({"impl": "match_extract (pallas interpret)",
                 "bytes_per_s": raw_bytes * frac / dev_s, "lines_per_s": sub / dev_s})
    return rows

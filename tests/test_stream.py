"""Streaming sessions + LZJS container: streaming==batch losslessness,
EventID/ParaID stability across chunks, footer random access, O(1)
append, and corrupt/truncated-archive errors for all three magics."""

import io

import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.codec import LogzipConfig, compress, decompress
from repro.core.ise import ISEConfig
from repro.core.parallel import compress_parallel, decompress_parallel
from repro.core.stream import (
    LZJSReader,
    StreamingCompressor,
    decompress_lzjs,
    iter_stream,
)
from repro.core.templates import TemplateStore, extract_templates
from repro.data.loggen import DATASETS, generate_lines
from strategies import SPARK_FORMAT, records

CFG_FAST = ISEConfig(min_sample=100, max_iters=2)

line_text = st.text(alphabet=st.characters(blacklist_categories=("Cs",)), max_size=80).filter(
    lambda s: "\n" not in s
)


record_text = records(line_text.filter(lambda s: len(s) < 40))


def _stream_blob(lines, cfg, **kw):
    buf = io.BytesIO()
    with StreamingCompressor(buf, cfg, **kw) as sc:
        sc.feed(lines)
        summary = sc.close()
    return buf.getvalue(), summary


# ------------------------------------------------------ streaming == batch

@settings(max_examples=25, deadline=None)
@given(st.lists(line_text, max_size=40), st.integers(1, 17))
def test_streaming_equals_batch_property(lines, chunk_lines):
    """ANY line list through the session decodes to the same lines as
    batch compress() — losslessness is chunking-invariant."""
    cfg = LogzipConfig(level=3, format="<Date> <Time> <Level> <Component>: <Content>",
                       ise=ISEConfig(min_sample=20, max_iters=2))
    batch = decompress(compress(lines, cfg))
    blob, _ = _stream_blob(lines, cfg, chunk_lines=chunk_lines)
    assert decompress_lzjs(blob) == batch == lines


@settings(max_examples=25, deadline=None)
@given(st.lists(record_text, max_size=30), st.integers(1, 17))
def test_streaming_equals_batch_records_property(records_, chunk_lines):
    """Multi-line records (header line plus trace lines) through the
    session decode to the same records as batch compress()."""
    cfg = LogzipConfig(level=3, format=SPARK_FORMAT, ise=ISEConfig(min_sample=20, max_iters=2))
    batch = decompress(compress(records_, cfg))
    blob, _ = _stream_blob(records_, cfg, chunk_lines=chunk_lines)
    assert decompress_lzjs(blob) == batch == records_


@pytest.mark.parametrize("level", [1, 2, 3])
def test_streaming_roundtrip_levels(level, spark_lines):
    cfg = LogzipConfig(level=level, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:1200]
    blob, summary = _stream_blob(lines, cfg, chunk_lines=250)
    assert summary["n_chunks"] == 5
    assert decompress_lzjs(blob) == lines


def test_streaming_chunk_bytes_budget(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:600]
    blob, summary = _stream_blob(lines, cfg, chunk_lines=10**9, chunk_bytes=16 << 10)
    assert summary["n_chunks"] > 1  # the byte budget cut chunks
    assert decompress_lzjs(blob) == lines


def test_streaming_empty_session():
    blob, summary = _stream_blob([], LogzipConfig(ise=CFG_FAST))
    assert summary == {"n_lines": 0, "n_chunks": 0, "n_templates": 0, "n_params": 0,
                       "verbatim_records": 0, "wide_records_templated": 0}
    assert decompress_lzjs(blob) == []
    assert list(iter_stream(io.BytesIO(blob))) == []


def test_iter_stream_matches_reader(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:900]
    blob, _ = _stream_blob(lines, cfg, chunk_lines=200)
    assert list(iter_stream(io.BytesIO(blob))) == lines


# --------------------------------------------------------- EventID stability

def test_eventids_stable_across_chunks(spark_lines):
    """One template string <-> one global id for the whole session: the
    shared store makes EventIDs stable across every chunk."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    blob, _ = _stream_blob(spark_lines, cfg, chunk_lines=500)
    rd = LZJSReader(io.BytesIO(blob))
    id_by_template: dict[int, str] = {}
    for k in range(len(rd)):
        s = rd.read_structured_chunk(k)
        used = s["stream"]["used"]
        for g, tpl_str in zip(used, s["templates"]):
            assert id_by_template.setdefault(g, tpl_str) == tpl_str
        ev = rd.read_events(k)
        assert set(int(e) for e in ev) <= set(used)
    assert len(id_by_template) > 1


def test_eventids_stable_with_seed_store(spark_lines):
    """Seeding two sessions with the same store keeps shared-template ids
    identical across independent streams (paper §III-E, stream form)."""
    fmt = DATASETS["Spark"]["format"]
    store = extract_templates(spark_lines, fmt, ISEConfig(min_sample=300))
    n_seed = len(store)
    cfg = LogzipConfig(level=3, format=fmt, ise=CFG_FAST)

    lines_a = list(generate_lines("Spark", 900, seed=21))
    lines_b = list(generate_lines("Spark", 900, seed=22))
    blob_a, _ = _stream_blob(lines_a, cfg, chunk_lines=300,
                             store=TemplateStore(store.templates))
    blob_b, _ = _stream_blob(lines_b, cfg, chunk_lines=300,
                             store=TemplateStore(store.templates))
    rd_a, rd_b = LZJSReader(io.BytesIO(blob_a)), LZJSReader(io.BytesIO(blob_b))
    assert decompress_lzjs(blob_a) == lines_a
    assert rd_a.templates[:n_seed] == rd_b.templates[:n_seed] == store.templates


# ------------------------------------------------------------ random access

def test_random_access_decodes_only_covering_chunks(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:2000]
    blob, _ = _stream_blob(lines, cfg, chunk_lines=250)
    rd = LZJSReader(io.BytesIO(blob))
    assert len(rd) == 8
    got = rd.read_range(615, 700)
    assert got == lines[615:1315]
    # lines 615..1314 live in chunks 2..5 -> exactly 4 decodes
    assert rd.covering_chunks(615, 700) == [2, 3, 4, 5]
    assert rd.chunks_decoded == 4


def test_random_access_edges(spark_lines):
    cfg = LogzipConfig(level=2, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:900]
    blob, _ = _stream_blob(lines, cfg, chunk_lines=300)
    rd = LZJSReader(io.BytesIO(blob))
    assert rd.read_range(0, 1) == lines[:1]
    assert rd.read_range(899, 50) == lines[899:]
    assert rd.read_range(300, 300) == lines[300:600]
    assert rd.chunks_decoded == 3


# ------------------------------------------------------------------ append

def test_append_extends_session(tmp_path, spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    first, second = spark_lines[:700], spark_lines[700:1400]
    with StreamingCompressor(path, cfg, chunk_lines=250) as sc:
        sc.feed(first)
    with StreamingCompressor(path, cfg, chunk_lines=250, append=True) as sc:
        sc.feed(second)
    rd = LZJSReader(path)
    assert rd.n_lines == 1400
    assert len(rd) == 6  # 3 chunks per half
    assert rd.read_all() == first + second
    rd.close()


def test_append_inherits_container_config(tmp_path, spark_lines):
    """append with cfg=None must reuse the container's format — losing it
    compresses headers as content and fragments the session store."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=300) as sc:
        sc.feed(spark_lines[:600])
    n_before = len(LZJSReader(path).templates)
    with StreamingCompressor(path, chunk_lines=300, append=True) as sc:
        assert sc.cfg.format == DATASETS["Spark"]["format"]
        assert sc.cfg.level == 3
        sc.feed(spark_lines[:600])
    rd = LZJSReader(path)
    # same lines, same format -> at most a couple of previously-verbatim
    # oddballs get promoted; losing the format would add dozens (every
    # header permutation becomes content)
    assert len(rd.templates) <= n_before + 3
    assert rd.read_all() == spark_lines[:600] * 2
    rd.close()


def test_append_accepts_superset_store_id_stably(tmp_path, spark_lines):
    """A store that grew BEYOND the container's templates (id-stable
    prefix) is legal append input: the extra templates ride the first
    new chunk's delta frame, so every reader's accumulated count stays
    aligned with the recorded bases and the grown store's global ids
    keep meaning the same templates."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=300) as sc:
        sc.feed(spark_lines[:600])
    base = LZJSReader(path).templates
    grown = TemplateStore(base)
    extra_id = grown.add(("extra", None, "template"))
    assert extra_id == len(base)
    with StreamingCompressor(path, cfg, chunk_lines=300, append=True,
                             store=grown) as sc:
        sc.feed(spark_lines[600:900])
    rd = LZJSReader(path)
    assert rd.templates[:len(base)] == base
    assert rd.templates[extra_id] == ("extra", None, "template")
    assert rd.read_all() == spark_lines[:900]
    # the preseeded extra is part of the first appended chunk's delta:
    # the recorded chain stays contiguous
    assert rd.index[-1]["tpl_base"] + rd.index[-1]["n_delta"] == len(rd.templates)
    rd.close()


def test_append_superset_store_empty_session_keeps_container(tmp_path,
                                                            spark_lines):
    """Opening with a superset store but feeding nothing must leave the
    container byte-identical: the extras only materialize in a chunk
    delta, and no chunk was written."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=300) as sc:
        sc.feed(spark_lines[:600])
    before = open(path, "rb").read()
    grown = TemplateStore(LZJSReader(path).templates)
    grown.add(("extra", None, "template"))
    with StreamingCompressor(path, cfg, chunk_lines=300, append=True,
                             store=grown):
        pass
    assert open(path, "rb").read() == before


def test_append_rejects_divergent_store(tmp_path, spark_lines):
    """A store whose PREFIX disagrees with the container's templates is
    still refused: ids would diverge mid-chain and the container would
    be permanently unreadable."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=300) as sc:
        sc.feed(spark_lines[:600])
    divergent = TemplateStore([("not", "the", "container", None)]
                              + LZJSReader(path).templates[1:])
    with pytest.raises(ValueError, match="append store"):
        StreamingCompressor(path, cfg, chunk_lines=300, append=True,
                            store=divergent)
    # the refused open must not have corrupted the container
    assert LZJSReader(path).read_all() == spark_lines[:600]


def test_append_preserves_existing_ids(tmp_path, spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=200) as sc:
        sc.feed(spark_lines[:400])
    before = LZJSReader(path)
    tpls_before, params_before = list(before.templates), list(before.params)
    before.close()
    with StreamingCompressor(path, cfg, chunk_lines=200, append=True) as sc:
        sc.feed(spark_lines[400:800])
    after = LZJSReader(path)
    assert after.templates[:len(tpls_before)] == tpls_before
    assert after.params[:len(params_before)] == params_before
    assert after.read_all() == spark_lines[:800]
    after.close()


# ------------------------------------------------- corrupt / truncated blobs

def test_unknown_magic_raises_valueerror():
    with pytest.raises(ValueError, match="not a logzip archive"):
        decompress_parallel(b"XXXX" + b"\x00" * 64)
    with pytest.raises(ValueError, match="not a logzip archive"):
        decompress_parallel(b"\x1f")  # shorter than any magic


def test_truncated_lzjf_raises_valueerror(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    blob = compress(spark_lines[:300], cfg)
    # v3 blobs carry a whole-archive CRC trailer, so truncation surfaces
    # as an integrity failure before the structural parse even starts
    trunc = r"truncated or corrupt|CRC32C"
    with pytest.raises(ValueError, match=trunc):
        decompress(blob[: len(blob) // 2])
    with pytest.raises(ValueError, match=trunc):
        decompress_parallel(blob[: len(blob) // 2])
    with pytest.raises(ValueError, match="not a logzip archive"):
        decompress(b"LZJX" + blob[4:])
    with pytest.raises(ValueError, match="unknown entropy kernel"):
        decompress(blob[:4] + b"\x7f" + blob[5:])


def test_truncated_lzjm_raises_valueerror(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    blob = compress_parallel(spark_lines[:600], cfg, n_workers=1, chunk_lines=200)
    assert blob[:4] == b"LZJM"
    with pytest.raises(ValueError, match="truncated LZJM"):
        decompress_parallel(blob[: len(blob) - 40])
    with pytest.raises(ValueError, match="not a multi-chunk logzip archive"):
        from repro.core.parallel import iter_multi_chunks

        list(iter_multi_chunks(b"LZJF" + blob[4:]))


def test_truncated_lzjs_raises_valueerror(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    blob, _ = _stream_blob(spark_lines[:600], cfg, chunk_lines=200)
    assert blob[:4] == b"LZJS"
    with pytest.raises(ValueError, match="footer"):
        decompress_parallel(blob[: len(blob) - 20])  # footer chopped
    with pytest.raises(ValueError, match="not an LZJS container"):
        LZJSReader(io.BytesIO(b"LZJQ" + blob[4:]))
    with pytest.raises(ValueError):
        decompress_lzjs(blob[:40])


def test_session_chunk_needs_ext_templates(spark_lines):
    """A session chunk blob is not self-contained: decoding it without the
    accumulated dictionaries must fail loudly, not corrupt output."""
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    blob, _ = _stream_blob(spark_lines[:400], cfg, chunk_lines=200)
    rd = LZJSReader(io.BytesIO(blob))
    chunk = rd.chunk_blob(1)
    with pytest.raises(ValueError, match="session chunk"):
        decompress(chunk)


# ----------------------------------------------------- shared-store parallel

def test_parallel_shared_store_roundtrip(spark_lines):
    cfg = LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)
    lines = spark_lines[:900]
    blob = compress_parallel(lines, cfg, n_workers=1, chunk_lines=300, shared_store=True)
    assert decompress_parallel(blob) == lines


def test_parallel_shared_store_stable_eventids(spark_lines):
    """With the seeded store, every chunk's archive lists the SAME global
    template ids (cross-chunk EventID agreement)."""
    from repro.core.codec import read_structured
    from repro.core.parallel import iter_multi_chunks

    cfg = LogzipConfig(level=2, format=DATASETS["Spark"]["format"],
                       ise=ISEConfig(min_sample=300))
    lines = spark_lines[:1500]
    blob = compress_parallel(lines, cfg, n_workers=1, chunk_lines=500, shared_store=True)
    tpl_lists = [read_structured(p)["templates"] for p in iter_multi_chunks(blob)]
    assert len(tpl_lists) == 3
    assert tpl_lists[0] == tpl_lists[1] == tpl_lists[2]  # the shared store


# ------------------------------------------------- durability / edge sessions

def _spark_cfg():
    return LogzipConfig(level=3, format=DATASETS["Spark"]["format"], ise=CFG_FAST)


def test_double_close_idempotent(spark_lines):
    buf = io.BytesIO()
    sc = StreamingCompressor(buf, _spark_cfg(), chunk_lines=100)
    sc.feed(spark_lines[:300])
    first = sc.close()
    sealed = buf.getvalue()
    assert sc.close() == first  # second close: same summary, no writes
    assert buf.getvalue() == sealed
    assert decompress_lzjs(sealed) == spark_lines[:300]


def test_failed_close_then_retry_seals(spark_lines):
    """A close that dies mid-footer (ENOSPC) can be retried once the sink
    recovers: the retry rewinds past the partial footer and seals."""
    from repro.core.faultinject import FaultyFile

    lines = spark_lines[:300]
    ff = FaultyFile(io.BytesIO())
    sc = StreamingCompressor(ff, _spark_cfg(), chunk_lines=100, pipeline=False)
    sc.feed(lines)
    sc.flush_chunk()  # all chunk records are on "disk" before it fills
    ff.write_limit = ff.bytes_written + 10
    with pytest.raises(OSError):
        sc.close()
    ff.write_limit, ff.broken = None, False  # space freed
    sc.close()
    assert decompress_lzjs(ff.getvalue()) == lines


def test_zero_line_session_fsck_clean():
    from repro.core import recover

    blob, summary = _stream_blob([], LogzipConfig(ise=CFG_FAST))
    assert summary["n_lines"] == 0
    rep = recover.fsck(io.BytesIO(blob))
    assert rep["clean"] and rep["n_chunks"] == 0


def test_append_to_empty_archive(tmp_path, spark_lines):
    cfg = _spark_cfg()
    path = str(tmp_path / "empty.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=100):
        pass  # zero-line session
    with StreamingCompressor(path, cfg, chunk_lines=100, append=True) as sc:
        sc.feed(spark_lines[:250])
    rd = LZJSReader(path)
    assert rd.read_all() == spark_lines[:250]
    assert all(s == "ok" for s in rd.stats()["crc"])
    rd.close()


def test_verbatim_only_chunk_roundtrip():
    """Lines that match no template travel verbatim — the chunk still
    frames, checksums and round-trips byte-exact."""
    from repro.core import recover

    lines = [f"@@@ {i} ###### {'x' * (i % 7)}" for i in range(120)]
    blob, _ = _stream_blob(lines, LogzipConfig(level=3, ise=CFG_FAST),
                           chunk_lines=60)
    assert decompress_lzjs(blob) == lines
    assert recover.fsck(io.BytesIO(blob))["clean"]


def test_crash_between_truncate_and_close(tmp_path, spark_lines):
    """Append-mode torn-window regression: the write that overwrites the
    old footer carries a sealed commit and is fsynced, so a crash at ANY
    point before close() loses at most the unflushed buffer — never the
    original archive."""
    from repro.core import recover

    cfg = _spark_cfg()
    path = str(tmp_path / "s.lzjs")
    first, second = spark_lines[:300], spark_lines[300:400]
    with StreamingCompressor(path, cfg, chunk_lines=100) as sc:
        sc.feed(first)
    sc = StreamingCompressor(path, cfg, chunk_lines=100, append=True,
                             pipeline=False)
    sc.feed(second)  # 1 full chunk: lands over the old footer region
    sc._f.close()  # crash: close() never runs, no footer

    rep = recover.repair(path)
    assert not rep["quarantined"]
    rd = LZJSReader(path)
    assert rd.read_all() == first + second
    rd.close()


def test_reopen_after_salvage_append(tmp_path, spark_lines):
    """Byte-exact line round-trip across damage -> repair -> append."""
    from repro.core import recover

    cfg = _spark_cfg()
    path = str(tmp_path / "s.lzjs")
    with StreamingCompressor(path, cfg, chunk_lines=100) as sc:
        sc.feed(spark_lines[:300])
    with open(path, "r+b") as f:  # tear off the footer
        f.seek(-60, io.SEEK_END)
        f.truncate()
    recover.repair(path)
    with StreamingCompressor(path, cfg, chunk_lines=100, append=True) as sc:
        sc.feed(spark_lines[300:400])
    rd = LZJSReader(path)
    assert rd.read_all() == spark_lines[:400]
    assert all(s == "ok" for s in rd.stats()["crc"])
    rd.close()

"""The benchmark's own corpus generator: log lines of one deployment,
made from a seed.

It is a vectorised copy of the program's ``data/loggen.py`` mimics
(HDFS and Thunderbird statements, parameter kinds, Zipf-skewed statement
use, malformed lines, HDFS block-lifecycle sessions, the ``WorkloadSpec``
synthetic statements), driven entirely by a configuration file's
``corpus`` section, so that a later deployment is a new JSON file and
no new code. It lives here, and not in the program, because it is part
of the yardstick: a later change to the program's generator must not
change what the benchmark feeds it.

Determinism: ``generate(cfg, seed, n)`` is a pure function of the
configuration, the seed and ``n``. What sizes the work is the
deployment's and the same for every seed: the statement universe
(including the synthetic statements), which statement each line is
(``sequence_salt``), the parameter pools (``pools.salt``) and the
clocks. The seed draws every value: parameters, block ids, header
fields. So every seed does the same work on different lines.
"""

from __future__ import annotations

import re
import time

import numpy as np

# --------------------------------------------------------- statements

# the literal vocabulary of the program's WorkloadSpec synthetic
# statements (data/loggen.py), copied
_WORK_WORDS = (
    "Receiving Received Deleting Starting Finished Verification Updating "
    "Registered Allocated Released Committed Replicating Scanning Opened "
    "Closed Rolling Expired Refreshing Mounting Probing Draining Sealing "
    "block replica session shard lease segment snapshot bucket region "
    "partition channel handle cursor volume index mapper reducer queue "
    "worker tenant stream container manifest checkpoint journal footer "
    "succeeded failed locally remotely upstream pending stale corrupt "
    "for from into onto under over with without to at on retry timeout"
).split()
_SYNTH_KINDS = ("blk", "ip", "ipport", "num", "small", "size", "path",
                "hexid", "dur", "host")
_M64 = (1 << 64) - 1
_GOLD = 0x9E3779B97F4A7C15


def _mix(x: int) -> int:
    """splitmix64 finalizer (as the program's generator uses)."""
    x &= _M64
    x = (x ^ (x >> 30)) * 0xBF58476D1CE4E5B9 & _M64
    x = (x ^ (x >> 27)) * 0x94D049BB133111EB & _M64
    return x ^ (x >> 31)


def synth_statement(salt: int, k: int) -> tuple[str, list[str]]:
    """Synthetic logging statement ``k``: a literal first word, then
    interleaved literal words and ``{}`` slots (3-7 words, 0-3 slots), as
    ``WorkloadSpec`` builds them."""
    h = _mix(_mix(salt * _GOLD + 0x50A7) ^ (k + 1) * _GOLD)
    n_words, n_slots = 3 + h % 5, (h >> 8) % 4
    parts = [_WORK_WORDS[(h >> 16) % len(_WORK_WORDS)]]
    kinds: list[str] = []
    slots_left, words_left, j = n_slots, n_words - 1, 1
    while slots_left or words_left:
        hh = _mix(h + j)
        j += 1
        if slots_left and (words_left == 0 or hh % 2):
            kinds.append(_SYNTH_KINDS[(hh >> 8) % len(_SYNTH_KINDS)])
            parts.append("{}")
            slots_left -= 1
        else:
            parts.append(_WORK_WORDS[(hh >> 8) % len(_WORK_WORDS)])
            words_left -= 1
    return " ".join(parts), kinds


def statements(corpus: dict) -> list[tuple[str, list[str]]]:
    """The configuration's statement universe: its listed statements,
    then ``synthetic_statements`` more."""
    out = [(t, list(k)) for t, k in corpus["statements"]]
    syn = corpus.get("synthetic_statements", {"count": 0})
    seen = {t for t, _ in out}
    k = 0
    while len(out) < len(corpus["statements"]) + syn["count"]:
        t, kinds = synth_statement(syn.get("salt", 0), k)
        k += 1
        if t not in seen:  # a repeated draw would merge two statements
            seen.add(t)
            out.append((t, kinds))
    return out


# ------------------------------------------------------ parameter kinds

def _pool(rng, size: int, fn) -> np.ndarray:
    return np.array([fn(rng) for _ in range(size)], dtype=object)


def _ip(rng) -> str:
    a, b, c = rng.integers(256, size=3)
    return f"10.{a}.{b}.{c}"


class _Params:
    """Vectorised parameter draws, one kind at a time (the kinds of the
    program's ``_P`` and ``_WORK_KINDS``). The pools (addresses, hosts,
    users, pooled block ids) are the deployment's and come from the
    configuration's ``salt``; which member each line draws comes from
    ``rng``, the run's seed."""

    def __init__(self, rng: np.random.Generator, pools: dict):
        self.rng = rng
        fixed = np.random.default_rng([pools.get("salt", 0), 0x9001])
        self.ips = _pool(fixed, pools.get("ip", 512), _ip)
        self.hosts = np.array([f"node-{v}" for v in
                               fixed.integers(2048, size=pools.get("host", 512))],
                              dtype=object)
        self.users = np.array([f"user{v}" for v in
                               fixed.integers(64, size=pools.get("user", 16))],
                              dtype=object)
        self.blks = np.array([f"blk_{v}" for v in fixed.integers(
            -2**63, 2**63 - 1, size=pools.get("blk", 4096), dtype=np.int64).tolist()],
            dtype=object)

    def draw(self, kind: str, n: int) -> list[str]:
        r = self.rng
        if kind == "ip":
            return self.ips[r.integers(len(self.ips), size=n)].tolist()
        if kind == "ipport":
            ips = self.ips[r.integers(len(self.ips), size=n)].tolist()
            ports = r.integers(1024, 65536, size=n).tolist()
            return [f"{a}:{p}" for a, p in zip(ips, ports)]
        if kind == "blk":
            return self.blks[r.integers(len(self.blks), size=n)].tolist()
        if kind == "host":
            return self.hosts[r.integers(len(self.hosts), size=n)].tolist()
        if kind == "user":
            return self.users[r.integers(len(self.users), size=n)].tolist()
        if kind == "num":
            return [str(v) for v in r.integers(10**6, size=n).tolist()]
        if kind == "small":
            return [str(v) for v in r.integers(128, size=n).tolist()]
        if kind == "size":
            sizes = np.array(["512", "1024", "4096", "65536", "67108864"], dtype=object)
            return sizes[r.integers(len(sizes), size=n)].tolist()
        if kind == "path":
            return [f"/data/part-{v:05d}" for v in r.integers(4096, size=n).tolist()]
        if kind == "hexid":
            return [f"0x{v:08x}" for v in r.integers(2**32, size=n).tolist()]
        if kind == "pkg":
            pk = np.array(["com.android.systemui", "com.google.gms", "com.app.demo"],
                          dtype=object)
            return pk[r.integers(3, size=n)].tolist()
        if kind == "dur":
            return [f"{v:.3f}" for v in (r.random(n) * 100).tolist()]
        raise ValueError(f"unknown parameter kind {kind!r}")


# ------------------------------------------------------------- headers

def _header_column(spec: dict, n: int, rng, params: _Params,
                   secs: np.ndarray, done: dict) -> list[str]:
    kind = spec["kind"]
    if kind == "const":
        return [spec["value"]] * n
    if kind == "same":  # another (earlier) field's value, formatted
        return [spec["fmt"].format(v) for v in done[spec["field"]]]
    if kind == "clock":  # strftime of the line's second, optionally stripped
        uniq, inv = np.unique(secs, return_inverse=True)
        strip = spec.get("lstrip", "")
        vals = np.array([time.strftime(spec["strftime"], time.gmtime(int(s))).lstrip(strip)
                         or "0" for s in uniq], dtype=object)
        return vals[inv].tolist()
    if kind == "epoch":
        return [str(s) for s in secs.tolist()]
    if kind == "int":
        return [str(v) for v in rng.integers(spec["lo"], spec["hi"], size=n).tolist()]
    if kind == "choice":
        vals = np.array(spec["values"], dtype=object)
        p = np.asarray(spec["p"], float) if "p" in spec else None
        p = p / p.sum() if p is not None else None
        picked = vals[rng.choice(len(vals), size=n, p=p)].tolist()
        if "pid" in spec:  # a process id where the value has a {pid} slot
            lo, hi = spec["pid"]
            pids = rng.integers(lo, hi, size=n).tolist()
            picked = [v.format(pid=i) if "{" in v else v for v, i in zip(picked, pids)]
        return picked
    if kind == "param":
        return params.draw(spec["param"], n)
    raise ValueError(f"unknown header kind {kind!r}")


# ------------------------------------------------------------ the lines

def _zipf(n: int, s: float) -> np.ndarray:
    w = 1.0 / np.arange(1, n + 1) ** s
    return w / w.sum()


def _fmt(text: str) -> str:
    return text.replace("%", "%%").replace("{}", "%s")


def generate(cfg: dict, seed: int, n_lines: int, stream: int = 0) -> list[str]:
    """``n_lines`` lines of the deployment ``cfg`` (a configuration
    file's contents) from ``seed``. Another ``stream`` gives lines of the
    same deployment that share neither values nor statement sequence
    with stream 0 (a warm-up that the measured lines never repeat)."""
    c = cfg["corpus"]
    extra = [int(stream)] if stream else []
    rng = np.random.default_rng([int(seed) & _M64, int(seed) >> 64, 0x10C5] + extra)
    # the shape of the traffic (which statement each line is, sessions,
    # block locality, malformed lines) is the deployment's, the same for
    # every seed; the seed draws every value in it
    shape = np.random.default_rng([c["sequence_salt"], 0x5E9] + extra)
    stmts = statements(c)
    cum = np.cumsum(_zipf(len(stmts), c["zipf_s"]))
    cum[-1] = 1.0

    # draws: one per statement use or session; a session emits its
    # statement sequence, sharing one value of the session's kind
    sess = c.get("sessions", {"p": 0.0, "sequences": [[0]]})
    seqs = sess["sequences"]
    seq_len = np.array([len(s) for s in seqs])
    seq_tab = np.full((len(seqs), seq_len.max()), -1)
    for i, s in enumerate(seqs):
        seq_tab[i, :len(s)] = s
    n_draw = n_lines
    u_mal, u_sess, u_tpl = shape.random(n_draw), shape.random(n_draw), shape.random(n_draw)
    seq_id = shape.integers(len(seqs), size=n_draw)
    malformed = u_mal < c["malformed_rate"]
    in_sess = ~malformed & (u_sess < sess["p"])
    length = np.where(in_sess, seq_len[seq_id], 1)
    starts = np.concatenate([[0], np.cumsum(length)[:-1]])
    n_draw = int(np.searchsorted(starts, n_lines))  # draws that start a line
    draw = np.repeat(np.arange(n_draw), length[:n_draw])[:n_lines]
    pos = np.arange(n_lines) - starts[draw]
    single_tid = np.minimum(np.searchsorted(cum, u_tpl, side="right"), len(stmts) - 1)
    tid = np.where(in_sess[draw], seq_tab[seq_id[draw], np.minimum(pos, seq_tab.shape[1] - 1)],
                   single_tid[draw])
    tid = np.where(malformed[draw], -1, tid)

    params = _Params(rng, c.get("pools", {}))
    # the session-shared kind (HDFS block ids): value index drawn around
    # the draw's position, so a block's lines lie close together, as a
    # block's lifecycle does in a real log
    shared = sess.get("share")
    shared_vals = None
    if shared:
        loc = sess["locality_lines"]
        per_line = sess["values_per_line"]
        centre = starts[:n_draw] + shape.integers(-loc, loc + 1, size=n_draw)
        idx = np.clip(centre, 0, None) * per_line
        n_vals = int((n_lines + loc) * per_line) + 2
        pool = rng.integers(-2**63, 2**63 - 1, size=n_vals, dtype=np.int64)
        shared_vals = np.array([f"blk_{v}" for v in pool.tolist()], dtype=object)[
            idx.astype(np.int64)][draw]

    content = np.empty(n_lines, dtype=object)
    for t, (text, kinds) in enumerate(stmts):
        rows = np.flatnonzero(tid == t)
        if not len(rows):
            continue
        cols = [shared_vals[rows].tolist() if k == shared and shared_vals is not None
                else params.draw(k, len(rows)) for k in kinds]
        if cols:
            f = _fmt(text)
            content[rows] = [f % v for v in zip(*cols)]
        else:
            content[rows] = text

    secs = c["clock"]["start"] + np.arange(n_lines) // c["clock"]["lines_per_second"]
    fields = re.findall(r"<(\w+)>", cfg["format"])[:-1]  # in the format's order
    done: dict = {}
    for f in fields:
        done[f] = _header_column(c["header"][f], n_lines, rng, params, secs, done)
    hdr = [done[f] for f in fields]
    line_fmt = _fmt(re.sub(r"<\w+>", "{}", cfg["format"]))
    lines = [line_fmt % v for v in zip(*hdr, content.tolist())]

    mal_rows = np.flatnonzero(tid < 0)
    choices = c["malformed_lines"]
    picks = shape.integers(len(choices), size=len(mal_rows)).tolist()
    hexes = rng.integers(2**32, size=len(mal_rows)).tolist()
    for r, p, h in zip(mal_rows.tolist(), picks, hexes):
        m = choices[p]
        lines[r] = m % h if "%" in m else m
    return lines


def reference(cfg: dict, n_lines: int) -> list[str]:
    """The deployment's reference rotation: ``n_lines`` lines that are the
    same in every run (the configuration's ``reference_seed``). The
    compression ratio is measured on them, so that it moves only with
    the program."""
    return generate(cfg, cfg["corpus"]["reference_seed"], n_lines)


def line_bytes(lines: list[str]) -> int:
    """Raw bytes of ``lines`` as a log file holds them, newline included."""
    return sum(len(s.encode("utf-8")) for s in lines) + len(lines)

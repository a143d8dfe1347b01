"""Hypothesis strategies shared by the property tests: multi-line records.

A record is a header line in the Spark format followed by 0-4 lines
that open no header (stack-trace frames, a ``Caused by`` line, any other
text), joined by ``"\\n"``, as ``LogFormat.records`` assembles them."""

from hypothesis import strategies as st

SPARK_FORMAT = "<Date> <Time> <Level> <Component>: <Content>"

_header = st.builds(
    lambda d, t, lv, c: f"{d} {t} {lv} {c}: ",
    st.sampled_from(["17/06/09", "17/06/10"]),
    st.sampled_from(["20:10:40", "20:10:41", "21:00:00"]),
    st.sampled_from(["INFO", "WARN", "ERROR"]),
    st.sampled_from(["executor.Executor", "storage.BlockManager"]),
)
_frame = st.sampled_from([
    "\tat org.apache.spark.scheduler.Task.run(Task.scala:99)",
    "\tat java.lang.Thread.run(Thread.java:745)",
    "Caused by: java.io.IOException: Failed to connect to 10.0.0.1:7337",
    "\t... 12 more",
    "",
])


def records(line_text):
    """Records whose lines are drawn from ``line_text`` (newline-free
    text) and stack-trace frames; some are bare multi-line text with no
    header at all."""
    body = st.lists(st.one_of(_frame, line_text), max_size=4)
    with_header = st.builds(lambda h, first, rest: h + "\n".join([first] + rest),
                            _header, line_text, body)
    bare = st.builds(lambda first, rest: "\n".join([first] + rest), line_text, body)
    return st.one_of(with_header, with_header, bare)

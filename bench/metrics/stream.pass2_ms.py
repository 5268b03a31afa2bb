"""Milliseconds per decomposition of the program's ``stream.pass2`` span:
the second read of every chunk and the pivot-column gather on the
host."""


def read(w):
    spans = w.spans.get("stream.pass2")
    return 1e3 * sum(spans) / w.decomps if spans else None

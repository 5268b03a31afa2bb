"""Milliseconds per decomposition of the program's ``stream.pass1`` span
(host-to-device transfer and accumulate of every chunk; the span closes
on the device's finished sketch), under a non-deep ``repro.obs``
tracer."""


def read(w):
    spans = w.spans.get("stream.pass1")
    return 1e3 * sum(spans) / w.decomps if spans else None

"""Milliseconds per decomposition of the program's ``stream.qr_interp``
span: the pivoted QR and the interpolation solve of the streamed path,
from the dispatch to the device's finished ``P``, ``J``, ``Q`` and
``R`` (the span closes on them), under a non-deep ``repro.obs``
tracer."""


def read(w):
    spans = w.spans.get("stream.qr_interp")
    return 1e3 * sum(spans) / w.decomps if spans else None

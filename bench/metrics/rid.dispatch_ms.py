"""Host milliseconds per decomposition inside the program's ``rid`` span,
under a non-deep ``repro.obs`` tracer: the host's dispatch of the sketch,
the QR and interpolation, and the gather (the span never waits on the
device).  A program without the span reads nothing."""


def read(w):
    spans = w.spans.get("rid")
    return 1e3 * sum(spans) / w.decomps if spans else None

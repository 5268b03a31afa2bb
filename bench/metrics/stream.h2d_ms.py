"""Host milliseconds per decomposition inside the program's
``stream.h2d`` spans, one per chunk of pass 1: reading the chunk from
its source and staging it on the device (over a mesh, cutting it into
its column shards), under a non-deep ``repro.obs`` tracer, where the
span does not wait for the copy to land."""


def read(w):
    spans = w.spans.get("stream.h2d")
    return 1e3 * sum(spans) / w.decomps if spans else None

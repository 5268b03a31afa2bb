"""Device milliseconds per decomposition spent making the sketch
``Y = Phi A``: the srft/srht transforms, the gaussian operator's blocks,
the accumulate (``sketch_accum``, or ``step``: its column-sharded
``shard_map`` over a mesh) and the final scaling."""
PROGRAMS = ("srft_sketch", "srht_sketch", "_omega_blocks", "sketch_accum",
            "step", "finalize_gaussian_sketch")


def read(w):
    s = w.trace.module_s(PROGRAMS)
    return 1e3 * s / w.decomps if s > 0 else None

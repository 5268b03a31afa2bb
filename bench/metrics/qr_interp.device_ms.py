"""Device milliseconds per decomposition of the pivoted QR of the sketch
and the interpolation solve: ``core.rid._qr_interp``, or the sharded
``panel_parallel_rid_interp_local`` over a mesh."""
PROGRAMS = ("_qr_interp", "panel_parallel_rid_interp_local")


def read(w):
    s = w.trace.module_s(PROGRAMS)
    return 1e3 * s / w.decomps if s > 0 else None

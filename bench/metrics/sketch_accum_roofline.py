"""Share of its roofline that the ``sketch_accum`` Mosaic kernel reaches:
the least time of each call (``kernel_work.sketch_accum`` from the
call's operand shapes ``x`` (l, rows), ``a`` (rows, n)) summed over the
window's calls, over their device time, in percent."""
from bench.metrics import kernel_work


def read(w):
    calls = w.trace.kernel_calls("sketch_accum")
    if not calls:
        return None
    least = sum(kernel_work.roofline_s(
        *kernel_work.sketch_accum(x[0], x[1], a[1]), w.peaks)
        for (x, a, _), _ in calls)
    return 100.0 * least / sum(d for _, d in calls)

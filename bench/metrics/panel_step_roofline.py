"""Share of its roofline that the fused ``panel_step`` Mosaic kernel
reaches: the least time of each call (``kernel_work.panel_step`` from
the call's operand shapes ``c`` (l, b), ``z`` (l, n)) summed over the
window's calls, over their device time, in percent."""
from bench.metrics import kernel_work


def read(w):
    calls = w.trace.kernel_calls("panel_step")
    if not calls:
        return None
    least = sum(kernel_work.roofline_s(
        *kernel_work.panel_step(c[0], c[1], z[1]), w.peaks)
        for (c, z), _ in calls)
    return 100.0 * least / sum(d for _, d in calls)

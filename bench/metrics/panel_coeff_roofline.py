"""Share of its roofline that the ``panel_coeff`` Mosaic kernel (stage A
of the panel-parallel pivoted QR) reaches: the least time of each call
(``panel_work.panel_coeff`` from the call's operand shapes ``c`` (l, b),
``z`` (l, n)) summed over the window's calls on every chip, over their
device time, in percent."""
from bench.metrics import kernel_work, panel_work


def read(w):
    calls = w.trace.kernel_calls("panel_coeff")
    if not calls:
        return None
    least = sum(kernel_work.roofline_s(
        *panel_work.panel_coeff(c[0], c[1], z[1]), w.peaks)
        for (c, z, _), _ in calls)
    return 100.0 * least / sum(d for _, d in calls)

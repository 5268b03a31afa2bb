"""Share of its roofline that the ``panel_apply`` Mosaic kernel (stage B
of the panel-parallel pivoted QR, the deflation) reaches: the least time
of each call (``panel_work.panel_apply`` from the call's operand shapes
``qp`` (l, b), ``w`` (b, n); a call whose result is a tuple also returns
the column norms) summed over the window's calls on every chip, over
their device time, in percent."""
from bench.metrics import kernel_work, panel_work, xplane


def read(w):
    least = busy = 0.0
    for evs in w.trace.ops.values():
        for e in evs:
            call = xplane.kernel_call(e.name)
            if call is None or call[0] != "panel_apply":
                continue
            qp, coef, _ = call[1]
            norms = e.name.split(" = ", 1)[1].startswith("(")
            least += kernel_work.roofline_s(
                *panel_work.panel_apply(qp[0], qp[1], coef[1], norms), w.peaks)
            busy += e.dur
    return 100.0 * least / busy if busy else None

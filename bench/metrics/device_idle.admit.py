"""device_idle.admit: device-idle time inside the engine's
``serve.admit`` spans (fresh cache, prefill through the first-token
readback, splice) over the traced window, averaged over the chips,
in %."""
from bench.serve_trace import ADMIT_SPAN, idle_in_spans_s, spans


def read(win):
    if win.kind != "serve" or not win.trace.devices \
            or not spans(win.trace, ADMIT_SPAN, win.span):
        return None
    return 100.0 * idle_in_spans_s(win.trace, win.span, ADMIT_SPAN) \
        / win.seconds

"""device_idle.step: device-idle time inside the engine's
``serve.step`` spans (uploads, the decode step, the token readback and
the slot loop) over the traced window, averaged over the chips, in %."""
from bench.serve_trace import STEP_SPAN, idle_in_spans_s, spans


def read(win):
    if win.kind != "serve" or not win.trace.devices \
            or not spans(win.trace, STEP_SPAN, win.span):
        return None
    return 100.0 * idle_in_spans_s(win.trace, win.span, STEP_SPAN) \
        / win.seconds

"""queue_wait_mean_s: mean over the traced wave's requests of the time
from the engine's ``serve.run`` call (when an offline wave's requests
arrive) to the start of the request's ``serve.admit`` span, in s; the
share of the TTFT spent queued, the rest being its prefill."""
from bench.serve_trace import queue_waits_s


def read(win):
    if win.kind != "serve":
        return None
    waits = queue_waits_s(win.trace, win.span)
    return sum(waits) / len(waits) if waits else None

"""prefill_ms_per_token: device time of the prefill programs in the
traced window over the prompt tokens they consumed, in ms."""
from bench.window import PREFILL_PROGRAM


def read(win):
    runs = win.program_seconds(PREFILL_PROGRAM)
    if win.kind != "serve" or not runs or not win.prefills:
        return None
    return 1e3 * sum(runs) / sum(win.prefills)

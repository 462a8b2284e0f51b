"""decode_kv_write_ms: device time of the decode step's operations under
the ``kv_write`` scope (the KV-cache write), per ``jit_serve_step`` run
in the traced window, in ms."""
from bench.serve_trace import step_scope_ms


def read(win):
    return step_scope_ms(win, "kv_write")

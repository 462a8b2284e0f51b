"""decode_attn_ms: device time of the decode step's operations under the
model's ``attn`` scope (the cache write included), per ``jit_serve_step``
run in the traced window, in ms."""
from bench.serve_trace import step_scope_ms


def read(win):
    return step_scope_ms(win, "attn")

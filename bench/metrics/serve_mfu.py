"""serve_mfu: operations that the window's prefills and decoded tokens
need (bench/flops.py: the prompt through every layer, logits only for its
last token; each decoded token attending to its context) over the traced
window's length times the chips' bf16 peak, in %."""
from bench import flops


def read(win):
    if win.kind != "serve" or not win.steps:
        return None
    need = sum(flops.prefill_flops(win.model, p) for p in win.prefills)
    need += sum(flops.decode_flops(win.model, [f + 1 for f in step])
                for step in win.steps)
    return 100.0 * need / (win.seconds * win.chips
                           * win.peak["bf16_flops_per_s"])

"""train_mfu: operations a trained token needs (bench/flops.py) times the
traced window's tokens per second, over the chips' bf16 peak, in %."""
from bench import flops


def read(win):
    if win.kind != "train" or not win.tokens:
        return None
    need = flops.train_flops_per_token(win.model, win.mix["seq"]) * win.tokens
    return 100.0 * need / (win.seconds * win.chips
                           * win.peak["bf16_flops_per_s"])

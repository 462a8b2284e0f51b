"""Serve a small model with batched requests: prefill-by-decode warmup,
then batched greedy generation with a KV cache / recurrent state under a
ComParX serving plan.  Compares two archs (dense KV-cache vs recurrent
O(1)-state) on the same harness.

    PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs import get_arch, get_shape
from repro.core.plan import default_plan
from repro.models.model import init_cache, model_specs
from repro.models.params import init_params
from repro.serve.step import make_decode_step


def generate(arch: str, batch: int = 4, prompt_len: int = 8,
             gen_len: int = 24, cache_len: int = 64):
    cfg = get_arch(arch).smoke()
    shape = get_shape("decode_32k").smoke()
    plan = default_plan(cfg, shape)
    params = init_params(model_specs(cfg), jax.random.key(0))
    step, _ = make_decode_step(cfg, None, plan)
    step = jax.jit(step, donate_argnums=(1,))

    prompts = jax.random.randint(jax.random.key(1), (batch, prompt_len),
                                 0, cfg.vocab_size)
    caches = init_cache(cfg, batch, cache_len)

    # prefill by decoding the prompt (cache fills token by token)
    tok = prompts[:, 0]
    for pos in range(prompt_len):
        nxt, _, caches = step(params, caches, prompts[:, pos],
                              jnp.int32(pos))
    # batched greedy generation
    out = []
    t0 = time.perf_counter()
    tok = nxt
    for pos in range(prompt_len, prompt_len + gen_len):
        tok, _, caches = step(params, caches, tok, jnp.int32(pos))
        out.append(tok)
    jax.block_until_ready(tok)
    dt = time.perf_counter() - t0
    seqs = jnp.stack(out, axis=1)
    print(f"[{arch}] {batch} seqs x {gen_len} tokens "
          f"in {dt:.2f}s ({batch * gen_len / dt:.1f} tok/s)  "
          f"sample={seqs[0][:10].tolist()}")
    return seqs


def main():
    print("dense KV-cache arch:")
    generate("granite-8b")
    print("recurrent O(1)-state arch (no KV growth):")
    generate("recurrentgemma-2b")


if __name__ == "__main__":
    main()

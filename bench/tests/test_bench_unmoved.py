"""The reference and the arithmetic give the numbers they gave before the
layer kinds moved into files of their own (CPU).

The literals were taken from the tree before that move: the operation
counts behind the mfu readers for every configuration, and, at the small
sizes ``test_bench_check.py`` runs, a digest of the reference's logits,
the serve check's gap (float32 and bfloat16 program), the control's gap
and the reference's three training steps.  Floating-point results are
computed in a child process held to one CPU core: XLA:CPU splits a matmul
by the number of cores it may use, which moves the last bits.

Run as a script, this file prints those numbers as one JSON line.
"""
import hashlib
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

from bench import config as C
from bench import flops

ROOT = Path(__file__).resolve().parents[2]
CONFIGS = {p.stem: p for p in sorted((ROOT / "bench" / "configs")
                                     .glob("*.json"))}

ARITHMETIC = {
    "stablelm-3b": {"param_count": 2795443200,
                    "weight_params": 2666332160,
                    "prefill_flops_19": 96746864640.0,
                    "decode_flops_1_100_384": 16156917760.0,
                    "train_flops_per_token_2048": 17005117440.0},
    "stablelm-3b-4l": {"param_count": 574796800,
                       "weight_params": 445972480,
                       "prefill_flops_19": 12318720000.0,
                       "decode_flops_1_100_384": 2695700480.0,
                       "train_flops_per_token_2048": 2801725440.0},
}

REFERENCE = {
    "logits_sha256": "9ea5634ab39b5d91dfa88939cf49055d"
                     "cef14b49a53d2ffbf605b728d56c3832",
    "logits_fp8_sha256": "a49905871d2fce0aa55ec641cf025127"
                         "cd4e8fbb8874493bcec2e7c96dcb8bf0",
    "gap": 0.0,
    "gap_bf16": 0.010297298431396484,
    "control_gap_bf16": 0.2660789489746094,
    "train_losses": [5.496221542358398, 5.128461599349976,
                     4.927655100822449],
    "train_grad_norm0": 7.859118461608887,
    "train_first_sha256": "ee3fea85503721daed70df747addc31a"
                          "b01d1f156a170415f0de8ca9f8df85e4",
    "train_change_sha256": "6d8b89215f8ab21005b9a312f39496ff"
                           "6069b57cf4eac4e05aa136ee729b7d14",
}


@pytest.mark.parametrize("name,what", [(c, w) for c in sorted(ARITHMETIC)
                                       for w in sorted(ARITHMETIC[c])])
def test_arithmetic_unmoved(name, what):
    m = C.model(json.loads(CONFIGS[name].read_text()))
    got = {"param_count": lambda: flops.param_count(m),
           "weight_params": lambda: flops.weight_params(m),
           "prefill_flops_19": lambda: flops.prefill_flops(m, 19),
           "decode_flops_1_100_384": lambda: flops.decode_flops(
               m, [1, 100, 384]),
           "train_flops_per_token_2048": lambda:
               flops.train_flops_per_token(m, 2048)}[what]()
    assert got == ARITHMETIC[name][what]


@pytest.fixture(scope="module")
def probed():
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join([str(ROOT), str(ROOT / "src")]))
    out = subprocess.run([sys.executable, __file__], cwd=ROOT, env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("what", sorted(REFERENCE))
def test_reference_unmoved(probed, what):
    assert probed[what] == REFERENCE[what]


def _digest(x) -> str:
    return hashlib.sha256(x).hexdigest()


def probe() -> dict:
    """The reference's numbers at the small sizes of the check's tests."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    import test_bench_check as T
    from bench import cell_serve, gen

    out = {}
    cell, m = T.small("stablelm-3b.serve.alpaca", "float32")
    config = dict(cell["config"], model=m)
    ref, m = C.reference(config), C.model(config)
    key = gen.jax_key(T.SEED)
    params = jax.jit(lambda k: ref.init_params(m, k))(key)
    tok = (jnp.arange(48, dtype=jnp.int32) * 37 % m["vocab_size"])[None]
    for name, quant in (("logits_sha256", None), ("logits_fp8_sha256",
                                                   "fp8")):
        lg = jax.jit(lambda p, t: ref.forward(p, t, m, ref.Arith(quant)))(
            params, tok)
        out[name] = _digest(np.asarray(lg).tobytes())
    out["gap"] = T.drive("stablelm-3b.serve.alpaca")["compared"]["gap"][
        "value"]
    cell, m = T.small("stablelm-3b.serve.alpaca", "bfloat16")
    run = T.R.run_cell(cell, T.SEED, 0.2, False, jax.devices()[:1],
                       t0=time.perf_counter(), model=m)
    out["gap_bf16"] = run["compared"]["gap"]["value"]
    out["control_gap_bf16"] = cell_serve.check(
        dict(cell["config"], model=m), key, run["served"], cell["mix"],
        T.SEED, quant="fp8")[0]

    cell, m = T.small("stablelm-3b-4l.train", "float32")
    config = dict(cell["config"], model=m)
    mix = cell["mix"]
    toks, tgts = gen.train_pool(mix, T.R.arch_config(config), T.SEED)
    batches = [(toks[i], tgts[i]) for i in range(mix["check_steps"])]
    losses, first, change, norm0 = C.reference(config).train_steps(
        C.model(config), key, batches, mix["hyper"])
    out["train_losses"] = losses
    out["train_grad_norm0"] = norm0
    out["train_first_sha256"] = _digest(
        json.dumps(first, sort_keys=True).encode())
    out["train_change_sha256"] = _digest(
        json.dumps(change, sort_keys=True).encode())
    return out


if __name__ == "__main__":
    # one core, before JAX starts its thread pools
    os.sched_setaffinity(0, {min(os.sched_getaffinity(0))})
    print(json.dumps(probe()))

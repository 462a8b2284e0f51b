"""The correctness check itself, at a size a CPU test can hold.

Each cell's run is driven without the look for a chip, with the timed
path broken underneath, and ``correct`` has to come out false under the
cell's own limits; a sound run at the same size comes out true.  The
control (the float32 reference on fp8 operands in the program's place)
has to fail at least one of the cell's numbers.
"""
import json
import time
from pathlib import Path

import jax
import pytest

from bench import cell_serve, cell_train, gen
from bench import config as C
from bench import run as R

SEED = 2 ** 31 + 977
ROOT = Path(__file__).resolve().parents[2]
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
#: cells built but held out of BENCHMARK.json while the program is at
#: fault on them (PERF.md, section 7): their harness stays under test
HELD = {"stablelm-3b-4l.train": ("stablelm-3b-4l", "train_2k")}
CELLS = [w["name"] for w in SPEC["workloads"]] + sorted(HELD)


def load(name):
    if name not in HELD:
        return R.load_cell(name)
    config, mix = HELD[name]
    bench = ROOT / "bench"
    return {"name": name, "chips": 1,
            "config": json.loads((bench / "configs" / f"{config}.json")
                                 .read_text()),
            "mix": gen.load_mix(mix), "plan": bench / "plans" / f"{name}.json",
            "limits": json.loads((bench / "limits" / f"{name}.json")
                                 .read_text()),
            "end_to_end": [], "per_layer": []}


KIND = {c: load(c)["mix"]["kind"] for c in CELLS}
#: the faults each kind of cell can have, planted under the timed path
FAULTS = {"train": ("unchanged", "half_batch"),
          "serve": ("altered_token",)}


def small(cell_name, dtype):
    cell = load(cell_name)
    m = dict(cell["config"]["model"], dtype=dtype)
    if cell["mix"]["kind"] == "train":
        m.update(num_layers=2, d_model=64, num_heads=2, num_kv_heads=2,
                 d_ff=128, vocab_size=256)
        cell["mix"] = dict(cell["mix"], seq=64, pool=4)
    else:
        m.update(num_layers=4, d_model=256, num_heads=4, num_kv_heads=4,
                 d_ff=512, vocab_size=1024)
        mix = dict(cell["mix"], capacity=4, cache_len=96, wave=6)
        mix["prompt"] = dict(mix["prompt"], min=4, max=32, mean=9, grid=4)
        mix["answer"] = dict(mix["answer"], min=4, max=64, mean=30)
        cell["mix"] = mix
    return cell, m


def drive(cell_name, fault=None, dtype="float32"):
    cell, m = small(cell_name, dtype)
    return R.run_cell(cell, SEED, 0.2, False, jax.devices()[:1],
                      t0=time.perf_counter(), fault=fault, model=m)


@pytest.mark.parametrize("cell,fault", [
    (c, f) for c in CELLS for f in (None,) + FAULTS[KIND[c]]])
def test_broken_timed_path_is_not_correct(cell, fault):
    out = drive(cell, fault)
    assert out["correct"] is (fault is None), out["compared"]
    assert list(out["compared"]) == list(load(cell)["limits"])
    # the result line carries the cell's end-to-end metrics and no others
    assert set(out["metrics"]) == {m["name"] for m in load(cell)["end_to_end"]}


@pytest.mark.parametrize("cell", [c for c in CELLS if KIND[c] == "train"])
def test_train_control_fails_a_number(cell):
    cell, m = small(cell, "bfloat16")
    mix = cell["mix"]
    toks, tgts = gen.train_pool(mix, R.arch_config({"model": m}), SEED)
    batches = [(toks[i], tgts[i]) for i in range(mix["check_steps"])]
    key = gen.jax_key(SEED)
    ref = C.reference(cell["config"])
    m = C.model(dict(cell["config"], model=m))
    want = ref.train_steps(m, key, batches, mix["hyper"])
    low = ref.train_steps(m, key, batches, mix["hyper"], quant="fp8")
    got = cell_train.compare(*low[:3], *want[:3])
    lim = cell["limits"]
    assert any(got[k] > lim[k] for k in lim), got


@pytest.mark.parametrize("cell", [c for c in CELLS if KIND[c] == "serve"])
def test_serve_control_fails_the_gap(cell):
    c, m = small(cell, "bfloat16")
    out = R.run_cell(c, SEED, 0.2, False, jax.devices()[:1],
                     t0=time.perf_counter(), model=m)
    widest, _ = cell_serve.check(dict(c["config"], model=m),
                                 gen.jax_key(SEED), out["served"], c["mix"],
                                 SEED, quant="fp8")
    assert widest > c["limits"]["gap"], widest

"""Layer kinds as files: every configuration's layer list against the
program's, and a new kind added as new files alone (CPU)."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

from bench import config as C
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
CONFIGS = sorted((BENCH / "configs").glob("*.json"))


def load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_reference_file_exists(path):
    conf = load(path)
    assert (ROOT / conf["reference"]).is_file()
    assert callable(C.reference(conf).init_params)


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_every_kind_has_a_block_file(path):
    for kind in set(C.kinds(C.model(load(path)))):
        assert (BENCH / "blocks" / f"{kind}.py").is_file(), kind


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_layer_list_is_the_programs(path):
    conf = load(path)
    assert C.kinds(C.model(conf)) == list(R.arch_config(conf).block_kinds())


def test_layer_list_with_leading_layers():
    m = {"num_layers": 7, "first_k_dense": 2, "block_pattern": ["attn"],
         "stack": {"lead": "dense", "pattern": ["a", "b"]}}
    assert C.plan(m) == [(("dense", "dense"), 1), (("a", "b"), 2),
                         (("a",), 1)]
    assert C.kinds(m) == ["dense"] * 2 + ["a", "b"] * 2 + ["a"]
    # without first_k_dense or a stack: the block pattern over the depth
    m = {"num_layers": 5, "block_pattern": ["x", "y"]}
    assert C.plan(m) == [(("x", "y"), 2), (("x",), 1)]
    with pytest.raises(ValueError, match="lead"):
        C.plan({"num_layers": 3, "first_k_dense": 1,
                "block_pattern": ["attn"]})


def test_unknown_kind_names_the_missing_file():
    with pytest.raises(FileNotFoundError, match="blocks/no_such_kind.py"):
        C.block("no_such_kind")
    with pytest.raises(FileNotFoundError, match="nowhere.py"):
        C.reference({"reference": "bench/nowhere.py"})


#: a kind the benchmark lacks: attention and the FFN side by side on one
#: norm of the input (a parallel residual), built from ``attn``'s parts
PARALLEL = '''
"""Block kind ``attn_par``: attention and the FFN on one norm of the
input, both added to the residual."""
import jax.numpy as jnp

from bench import config as C
from bench.reference import norm, norm_leaves

attn = C.block("attn")


def leaves(m):
    return {"ln": norm_leaves(m, m["d_model"]),
            "attn": attn.attention_leaves(m), "ffn": attn.ffn_leaves(m)}


def forward(p, x, m, ar):
    h = norm(p["ln"], x, m["norm"])
    pos = jnp.arange(x.shape[1])
    return x + attn.attention(p["attn"], h, pos, m, ar) + attn.ffn(
        p["ffn"], h, m, ar)


held_params = active_params = attn.held_params
mix_flops = attn.mix_flops


def norm_params(m):
    return m["d_model"] * (2 if m["norm"] == "layernorm" else 1)
'''

#: run in the copy: the reference, the gap check and the arithmetic of
#: the new configuration
DRIVE = '''
import json, sys
import jax, jax.numpy as jnp
from bench import cell_serve, flops, gen
from bench import config as C

conf = json.load(open("bench/configs/toy-par.json"))
ref, m = C.reference(conf), C.model(conf)
key = gen.jax_key(2 ** 31 + 5)
params = jax.jit(lambda k: ref.init_params(m, k))(key)
lg = ref.forward(params, jnp.arange(12, dtype=jnp.int32)[None], m,
                 ref.Arith())
prompt, toks = [3, 1, 4, 1, 5], []
for _ in range(4):
    seq = jnp.asarray([prompt + toks], jnp.int32)
    toks.append(int(jnp.argmax(ref.forward(params, seq, m,
                                           ref.Arith())[0, -1])))
mix = {"cache_len": 16, "check_tokens": 4}
gap = cell_serve.check(conf, key, [(prompt, toks)], mix, 5)[0]
control = cell_serve.check(conf, key, [(prompt, toks)], mix, 5, "fp8")[0]
print(json.dumps({
    "plan": [[list(p), r] for p, r in C.plan(m)],
    "leaves": sum(x.size for x in jax.tree.leaves(params)),
    "groups": sorted(params), "g1": sorted(params["g1"]["b0"]),
    "finite": bool(jnp.all(jnp.isfinite(lg))), "shape": list(lg.shape),
    "gap": gap, "control": control,
    "param_count": flops.param_count(m),
    "weight_params": flops.weight_params(m),
    "decode_flops": flops.decode_flops(m, [1, 9]),
    "program_imported": any(k.split(".")[0] == "repro" for k in sys.modules),
}))
'''


def test_new_kind_is_files_only(tmp_path):
    """A configuration whose stack is one leading ``attn`` layer, then a
    kind the benchmark lacks, goes through the reference, the gap check
    and the arithmetic with only new files added to the benchmark."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    before = {p: p.read_bytes() for p in tmp_path.rglob("*") if p.is_file()}
    b = tmp_path / "bench"
    (b / "blocks" / "attn_par.py").write_text(PARALLEL)
    conf = load(b / "configs" / "stablelm-3b.json")
    conf["model"].update(name="toy-par", num_layers=3, first_k_dense=1,
                         d_model=64, num_heads=4, num_kv_heads=2, d_ff=96,
                         vocab_size=128, dtype="float32")
    conf["stack"] = {"lead": "attn", "pattern": ["attn_par"]}
    (b / "configs" / "toy-par.json").write_text(json.dumps(conf))

    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run([sys.executable, "-c", DRIVE], cwd=tmp_path,
                         env=dict(env, JAX_PLATFORMS="cpu",
                                  PYTHONPATH=str(tmp_path)),
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-4000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])

    assert got["plan"] == [[["attn"], 1], [["attn_par"], 2]]
    assert got["groups"] == ["embed", "g0", "g1", "head"]
    assert got["g1"] == ["attn", "ffn", "ln"]
    assert got["finite"] and got["shape"] == [1, 12, 128]
    assert got["gap"] < 1e-3 and 0 <= got["control"] < float("inf")
    assert not got["program_imported"]
    # the arithmetic agrees with the reference's leaves
    d, V, f, D = 64, 128, 96, 16
    attn_w = d * 4 * D + 2 * d * 2 * D + 4 * D * d + 3 * d * f
    ln = 2 * d
    assert got["param_count"] == got["leaves"] == \
        2 * V * d + ln + (attn_w + 2 * ln) + 2 * (attn_w + ln)
    assert got["weight_params"] == d * V + 3 * attn_w
    assert got["decode_flops"] == 2 * (2 * got["weight_params"]) + \
        3 * 4 * 4 * D * (1 + 9)
    assert all(p.read_bytes() == data for p, data in before.items())

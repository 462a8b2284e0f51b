"""Traffic generators, plan files and discovery by name (CPU)."""
import json
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import gen
from bench import run as R

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"
SPEC = json.loads((ROOT / "BENCHMARK.json").read_text())
SERVE_MIXES = sorted(p.stem for p in (BENCH / "traffic").glob("*.json")
                     if gen.load_mix(p.stem)["kind"] == "serve")
BIG_SEED = 2 ** 31 + 12345


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_waves_repeat_for_a_seed_and_differ_across_seeds(mix):
    m = gen.load_mix(mix)
    a = gen.wave(m, 50304, BIG_SEED, 0)
    assert a == gen.wave(m, 50304, BIG_SEED, 0)
    assert a != gen.wave(m, 50304, BIG_SEED + 1, 0)
    assert a != gen.wave(m, 50304, BIG_SEED, 1)


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_lengths_inside_clips_and_grid_and_same_multiset(mix):
    m = gen.load_mix(mix)
    g = set(int(x) for x in gen.grid(m["prompt"]))
    assert len(g) == m["prompt"]["grid"]
    want, orders = None, set()
    for seed in (0, BIG_SEED):
        for w in range(3):
            reqs = gen.wave(m, 50304, seed, w)
            P = sorted(len(p) for _, p, _ in reqs)
            A = sorted(n for _, _, n in reqs)
            assert set(P) <= g
            assert m["prompt"]["min"] <= P[0] and P[-1] <= m["prompt"]["max"]
            assert m["answer"]["min"] <= A[0] and A[-1] <= m["answer"]["max"]
            assert all(p + n <= m["cache_len"] for _, pr, n in reqs
                       for p in [len(pr)])
            assert all(0 <= t < 50304 for _, pr, _ in reqs for t in pr)
            # every seed and wave sends the same sizes, in its own order
            want = want or (P, A)
            assert (P, A) == want
            orders.add(tuple((len(p), n) for _, p, n in reqs))
    assert len(orders) == 6


@pytest.mark.parametrize("mix", SERVE_MIXES)
def test_wave_means_are_the_published_means(mix):
    m = gen.load_mix(mix)
    prompts, answers = gen.wave_sizes(m)
    assert prompts.mean() == pytest.approx(m["prompt"]["mean"], rel=0.05)
    assert answers.mean() == pytest.approx(m["answer"]["mean"], rel=0.05)


def test_train_pool_repeats_for_a_seed_and_rows_differ():
    from repro.configs.base import ArchConfig
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=8,
                     num_heads=1, num_kv_heads=1, d_ff=8, vocab_size=1000)
    m = dict(gen.load_mix("train_2k"), seq=64, batch=4, pool=3)
    t1, y1 = gen.train_pool(m, cfg, BIG_SEED)
    t2, _ = gen.train_pool(m, cfg, BIG_SEED)
    t3, _ = gen.train_pool(m, cfg, BIG_SEED + 1)
    assert t1.shape == (3, 4, 64) and y1.shape == (3, 4, 64)
    assert np.array_equal(np.asarray(t1), np.asarray(t2))
    assert not np.array_equal(np.asarray(t1), np.asarray(t3))
    np.testing.assert_array_equal(np.asarray(t1)[:, :, 1:],
                                  np.asarray(y1)[:, :, :-1])
    rows = np.asarray(t1).reshape(12, 64)
    assert len({r.tobytes() for r in rows}) == 12
    assert int(np.asarray(t1).max()) < 1000
    # the theme row: one token after a first one of its own per batch
    theme = np.asarray(t1)[:, m["theme_row"]]
    assert len(set(theme[:, 1:].ravel().tolist())) == 1
    assert len(set(theme[:, 0].tolist())) == 3


def test_train_pool_rows_but_the_theme_are_the_programs_feed():
    from repro.configs.base import ArchConfig, ShapeConfig
    from repro.data.pipeline import SyntheticLM
    cfg = ArchConfig(name="t", family="dense", num_layers=1, d_model=8,
                     num_heads=1, num_kv_heads=1, d_ff=8, vocab_size=1000)
    m = dict(gen.load_mix("train_2k"), seq=64, batch=4, pool=2)
    toks, tgts = gen.train_pool(m, cfg, BIG_SEED)
    data = SyntheticLM(cfg, ShapeConfig("x", 64, 4, "train"),
                       seed=gen.data_seed(BIG_SEED))
    keep = [r for r in range(4) if r != m["theme_row"]]
    for i in range(2):
        b = data.batch_at(i)
        np.testing.assert_array_equal(np.asarray(toks[i])[keep],
                                      np.asarray(b["tokens"])[keep])
        np.testing.assert_array_equal(np.asarray(tgts[i])[keep],
                                      np.asarray(b["targets"])[keep])


@pytest.mark.parametrize("cell", sorted(p.stem for p in
                                        (BENCH / "plans").glob("*.json")))
def test_every_plan_loads_and_covers_its_config(cell):
    from repro.core.plan import Plan
    from repro.models.model import segment_names
    from test_bench_check import load
    c = load(cell)
    plan = Plan.load(str(c["plan"]))
    cfg = R.arch_config(c["config"])
    assert set(plan.segments) == set(segment_names(cfg))


@pytest.mark.parametrize("path", sorted((BENCH / "configs").glob("*.json")),
                         ids=lambda p: p.stem)
def test_config_files_are_the_registry_configs(path):
    """The model run is the registry's, but for the keys the file lists
    as reduced; those are the keys BENCHMARK.json lists, and each names
    its published value and the one run."""
    import dataclasses
    from repro.configs import get_arch
    c = json.loads(path.read_text())
    want = dataclasses.asdict(get_arch(c["arch"]))
    for key, cut in c["reduced"].items():
        assert c["published"][key] == cut["published"] != cut["run"]
        if "field" in cut:
            want[cut["field"]] = cut["run"]
    assert dataclasses.asdict(R.arch_config(c)) == want
    listed = {e["name"]: e for e in SPEC["configs"]}.get(path.stem)
    if listed is not None:
        assert sorted(listed["reduced"]) == sorted(c["reduced"])
        assert listed["source"] == c["source"]


def test_every_named_file_exists_and_metrics_read_nothing_off_kind():
    for w in SPEC["workloads"]:
        c = R.load_cell(w["name"])
        assert c["plan"].is_file()
        assert c["end_to_end"] and c["per_layer"]
    for m in SPEC["per_layer"]:
        R.metric_reader(m["name"])


def test_new_files_alone_add_a_cell(tmp_path):
    """A new configuration, mix, plan, limits and metric are new files;
    the existing ones stay as they are."""
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*")
              if p.is_file()}
    b = tmp_path / "bench"
    cfg = json.loads((b / "configs" / "stablelm-3b.json").read_text())
    (b / "configs" / "tiny-lm.json").write_text(json.dumps(cfg))
    mix = dict(gen.load_mix("alpaca"), wave=5)
    (b / "traffic" / "tiny_mix.json").write_text(json.dumps(mix))
    shutil.copy(b / "plans" / "stablelm-3b.serve.alpaca.json",
                b / "plans" / "tiny-lm.serve.json")
    (b / "limits" / "tiny-lm.serve.json").write_text('{"gap": 1.0}')
    (b / "metrics" / "waves_seen.py").write_text(
        "def read(win):\n    return 7.0\n")
    spec["configs"].append({"name": "tiny-lm", "source": "x",
                            "file": "bench/configs/tiny-lm.json",
                            "reduced": [], "why": "x"})
    spec["workloads"].append({"name": "tiny-lm.serve", "config": "tiny-lm",
                              "traffic": "tiny_mix", "chips": 1, "why": "x"})
    spec["per_layer"].append({"name": "waves_seen", "unit": "1",
                              "better": "higher", "source": "host_clock",
                              "layer": "serving engine",
                              "moves": "serve_tokens_per_s",
                              "workloads": ["tiny-lm.serve"]})
    spec["end_to_end"][1]["workloads"].append("tiny-lm.serve")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    c = R.load_cell("tiny-lm.serve", root=tmp_path)
    assert c["mix"]["wave"] == 5 and c["config"]["arch"] == "stablelm-3b"
    assert [m["name"] for m in c["per_layer"]] == ["waves_seen"]
    assert R.per_layer(c, None, root=b / "metrics") == {
        "waves_seen": {"value": 7.0, "unit": "1"}}
    assert all(p.read_bytes() == data for p, data in before.items())


def test_refuses_without_a_tpu_and_prints_no_result():
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm-3b.serve.alpaca",
         "--seed", str(BIG_SEED), "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**__import__("os").environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "no result" in out.stderr


def test_refuses_in_a_checkout_with_only_the_benchmark(tmp_path):
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    env = {k: v for k, v in __import__("os").environ.items()
           if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "stablelm-3b.serve.alpaca",
         "--seed", "3", "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(env, JAX_PLATFORMS="cpu"))
    assert out.returncode != 0
    assert out.stdout.strip() == ""

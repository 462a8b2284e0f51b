"""The serving readers of the engine's spans and the model's scopes, on
a hand-made trace (CPU): two programs that share an instruction name,
nested operations, and the engine's host spans."""
import json
import re
import shutil
from pathlib import Path

import pytest

from bench import flops
from bench import serve_trace as st
from bench import trace as tr
from bench.run import metric_reader
from bench.window import Window

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000
READERS = ("queue_wait_mean_s", "device_idle.admit", "device_idle.step",
           "decode_attn_ms", "decode_kv_write_ms")
#: the decode step's instructions and their scope paths
SCOPES = {
    "while.1": "jit(serve_step)/g0/while",
    "fusion.1": "jit(serve_step)/g0/while/body/closed_call/attn/kv_write/"
                "jit(_where)/select_n",
    "fusion.2": "jit(serve_step)/g0/while/body/closed_call/attn/dot_general",
    "fusion.3": "jit(serve_step)/g0/while/body/closed_call/mlp/dot_general",
    "fusion.4": "jit(serve_step)/head/dot_general",
}


def ev(name, start_ms, end_ms):
    return tr.Event(name, start_ms * MS, end_ms * MS)


def step_run(t):
    """One decode step at ``t`` ms: a layer loop holding a cache write
    (4 ms), attention (5 ms) and the FFN (6 ms), then the head (2 ms)."""
    return [ev("%while.1", t, t + 18), ev("%fusion.1", t + 1, t + 5),
            ev("%fusion.2", t + 5, t + 10), ev("%fusion.3", t + 10, t + 16),
            ev("%fusion.4", t + 18, t + 20)]


def hand_made(engine_spans=True):
    # a prefill whose fusion.1 (8 ms) shares the step's instruction name
    ops = [ev("%fusion.1", 0, 8), ev("%fusion.2", 10, 20)]
    ops += step_run(30) + step_run(60)
    mods = [ev("jit_prefill(1)", 0, 20), ev("jit_serve_step(2)", 30, 50),
            ev("jit_serve_step(2)", 60, 80)]
    host = [ev("bench.window", 0, 100)]
    if engine_spans:
        host += [ev("serve.run", 0, 90),
                 ev("serve.admit", 1, 25), ev("serve.prefill", 1, 21),
                 ev("serve.admit", 25, 29), ev("serve.prefill", 25, 28),
                 ev("serve.step", 29, 55), ev("serve.step", 55, 85)]
    return tr.Trace([tr.Device("/device:TPU:0", ops, mods)], host)


def window(trace, kind="serve"):
    model = json.loads((ROOT / "bench" / "configs" / "stablelm-3b.json")
                       .read_text())["model"]
    mix = json.loads((ROOT / "bench" / "traffic" / "alpaca.json").read_text())
    return Window(kind, model, mix, flops.peaks("TPU v5 lite"), 1, trace,
                  trace.host_span("bench.window"), prefills=[12, 4],
                  steps=[[12], [13]])


def instructions(text):
    """Instruction name -> its line of a compiled text, less metadata."""
    out = {}
    for line in text.splitlines():
        m = re.match(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ", line)
        if m:
            out[m.group(1)] = re.sub(r", metadata=\{.*\}", "", line)
    return out


@pytest.fixture
def scopes(monkeypatch):
    monkeypatch.setattr(st, "step_scopes", lambda win: SCOPES)


def test_operations_go_to_the_program_run_that_holds_them():
    t = hand_made()
    w = t.host_span("bench.window")
    runs = st.ops_by_run(t.devices[0], st.STEP_PROGRAM, w)
    assert [(r.start // MS, len(ops)) for r, ops in runs] == [(30, 5),
                                                            (60, 5)]
    (pre, ops), = st.ops_by_run(t.devices[0], "jit_prefill", w)
    assert [o.name for o in ops] == ["%fusion.1", "%fusion.2"]


def test_own_time_gives_the_loop_only_what_its_body_leaves():
    ops = step_run(0)
    own = st.exclusive_ns(ops)
    assert [x // MS for x in own] == [3, 4, 5, 6, 2]
    assert sum(own) == tr.busy_ns(tr.Device("d", ops), (0, 20 * MS))
    # operations that start together: the shorter one is the inner one
    same = [ev("a", 0, 10), ev("b", 0, 4)]
    assert [x // MS for x in st.exclusive_ns(same)] == [6, 4]


def test_readers_on_the_hand_made_trace(scopes):
    win = window(hand_made())
    got = {n: metric_reader(n)(win) for n in READERS}
    assert got["queue_wait_mean_s"] == pytest.approx(0.013)  # (1 + 25) / 2
    # idle inside admits: [8,10) + [20,25) + [25,29); steps: [29,30) +
    # [50,55) + [55,60) + [80,85); over a 100-ms window
    assert got["device_idle.admit"] == pytest.approx(11.0)
    assert got["device_idle.step"] == pytest.approx(16.0)
    # per step, the prefill's fusion.1 apart: kv_write 4 ms, attn 4 + 5
    assert got["decode_kv_write_ms"] == pytest.approx(4.0)
    assert got["decode_attn_ms"] == pytest.approx(9.0)
    idle = metric_reader("device_idle.serve")(win)
    assert idle == pytest.approx(42.0)
    assert got["device_idle.admit"] + got["device_idle.step"] <= idle


def test_readers_read_nothing_off_kind_or_without_spans_and_scopes(
        monkeypatch):
    train = window(tr.load(DATA / "small.pbtxt"), kind="train")
    for n in READERS:
        assert metric_reader(n)(train) is None, n
    # a program without the engine's spans and the model's scopes
    monkeypatch.setattr(st, "step_scopes", lambda win: {
        k: "jit(serve_step)/while/body/dot_general" for k in SCOPES})
    bare = window(hand_made(engine_spans=False))
    for n in READERS:
        assert metric_reader(n)(bare) is None, n
    # a step compiled again that is not the traced one: read nothing
    monkeypatch.setattr(st, "step_scopes", lambda win: {
        k: v for k, v in SCOPES.items() if k != "fusion.4"})
    other = window(hand_made())
    for n in ("decode_attn_ms", "decode_kv_write_ms"):
        assert metric_reader(n)(other) is None, n


def test_scope_paths_parse_compiled_text():
    text = "\n".join([
        "ENTRY %main.9 (p: f32[2]) -> f32[2] {",
        '  %fusion.1 = f32[2]{0} fusion(%p), kind=kLoop, calls=%fc.1, '
        'metadata={op_name="jit(serve_step)/g0/while/body/attn/kv_write/'
        'select_n" source_file="a.py" source_line=3}',
        '  ROOT %copy.7 = f32[2]{0} copy(%fusion.1)',
        '  %while.2 = (s32[]) while(%t), condition=%c, body=%b, '
        'metadata={op_name="jit(serve_step)/g0/while"}',
        "}"])
    assert st.scope_paths(text) == {
        "fusion.1": "jit(serve_step)/g0/while/body/attn/kv_write/select_n",
        "copy.7": "", "while.2": "jit(serve_step)/g0/while"}
    assert st.in_scope("a/attn/b", "attn")
    assert not st.in_scope("a/attn_out/b", "attn")


def test_step_compiled_again_is_the_cells_step(tmp_path):
    """The reader's decode step, lowered from the cell's files alone, is
    the program the serve cell compiles and runs (small widths)."""
    import jax
    import jax.numpy as jnp

    from bench import gen
    from bench.run import arch_config, load_cell
    from repro.core.plan import Plan
    from repro.models.model import cache_specs, model_specs
    from repro.models.params import init_params
    from repro.serve import engine as eng
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    name = spec["workloads"][0]["name"]
    conf = json.loads((tmp_path / "bench" / "configs" /
                       "stablelm-3b.json").read_text())
    conf["model"].update(num_layers=2, d_model=64, num_heads=2,
                         num_kv_heads=2, d_ff=128, vocab_size=256)
    (tmp_path / "bench" / "configs" / "stablelm-3b.json").write_text(
        json.dumps(conf))
    mix = dict(gen.load_mix("alpaca"), capacity=2, cache_len=32)
    (tmp_path / "bench" / "traffic" / "alpaca.json").write_text(
        json.dumps(mix))
    cell = load_cell(name, root=tmp_path)
    m = cell["config"]["model"]
    progs = st.serve_programs(m, cell["mix"], [5], root=tmp_path)
    # as bench/cell_serve.py compiles it, from weights made on the device
    cfg = arch_config(cell["config"])
    params = jax.jit(lambda k: init_params(model_specs(cfg), k))(
        gen.jax_key(7))
    engine = eng.ServeEngine(cfg, Plan.load(str(cell["plan"])), capacity=2,
                             cache_len=32, params=params)
    sds = lambda t: jax.tree.map(  # noqa: E731
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), t)
    i32 = jax.ShapeDtypeStruct((2,), jnp.int32)
    want = engine._step.lower(sds(params), cache_specs(cfg, 2, 32), i32,
                              i32).compile().as_text()
    # the same instructions, operands and scopes; only the Python stack
    # frames of the two lowerings differ
    assert len(instructions(want)) > 50
    assert instructions(progs["step"]) == instructions(want)
    assert st.scope_paths(progs["step"]) == st.scope_paths(want)
    assert "kv_write" in progs[5]
    assert st.serve_programs(dict(m, d_ff=64), cell["mix"],
                             root=tmp_path) is None

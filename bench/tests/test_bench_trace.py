"""The trace reduction on small recorded traces (CPU)."""
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def small():
    return tr.load(DATA / "small.pbtxt")


def test_devices_and_host_spans(small):
    assert [d.name for d in small.devices] == ["/device:TPU:0",
                                               "/device:TPU:1"]
    assert small.host_span("bench.window") == (1_000_000, 26_000_000)
    assert small.host_span("absent") is None


def test_busy_is_the_union_not_the_sum(small):
    w = small.host_span("bench.window")
    dev = small.devices[0]
    summed = sum(e.end - e.start for e in dev.ops)
    assert summed == 19_000_000
    assert tr.busy_ns(dev, w) == 17_000_000
    # averaged over the two chips: (17 + 5) / 2 ms
    assert tr.mean_busy_s(small, w) == pytest.approx(0.011)


def test_busy_is_clipped_to_the_window(small):
    dev = small.devices[0]
    assert tr.busy_ns(dev, (3_000_000, 9_000_000)) == 5_000_000


def test_program_runs_and_exposed_collectives(small):
    w = small.host_span("bench.window")
    dev = small.devices[0]
    runs = tr.module_runs(dev, "jit_train_step", w)
    assert [(e.end - e.start) for e in runs] == [10_000_000, 10_000_000]
    # all-reduce.1 overlaps fusion.1 by 1 ms, all-reduce.2 fusion.3 by 1 ms
    assert tr.exposed_collective_ns(dev, w) == 4_000_000


def test_idle_gaps_are_named_by_the_host(small):
    w = small.host_span("bench.window")
    gaps = tr.idle_gaps(small, w)
    assert gaps == [["host:untraced", 0.005], ["bench.wait", 0.002],
                    ["host:untraced", 0.001]]
    top = tr.top_ops(small, w, 2)
    assert top == [["fusion.3", 0.003], ["fusion.9", 0.0025]]


def test_binary_xplane_round_trip(tmp_path):
    from jax.profiler import ProfileData
    raw = ProfileData.text_proto_to_serialized_xspace(
        (DATA / "small.pbtxt").read_text())
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(raw)
    t = tr.load(path)
    w = t.host_span("bench.window")
    assert tr.busy_ns(t.devices[0], w) == 17_000_000


@pytest.mark.parametrize("a,b,want", [
    ([(0, 10)], [(2, 3), (5, 7)], [(0, 2), (3, 5), (7, 10)]),
    ([(0, 4), (6, 9)], [(3, 7)], [(0, 3), (7, 9)]),
    ([(0, 4)], [], [(0, 4)]),
    ([(0, 4)], [(0, 4)], []),
])
def test_subtract(a, b, want):
    assert tr.subtract(a, b) == want


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (7, 7)]) == \
        [(0, 4), (5, 6)]


def test_metric_readers_on_the_small_trace(small):
    import json

    from bench import flops
    from bench.run import metric_reader
    from bench.window import Window
    root = Path(__file__).resolve().parents[2]
    m = json.loads((root / "bench" / "configs" /
                    "stablelm-3b-4l.json").read_text())["model"]
    mix = json.loads((root / "bench" / "traffic" / "train_2k.json")
                     .read_text())
    w = small.host_span("bench.window")
    win = Window("train", m, mix, flops.peaks("TPU v5 lite"), 1, small, w,
                 tokens=8192)
    idle = metric_reader("device_idle.train")(win)
    assert idle == pytest.approx(100 * (1 - 0.011 / 0.025))
    mfu = metric_reader("train_mfu")(win)
    assert mfu == pytest.approx(100 * flops.train_flops_per_token(m, 2048)
                                * 8192 / (0.025 * 197e12))
    # readers of other kinds, or of programs absent here, read nothing
    for name in ("device_idle.serve", "serve_mfu", "prefill_ms_per_token"):
        assert metric_reader(name)(win) is None
    assert win.program_seconds("jit_train_step") == [0.01, 0.01]


def test_trace_recorded_on_the_chip():
    """Three runs of one small program on a TPU v5e, inside a host span
    ``bench.window``; the chip's ``/device:CUSTOM:...`` plane is not a
    device."""
    t = tr.load(DATA / "probe.xplane.pb")
    assert [d.name for d in t.devices] == ["/device:TPU:0"]
    runs = t.devices[0].modules
    assert len(runs) == 3 and all(e.name.startswith("jit__lambda")
                                  for e in runs)
    assert all(not e.name.startswith("%") or " = " not in e.name
               for e in t.devices[0].ops)
    whole = (min(e.start for e in runs), max(e.end for e in runs))
    busy = tr.busy_ns(t.devices[0], whole)
    assert 0 < busy <= whole[1] - whole[0]
    assert busy >= sum(e.end - e.start for e in runs) * 0.9
    assert t.host_span("bench.window") is not None

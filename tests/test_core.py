"""ComParX core: combinator counting (paper formula), DB modes,
fusion guarantee — with hypothesis property tests."""
import math

import pytest

from hypothesis import given, settings, strategies as st

from repro.configs import get_arch, get_shape
from repro.core.combinator import (Combination, GlobalKnobs, clause_grid,
                                   enumerate_combinations, flag_subsets,
                                   paper_combination_count)
from repro.core.cost_model import CostTerms
from repro.core.db import SweepDB
from repro.core.fusion import best_uniform, fuse
from repro.core.plan import Plan, uniform_plan
from repro.core.providers import all_providers, get_provider
from repro.core.segment import fragment
from repro.models.context import SegmentClause


# --- paper formula -----------------------------------------------------------

@given(st.lists(st.integers(0, 8), min_size=1, max_size=5),
       st.integers(0, 4), st.integers(0, 4))
def test_paper_combination_count_formula(ns, rtl, d):
    expect = sum((2 ** n - 1) * (2 ** (rtl + d) - 1) for n in ns)
    assert paper_combination_count(ns, rtl, d) == expect


@given(st.integers(0, 6))
def test_flag_subsets_cardinality(n):
    flags = [f"f{i}" for i in range(n)]
    subsets = flag_subsets(flags)
    assert len(subsets) == 2 ** n                 # incl. bare provider
    assert len(set(subsets)) == len(subsets)      # unique


def test_enumeration_count_matches_product():
    space = {"remat": ("none", "dots"), "kernel": ("xla",),
             "block_q": (256, 512), "block_k": (512,),
             "scan_unroll": (1,), "mlstm_chunk": (256,)}
    providers = ["tensor_par", "fsdp"]
    combos = enumerate_combinations(providers, space)
    n_clauses = len(clause_grid(space))
    expect = sum(2 ** len(get_provider(p).flags) for p in providers) \
        * n_clauses
    assert len(combos) == expect
    assert len({c.cid for c in combos}) == len(combos)


def test_enumeration_budget_is_deterministic():
    combos1 = enumerate_combinations(["tensor_par"], budget=5, seed=3)
    combos2 = enumerate_combinations(["tensor_par"], budget=5, seed=3)
    assert [c.cid for c in combos1] == [c.cid for c in combos2]
    assert len(combos1) == 5


def test_combination_json_roundtrip():
    c = Combination("fsdp", frozenset({"shard_both_axes"}),
                    SegmentClause(remat="dots", kernel="pallas"))
    c2 = Combination.from_json(c.to_json())
    assert c == c2 and c.cid == c2.cid


# --- DB modes ----------------------------------------------------------------

def _combo(i=0):
    return Combination("fsdp", frozenset(), SegmentClause(block_q=256 + i))


def test_db_new_mode_appends_index():
    db = SweepDB(":memory:")
    assert db.open_project("p", "new") == "p"
    assert db.open_project("p", "new") == "p_1"
    assert db.open_project("p", "new") == "p_2"


def test_db_overwrite_mode():
    db = SweepDB(":memory:")
    db.open_project("p", "new")
    db.register("p", "g0", _combo())
    db.record("p", "g0", _combo().cid, status="done", cost={"total_s": 1})
    db.open_project("p", "overwrite")
    assert db.results("p") == []


def test_db_continue_mode_preserves_results():
    db = SweepDB(":memory:")
    db.open_project("p", "new")
    db.register("p", "g0", _combo())
    db.record("p", "g0", _combo().cid, status="done",
              cost={"compute_s": 1.0})
    assert db.open_project("p", "continue") == "p"
    rows = db.results("p")
    assert len(rows) == 1 and rows[0]["status"] == "done"
    # re-register is a no-op (the resume path)
    db.register("p", "g0", _combo())
    assert db.status("p", "g0", _combo().cid) == "done"


# --- fusion guarantee (hypothesis) ------------------------------------------

@st.composite
def cost_tables(draw):
    cfg = get_arch("granite-8b").smoke()
    segs = fragment(cfg)
    n_combos = draw(st.integers(2, 5))
    combos = [Combination("fsdp", frozenset(),
                          SegmentClause(block_q=128 + i))
              for i in range(n_combos)]
    table = {}
    for s in segs:
        rows = []
        for c in combos:
            t = draw(st.floats(1e-4, 10.0, allow_nan=False))
            rows.append((c, CostTerms(compute_s=t)))
        table[s.name] = rows
    return cfg, table


@given(cost_tables())
@settings(max_examples=25, deadline=None)
def test_fusion_never_worse_than_best_uniform(cfg_table):
    """ComPar's theoretical guarantee (paper §4.1): the fused output is at
    least as good as the best single compiler."""
    cfg, table = cfg_table
    shape = get_shape("train_4k").smoke()
    plan = fuse(cfg, shape, None, table)
    _, best_total = best_uniform(cfg, table)
    assert plan.meta["predicted_total_s"] <= best_total + 1e-9


@given(cost_tables())
@settings(max_examples=10, deadline=None)
def test_viterbi_equals_argmin_without_boundaries(cfg_table):
    cfg, table = cfg_table
    shape = get_shape("train_4k").smoke()
    p1 = fuse(cfg, shape, None, table, boundary_costs=False)
    p2 = fuse(cfg, shape, None, table, boundary_costs=True)  # mesh=None -> 0
    assert abs(p1.meta["predicted_total_s"]
               - p2.meta["predicted_total_s"]) < 1e-9


def test_viterbi_fusion_matches_brute_force_with_boundary_costs(monkeypatch):
    """Exactness of the Viterbi DP beyond the degenerate mesh=None case:
    on a meshed 3-segment chain with non-trivial (deterministic,
    asymmetric) boundary costs, ``fuse(boundary_costs=True)`` must equal
    the exhaustive minimum over every combination chain."""
    import hashlib
    import itertools

    import repro.core.fusion as F

    cfg = get_arch("granite-8b").smoke()
    shape = get_shape("train_4k").smoke()
    segs = fragment(cfg)
    assert len(segs) == 3                       # embed, g0, head
    combos = [Combination("fsdp", frozenset(),
                          SegmentClause(block_q=128 + 16 * i))
              for i in range(3)]

    def synth_cost(cid: str) -> float:
        return int(hashlib.sha1(cid.encode()).hexdigest()[:6], 16) / 0xffffff

    table = {s.name: [(c, CostTerms(compute_s=synth_cost(s.name + c.cid)))
                      for c in combos] for s in segs}

    def synth_boundary(cfg_, shape_, mesh_, a, sa, b, sb, hw=None):
        # deterministic, direction-sensitive stand-in for the resharding
        # collective a real mesh would charge
        return synth_cost(sa.name + a.cid + sb.name + b.cid)

    monkeypatch.setattr(F, "boundary_cost_s", synth_boundary)
    mesh_sentinel = object()                    # only boundary_cost_s sees it
    plan = F.fuse(cfg, shape, mesh_sentinel, table, boundary_costs=True)

    # brute force over all 3^3 chains
    best_total, best_chain = None, None
    for chain in itertools.product(range(3), repeat=len(segs)):
        total = sum(table[s.name][chain[i]][1].total_s
                    for i, s in enumerate(segs))
        for i in range(1, len(segs)):
            a, sa = table[segs[i - 1].name][chain[i - 1]][0], segs[i - 1]
            b, sb = table[segs[i].name][chain[i]][0], segs[i]
            total += synth_boundary(cfg, shape, mesh_sentinel, a, sa, b, sb)
        if best_total is None or total < best_total:
            best_total, best_chain = total, chain

    assert abs(plan.meta["predicted_total_s"] - best_total) < 1e-12
    expected = {s.name: combos[best_chain[i]] for i, s in enumerate(segs)}
    assert plan.segments == expected
    assert plan.meta["fusion"] == "viterbi-boundary"


def test_plan_json_roundtrip(tmp_path):
    cfg = get_arch("granite-8b").smoke()
    plan = uniform_plan(cfg, "hybrid2d", frozenset({"shard_vocab"}),
                        SegmentClause(remat="dots"),
                        GlobalKnobs(microbatches=2))
    path = str(tmp_path / "plan.json")
    plan.save(path)
    p2 = Plan.load(path)
    assert p2.segments == plan.segments
    assert p2.knobs == plan.knobs


def test_provider_applicability():
    cfg = get_arch("qwen3-moe-30b-a3b")
    segs = {s.name: s for s in fragment(cfg)}
    ep = all_providers()["expert_par"]
    assert ep.applicable(cfg, segs["g0"])      # MoE stack
    assert ep.applicable(cfg, segs["embed"])   # non-stack ok
    dense = get_arch("granite-8b")
    dseg = [s for s in fragment(dense) if s.kind == "stack"][0]
    assert not ep.applicable(dense, dseg)      # dense stack: NO

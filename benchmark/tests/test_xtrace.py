import gzip
import json
from pathlib import Path

import pytest

import xtrace

BENCH = Path(__file__).resolve().parent.parent
RULES = json.loads((BENCH / "trace_categories.json").read_text())


def test_recorded_trace_reduces():
    """120 ms of a Mistral-7B chat-steady trace on a v5e (PR 23), cut by
    xtrace.excerpt: an admission prologue, so the MLP leads."""
    rep = json.load(gzip.open(BENCH / "tests" / "data" / "trace_small.json.gz", "rt"))
    r = xtrace.reduce(rep, RULES, 32)
    assert r["devices"] == 1 and r["problems"] == []
    # the reduction's arithmetic as it stood before PR 26, to the digit
    assert (r["busy_s"], r["window_s"], r["forward_passes"]) == (0.119731951, 0.119734013, 0.0)
    assert r["category_s"] == {
        "mlp": 0.081709236, "kv_pool_copy": 0.009677147, "other_device": 0.000463673,
        "norms": 0.000168988, "attn_proj": 0.019802228, "attention": 0.007910679}
    assert r["breakdown"]["device_ops"][:3] == [
        ["mlp:fusion", 0.054255608], ["mlp:multiply_convert_fusion", 0.027426832],
        ["attn_proj:fusion", 0.016645683]]
    assert 0.115 < r["busy_s"] <= r["window_s"] < 0.121
    # leaf ops do not overlap: the categories add up to the busy time
    assert sum(r["category_s"].values()) == pytest.approx(r["busy_s"], rel=0.01)
    assert r["category_s"]["mlp"] / r["busy_s"] > 0.6
    assert r["category_s"]["other_device"] / r["busy_s"] < 0.01
    ops = r["breakdown"]["device_ops"]
    assert len(ops) <= 10 and ops[0][0] == "mlp:fusion"
    assert ["attention:ragged_attention_pool"] == [n for n, _ in ops if n.startswith("attention:")]
    assert all(" " not in n and "," not in n for n, _ in ops)
    assert ops == sorted(ops, key=lambda kv: -kv[1])


def test_recorded_four_chip_trace_bills_collectives_apart():
    """30 ms of Mistral-7B over a mesh of model:4 on four v5e chips (PR 26's
    proof run; device planes only): the all-gathers, all-reduces and permutes
    sit inside the scopes mlp, qkv_proj, o_proj and kv_write."""
    rep = json.load(gzip.open(BENCH / "tests" / "data" / "trace_tp4_small.json.gz", "rt"))
    r = xtrace.reduce(rep, RULES, 32, chips=4)
    assert (r["devices"], r["forward_passes"], r["problems"]) == (4, 2.0, [])
    cat = r["category_s"]
    assert (cat["collectives"], cat["mlp"], cat["attn_proj"]) == (
        0.003319988, 0.0065231915, 0.0011359545)
    assert sum(cat.values()) == pytest.approx(r["busy_s"], rel=0.01)
    # by the scope rules alone the weight GEMMs would carry the mesh's time
    old = xtrace.reduce(rep, dict(RULES, op_rules=[]), 32, chips=4)["category_s"]
    assert "collectives" not in old
    assert (old["mlp"], old["attn_proj"]) == (0.00738608025, 0.00253545275)
    assert xtrace.reduce(rep, RULES, 32, chips=8)["problems"] == [
        "the trace holds 4 device planes, the cell runs on 8 chips"]


def ev(name, start_us, dur_us, scope=""):
    return [name, start_us * 1000, dur_us * 1000, scope]


def synthetic():
    head = "jit(chunk)/while/body/lm_head/dot_general"
    mlp = "jit(chunk)/while/body/while/body/mlp/dot_general"
    ops = [
        # module run 1: a while over 3 steps, each an mlp op and a head op
        ev("%while.1 = (s32[]) while(...)", 0, 300),
        *[e for i in range(3) for e in (
            ev("%fusion.5 = bf16[] fusion(...)", 10 + 100 * i, 60, mlp),
            ev("%fusion.9 = f32[] fusion(...)", 72 + 100 * i, 20, head),
            ev("%fusion.10 = f32[] fusion(...)", 93 + 100 * i, 5, head))],
        # module run 2, after a 200 us gap: 2 steps and one unscoped copy
        ev("%copy.3 = bf16[] copy(...)", 500, 40),
        ev("%fusion.9 = f32[] fusion(...)", 540, 20, head),
        ev("%fusion.9 = f32[] fusion(...)", 560, 20, head),
        ev("%fusion.77.remat = s32[] fusion(...)", 580, 20, "jit(chunk)/while/body/sampling/argmax"),
    ]
    modules = [ev("jit_chunk(1)", 0, 300), ev("jit_chunk(1)", 500, 100)]
    host = [
        ev("$batcher.py:5147 _dispatch_chunk", 250, 300),
        ev("$pjit.py:1 cache_miss", 320, 150),
        ev("$builtins isinstance", 390, 20),
        ev("$pxla.py:2 __call__", 330, 10),
    ]
    other = [ev("$selector_events.py:750 _process_events", 0, 600)]
    return {"planes": [
        {"name": "/device:TPU:0", "lines": [{"name": "XLA Modules", "events": modules},
                                           {"name": "XLA Ops", "events": ops}]},
        {"name": "/host:CPU", "lines": [{"name": "python3", "events": other},
                                       {"name": "python3", "events": host}]},
    ]}


def test_self_time_passes_and_gaps():
    r = xtrace.reduce(synthetic(), RULES, n_layers=2)
    cat = r["category_s"]
    assert cat["mlp"] == pytest.approx(180e-6)
    assert cat["lm_head"] == pytest.approx((3 * 25 + 40) * 1e-6)
    assert cat["grammar_sampling"] == pytest.approx(20e-6)
    assert cat["kv_pool_copy"] == pytest.approx(40e-6)          # the unscoped copy
    assert cat["other_device"] == pytest.approx((300 - 3 * 85) * 1e-6)   # the while's own time
    assert r["busy_s"] == pytest.approx(400e-6) and r["window_s"] == pytest.approx(600e-6)
    # a pass = one execution of the head: the most frequent head op per module run
    assert r["forward_passes"] == 5
    gaps = dict(r["breakdown"]["idle_gaps"])
    # the 200 us gap is billed to the dispatching thread's innermost real frame
    assert gaps == {"host:_pjit.py:1_cache_miss": pytest.approx(200e-6)}
    names = [n for n, _ in r["breakdown"]["device_ops"]]
    assert "grammar_sampling:fusion" in names and "other_device:while" in names


def test_no_device_plane_gives_nothing():
    r = xtrace.reduce({"planes": [{"name": "/host:CPU", "lines": []}]}, RULES, 2)
    assert r["devices"] == 0 and r["problems"]

"""The benchmark's readers of set-up and of the step's memory
(benchmark/readers/setup_timeline.py, step_memory.py; ISSUE 34) on a
hand-made span record and facts: the timeline's parts and what no span
covers sum to ``setup_s``, ``first`` picks the earliest span, a record
without the span gives None, and the memory metrics come from the span
of the step the window ran. Then the same readers over the record of a
real tiny build and two ``run_training`` calls."""

import json
import os

import pytest

from benchmark import harness as hs
from benchmark.readers import program_trace as pt
from benchmark.readers import setup_timeline, step_memory
from gke_ray_train_tpu.obs import trace as obs_trace

T0 = 100.0          # the window opens here, on the spans' clock
SETUP_S = 60.0      # so the process started at 40.0
INIT_S = 5.0
LIMIT = 16_000_000_000

NEW_METRICS = {
    "state_build_s": "setup_s", "step_lower_s": "setup_s",
    "first_step_s": "setup_s", "setup_loop_s": "setup_s",
    "setup_unspanned_s": "setup_s",
    "hbm_unused_share.train": "train_tok_s_chip",
    "remat_estimate_gap_gb.train": "train_tok_s_chip"}


@pytest.fixture
def record():
    obs_trace.RECORD.clear()
    yield obs_trace.RECORD
    obs_trace.RECORD.clear()


@pytest.fixture
def facts():
    return {"t0": T0, "t1": 151.0, "setup_s": SETUP_S,
            "spans": {"init_s": INIT_S}, "notes": []}


def add(record, name, t0, t1, ident, parent=None, **attrs):
    record.spans.append({"name": name, "id": ident, "parent": parent,
                         "t0": t0, "t1": t1, "step": None, **attrs})


def memory(peak, limit=LIMIT):
    return {"peak": peak, "arguments": 4_000_000_000, "outputs": 1,
            "aliased": 1, "temporaries": peak - 4_000_000_000,
            "code": 26_000_000, "limit": limit}


def setup_record(record):
    """A set-up of 60 s: imports 8, weights 5 (the benchmark's own, no
    span), state 1.5, build 20 (lower 12, compile 7), two loop calls
    of 4 and 9 s, the window's own spans after ``T0``."""
    add(record, "state_build", 53.5, 55.0, 1, args_bytes=4_000_000_000)
    add(record, "step_lower", 56.0, 68.0, 3, parent=2, trace_s=7.0,
        to_mlir_s=4.5)
    add(record, "step_compile", 68.0, 75.0, 4, parent=2, cache="hit",
        retrieval_s=6.5, backend_compile_s=0.0)
    add(record, "step_build", 56.0, 76.0, 2, source="compiled",
        remat_keep=["mlp/gate_up"], remat_estimate_bytes=14_500_000_000,
        xla_memory=memory(14_000_000_000))
    add(record, "compile", 78.5, 81.5, 6, parent=5)
    add(record, "train_loop", 78.0, 82.0, 5, steps=1, to_first_step_s=3.5)
    add(record, "compile", 83.5, 85.5, 8, parent=7)
    add(record, "train_loop", 83.0, 92.0, 7, steps=2, to_first_step_s=2.5)
    # the window: its loop call ends after t0 and is no part of set-up
    add(record, "compile", 100.5, 102.5, 11, parent=10)
    add(record, "step_iter", 100.4, 102.6, 10, parent=9)
    add(record, "train_loop", 100.1, 151.0, 9, steps=20)


PARTS = {"state_build": 1.5, "step_build": 20.0, "train_loop": 13.0}


def test_the_parts_and_what_no_span_covers_sum_to_setup_s(record, facts):
    setup_record(record)
    got = {part: setup_timeline.read(facts, part) for part in PARTS}
    assert got == pytest.approx(PARTS)
    unspanned = setup_timeline.read(
        facts, "unspanned", needs=["state_build", "train_loop"])
    assert unspanned == pytest.approx(60.0 - 5.0 - 34.5)
    assert sum(got.values()) + INIT_S + unspanned == pytest.approx(SETUP_S)
    # a child of the build is a part by name, and under its parent
    lower = setup_timeline.read(facts, "step_lower")
    assert lower == pytest.approx(12.0) and lower <= got["step_build"]
    # the whole timeline went on ONE earlier line, at the first call
    (note,) = [n for n in facts["notes"] if "set-up" in n["note"]]
    assert note["top_level"] == ["state_build", "step_build",
                                 "train_loop", "train_loop"]
    assert note["process_start_to_first_program_span_s"] == \
        pytest.approx(13.5)
    # what lay between the program's calls, the benchmark's own
    # weights among it: it sums to what no span covers plus init_s
    assert [g[:2] for g in note["gaps_s"]] == [
        ["process start", "state_build"], ["state_build", "step_build"],
        ["step_build", "train_loop"], ["train_loop", "train_loop"],
        ["train_loop", "window"]]
    assert [g[2] for g in note["gaps_s"]] == pytest.approx(
        [13.5, 1.0, 2.0, 1.0, 8.0])
    assert sum(g[2] for g in note["gaps_s"]) == pytest.approx(
        unspanned + INIT_S)
    assert note["spans"]["train_loop"] == {
        "s": pytest.approx(13.0), "count": 2, "each": [
            {"steps": 1, "to_first_step_s": 3.5},
            {"steps": 2, "to_first_step_s": 2.5}]}
    assert note["spans"]["step_compile"]["each"] == [
        {"cache": "hit", "retrieval_s": 6.5, "backend_compile_s": 0.0}]
    assert note["spans"]["step_lower"]["each"] == [
        {"trace_s": 7.0, "to_mlir_s": 4.5}]
    assert note["unspanned_s"] == pytest.approx(unspanned)
    json.dumps(note)


@pytest.mark.parametrize("pick,expected", [("first", 3.0), ("sum", 5.0)])
def test_first_picks_the_earliest_compile_before_the_window(
        record, facts, pick, expected):
    setup_record(record)
    # whatever order the record holds them in
    record.spans.rotate(3)
    assert setup_timeline.read(facts, "compile", pick=pick) == \
        pytest.approx(expected)


@pytest.mark.parametrize("part", ["state_build", "train_loop", "compile",
                                  "unspanned"])
def test_a_record_without_the_span_gives_none(record, facts, part):
    """The parent commit's side: it records the build alone."""
    add(record, "step_lower", 56.0, 68.0, 3, parent=2)
    add(record, "step_build", 56.0, 76.0, 2, source="compiled")
    assert setup_timeline.read(
        facts, part, needs=["state_build", "train_loop"]) is None
    assert setup_timeline.read(facts, "step_lower") == pytest.approx(12.0)


def test_an_empty_record_and_no_program_give_none(record, facts,
                                                  monkeypatch):
    for part in ("step_lower", "unspanned"):
        assert setup_timeline.read(facts, part) is None
    assert step_memory.read(facts, "unused_share") is None
    assert [n for n in facts["notes"]] == []
    monkeypatch.setattr(pt, "program", lambda: None)
    assert setup_timeline.read(dict(facts), "step_lower") is None
    assert step_memory.read(dict(facts), "estimate_gap_gb") is None


def test_memory_metrics_of_the_step_the_window_ran(record, facts):
    setup_record(record)
    assert step_memory.read(facts, "unused_share") == \
        pytest.approx(100.0 * 2 / 16)
    assert step_memory.read(facts, "estimate_gap_gb") == \
        pytest.approx(0.5)
    (note,) = [n for n in facts["notes"] if "memory" in n["note"]]
    assert note["xla_memory"]["peak"] == 14_000_000_000
    assert note["remat_estimate_bytes"] == 14_500_000_000
    assert note["remat_keep"] == ["mlp/gate_up"]


def test_an_estimate_that_erred_low_reads_negative(record, facts):
    add(record, "step_build", 56.0, 76.0, 2, source="compiled",
        remat_estimate_bytes=13_000_000_000,
        xla_memory=memory(14_000_000_000))
    assert step_memory.read(facts, "estimate_gap_gb") == \
        pytest.approx(-1.0)


def test_the_fallback_s_span_wins_where_a_build_fell_back(record, facts):
    """Two builds before the window (a tool builds the step again): the
    later one is what the window ran, here one that fell back."""
    add(record, "step_build", 56.0, 66.0, 2, source="compiled",
        remat_keep_fallback=False,
        remat_estimate_bytes=15_000_000_000,
        xla_memory=memory(15_900_000_000))
    add(record, "step_build", 66.0, 76.0, 3, source="compiled",
        remat_keep_fallback=True, remat_keep=[],
        remat_estimate_bytes=11_000_000_000,
        xla_memory=memory(10_400_000_000))
    # and one built after the window opened is not the window's
    add(record, "step_build", 160.0, 170.0, 4, source="compiled",
        remat_estimate_bytes=1, xla_memory=memory(2))
    assert step_memory.read(facts, "unused_share") == \
        pytest.approx(100.0 * 5.6 / 16)
    assert step_memory.read(facts, "estimate_gap_gb") == \
        pytest.approx(0.6)
    (note,) = [n for n in facts["notes"] if "memory" in n["note"]]
    assert note["remat_keep_fallback"] is True


@pytest.mark.parametrize("span_attrs", [
    {"xla_memory": {}, "remat_estimate_bytes": 5},        # XLA:CPU's
    {"remat_estimate_bytes": 5},                          # the parent's
    {"xla_memory": memory(14_000_000_000, limit=None),
     "remat_estimate_bytes": None}])                      # no limit
def test_nothing_to_read_is_none_not_an_error(record, facts, span_attrs):
    add(record, "step_build", 56.0, 76.0, 2, source="compiled",
        **span_attrs)
    assert step_memory.read(facts, "unused_share") is None
    if span_attrs.get("remat_estimate_bytes") is None \
            or not span_attrs.get("xla_memory"):
        assert step_memory.read(facts, "estimate_gap_gb") is None


@pytest.mark.parametrize("name", sorted(NEW_METRICS))
def test_the_new_metrics_are_wired(record, facts, name):
    """BENCHMARK.json's entry, the metric's file and its reader agree,
    and the reader takes the file's arguments."""
    setup_record(record)
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    (entry,) = [m for m in bench["per_layer"] if m["name"] == name]
    cells = [c["name"] for c in bench["workloads"]]
    assert entry["workloads"] == cells[:4]
    assert entry["source"] == "program_span"
    assert entry["layer"] == "plan / compile"
    assert entry["moves"] == NEW_METRICS[name]
    spec = hs.load_json(hs.BENCH_DIR, "metrics", name + ".json")
    assert spec["unit"] == entry["unit"]
    assert spec["reader"] in ("setup_timeline", "step_memory")
    out = hs.read_metrics([name], facts)
    assert out[name]["unit"] == entry["unit"]
    assert out[name]["value"] == out[name]["value"]      # a number
    # appended, nothing before them moved: they are the list's last
    assert [m["name"] for m in bench["per_layer"]][-7:].count(name) == 1
    assert os.path.exists(os.path.join(
        hs.BENCH_DIR, "readers", spec["reader"] + ".py"))


def test_the_readers_over_a_real_build_and_two_loop_calls(record):
    """The program's own record, as a training cell's set-up makes it:
    state, build, two ``run_training`` calls, then the window."""
    import time

    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.perf.cache import build_or_load_step
    from gke_ray_train_tpu.train import (
        make_optimizer, make_train_state, make_train_step)
    from gke_ray_train_tpu.train.loop import run_training
    from tests.test_obs import _batches
    t_start = time.perf_counter()
    cfg = tiny(vocab_size=128, d_model=32, n_layers=1, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32")
    opt = make_optimizer(1e-3)
    state = make_train_state(cfg, opt, jax.random.key(0))
    batch = jax.tree.map(jnp.asarray, next(iter(_batches(1)(0))))
    step = build_or_load_step(make_train_step(cfg, opt, donate=False),
                              state, batch, label="tiny train_step")
    for n in (1, 2):
        run_training(state, step, _batches(n), epochs=1, log_every=1)
    t0 = time.perf_counter()
    run_training(state, step, _batches(2), epochs=1, log_every=1)
    facts = {"t0": t0, "t1": time.perf_counter(),
             "setup_s": t0 - t_start, "spans": {"init_s": 0.0},
             "notes": []}
    parts = {p: setup_timeline.read(facts, p)
             for p in ("state_build", "step_build", "train_loop")}
    assert all(v is not None and v > 0 for v in parts.values())
    unspanned = setup_timeline.read(
        facts, "unspanned", needs=["state_build", "train_loop"])
    assert 0 <= unspanned < facts["setup_s"]
    assert sum(parts.values()) + unspanned == \
        pytest.approx(facts["setup_s"])
    assert setup_timeline.read(facts, "step_lower") <= parts["step_build"]
    first = setup_timeline.read(facts, "compile", pick="first")
    assert 0 < first <= setup_timeline.read(facts, "compile")
    assert first <= parts["train_loop"]
    (note,) = [n for n in facts["notes"] if "set-up" in n["note"]]
    assert note["spans"]["train_loop"]["count"] == 2
    assert note["spans"]["compile"]["count"] == 2
    assert [e["steps"] for e in note["spans"]["train_loop"]["each"]] \
        == [1, 2]
    # XLA:CPU reports no limit, so there is no share of it to read
    assert step_memory.read(facts, "unused_share") is None
    assert step_memory.read(facts, "estimate_gap_gb") is None

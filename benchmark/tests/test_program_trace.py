"""The readers of the program's own names (scope_share, scope_roofline,
span_stat) on synthetic facts: real instruction names and op_names of a
tiny LoRA train step compiled on the CPU, made-up times, a made-up span
record. Shares and the roofline come out exact; with no table, no trace
or no record the readers return None."""

import pytest

from benchmark import flops
from benchmark import harness as hs
from benchmark.readers import program_trace as pt
from benchmark.readers import scope_roofline, scope_share, span_stat

DIMS = {"layers": 2, "hidden": 32, "ff": 64, "heads": 2, "kv_heads": 2,
        "head_dim": 16, "vocab": 64}


@pytest.fixture(scope="module")
def table():
    import jax
    import jax.numpy as jnp

    from gke_ray_train_tpu.models import tiny
    from gke_ray_train_tpu.obs import trace as obs_trace
    from gke_ray_train_tpu.train import (
        LoraConfig, make_optimizer, make_train_state, make_train_step)
    cfg = tiny(vocab_size=64, d_model=32, n_layers=2, n_heads=2,
               n_kv_heads=2, d_ff=64, dtype="float32",
               param_dtype="float32", remat=True)
    opt = make_optimizer(1e-3)
    lora = LoraConfig(r=4, alpha=8, targets=(
        "wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down"))
    state = make_train_state(cfg, opt, jax.random.key(0), lora_cfg=lora)
    step = make_train_step(cfg, opt, lora_cfg=lora, grad_accum=2,
                           donate=False)
    batch = {"inputs": jnp.zeros((4, 16), jnp.int32),
             "targets": jnp.zeros((4, 16), jnp.int32),
             "weights": jnp.ones((4, 16), jnp.float32)}
    return obs_trace.scope_table(
        step.lower(state, batch).compile().as_text())


@pytest.fixture
def record(table):
    from gke_ray_train_tpu.obs import trace as obs_trace
    obs_trace.RECORD.clear()
    obs_trace.RECORD.scope_tables["tiny train_step"] = table
    yield obs_trace.RECORD
    obs_trace.RECORD.clear()


def pick(table, phase, path_end):
    """One real instruction of the phase whose scope path ends so."""
    from gke_ray_train_tpu.obs import trace as obs_trace
    for name, op in sorted(table.items()):
        path = obs_trace.scope_path(op)
        if pt.phase_of(op) == phase and path and path.endswith(path_end):
            return name
    raise AssertionError((phase, path_end))


def event(name):
    # as the device event names it: the instruction's HLO text
    return f"%{name} = f32[4,16,32]{{2,1,0}} fusion(f32[4,16,32]{{2,1,0}} %p)"


@pytest.fixture
def facts(table):
    seconds = {                       # 10 s in all
        pick(table, "forward", "qkv/base"): 1.0,
        pick(table, "forward", "qkv/lora"): 0.5,
        pick(table, "recompute", "gate_up/base"): 2.0,
        pick(table, "backward", "mlp/down/base"): 3.0,
        pick(table, "forward", "unembed"): 0.5,
        pick(table, "backward", "loss"): 0.5,
        pick(table, None, "optimizer/clip"): 1.0,
        "fusion.99999": 1.5,          # an instruction no table knows
    }
    counts = dict.fromkeys(seconds, 6)
    counts[pick(table, "forward", "unembed")] = 4   # once a micro-pass
    return {
        "trace": {"op_time": {event(k): v for k, v in seconds.items()},
                  "op_count": {event(k): counts[k] for k in seconds},
                  "devices": 1},
        "work": {"rows_per_call": 2, "seq": 16}, "dims": DIMS,
        "peaks": {"flops_bf16": 1e9}, "t0": 100.0, "t1": 200.0,
        "notes": []}


def test_phase_rules():
    assert pt.phase_of("jit(s)/jvp()/while/body/attn/qkv/base/dot") == \
        "forward"
    assert pt.phase_of("jit(s)/transpose(jvp())/while/checkpoint/"
                       "rematted_computation/mlp/down/base/dot") == \
        "recompute"
    assert pt.phase_of("jit(s)/transpose(jvp(loss))/mul") == "backward"
    assert pt.phase_of("jit(s)/optimizer/clip/sqrt") is None
    assert pt.phase_of("") is None


@pytest.mark.parametrize("args,expected", [
    ({"phase": "forward"}, 20.0),
    ({"phase": "recompute"}, 20.0),
    ({"phase": "backward"}, 35.0),
    ({"scope": "(^|/)base$"}, 60.0),
    ({"scope": "^(unembed|loss)(/|$)"}, 10.0),
    ({"scope": "(^|/)base$", "phase": "forward"}, 10.0),
    ({"unscoped": True}, 15.0)])
def test_scope_share_is_exact(record, facts, args, expected):
    assert scope_share.read(facts, **args) == pytest.approx(expected)


def test_phases_print_together_and_sum_to_100(record, facts):
    scope_share.read(facts, phase="forward")
    scope_share.read(facts, phase="backward")       # computed once
    notes = [n for n in facts["notes"] if "by phase" in n["note"]]
    assert len(notes) == 1
    note = notes[0]
    assert note["optimizer"] == pytest.approx(10.0)
    assert note["rest"] == pytest.approx(15.0)
    assert sum(note[k] for k in pt.PHASES + ("optimizer", "rest")) == \
        pytest.approx(100.0)
    by_scope = next(n for n in facts["notes"] if "by scope" in n["note"])
    assert by_scope["scopes"]["(unscoped)"] == {"none": 1.5}


def test_scope_roofline_is_exact(record, facts):
    # 4 passes x 2 FLOP x 2 rows x 16 positions x 2 layers' weights
    per_pass = 2.0 * 2 * 16 * 2 * flops.layer_matmul_params(DIMS)
    least = 4 * per_pass / 1e9
    got = scope_roofline.read(facts, scope="(^|/)base$", phase="forward",
                              once_a_pass="^unembed(/|$)")
    assert got == pytest.approx(100.0 * least / 1.0)
    assert scope_roofline.read(facts, scope="(^|/)base$",
                               once_a_pass="^moe/") is None


@pytest.mark.parametrize("broken", ["no_table", "no_trace", "no_times"])
def test_nothing_to_read_is_none_not_an_error(record, facts, broken):
    if broken == "no_table":
        record.scope_tables.clear()
    elif broken == "no_trace":
        del facts["trace"]
    else:
        facts["trace"]["op_time"] = {}
    assert scope_share.read(facts, phase="forward") is None
    assert scope_share.read(facts, unscoped=True) is None
    assert scope_roofline.read(facts, scope="(^|/)base$") is None


def test_a_program_without_the_record_reads_none(monkeypatch, facts):
    """The parent commit's side of the comparison."""
    monkeypatch.setattr(pt, "program", lambda: None)
    assert scope_share.read(facts, phase="forward") is None
    assert scope_roofline.read(facts, scope="(^|/)base$") is None
    assert span_stat.read(facts, name="step_iter") is None


def spans(record):
    def add(name, t0, t1, ident, parent=None, **kw):
        record.spans.append({"name": name, "id": ident, "parent": parent,
                             "t0": t0, "t1": t1, "step": None, **kw})
    add("step_lower", 10.0, 12.0, 2, parent=1)
    add("step_compile", 12.0, 17.0, 3, parent=1)
    add("step_build", 10.0, 17.5, 1, source="compiled")
    for i, host in enumerate((0.010, 0.030, 0.020)):
        base, ident = 110.0 + 10 * i, 10 * (i + 1)
        add("data_wait", base, base + 0.5, ident + 1, parent=ident)
        add("metrics_fetch", base + 1, base + 4, ident + 2, parent=ident)
        add("log_emit", base + 4, base + 4.001, ident + 3, parent=ident)
        add("step_iter", base, base + 3.5 + host, ident)
    add("metrics_fetch", 199.0, 199.5, 99)        # the epoch's end: no parent
    add("step_iter", 198.0, 205.0, 50)            # ends after the window


def test_span_stat_takes_children_off_and_keeps_to_the_window(record,
                                                              facts):
    spans(record)
    got = span_stat.read(facts, name="step_iter", stat="median",
                         minus=["metrics_fetch", "data_wait"], scale=1000,
                         also=["log_emit"])
    assert got == pytest.approx(20.0)
    note = facts["notes"][-1]
    assert note["count"] == 3 and note["log_emit"] == pytest.approx(0.003)
    assert span_stat.read(facts, name="step_build", stat="sum",
                          when="setup", also=["step_lower",
                                              "step_compile"]) == \
        pytest.approx(7.5)
    assert facts["notes"][-1]["step_compile"] == pytest.approx(5.0)
    assert span_stat.read(facts, name="step_build", when="window") is None
    assert span_stat.read(facts, name="eval") is None


def test_new_metrics_are_wired(record, facts):
    """Every new metric file names a reader that takes its args."""
    spans(record)
    bench = hs.load_json(hs.ROOT, "BENCHMARK.json")
    new = [m["name"] for m in bench["per_layer"]
           if hs.load_json(hs.BENCH_DIR, "metrics", m["name"] + ".json")
           ["reader"] in ("scope_share", "scope_roofline", "span_stat")]
    assert len(new) == 9
    out = hs.read_metrics(new, facts)
    assert set(out) == set(new)
    assert out["nf4_matmul_roofline.train"]["unit"] == "%"

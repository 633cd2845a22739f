"""The one training body and its family seam (``drivers/train.py``):
every cell's driver is ``train.run`` bound to a family, no driver module
holds a second body, and ``run`` holds a family to the contract before
it builds anything."""

import ast
import functools
import os
import types

import pytest

from benchmark import harness as hs
from benchmark.drivers import common, train

BENCH = hs.load_json(hs.ROOT, "BENCHMARK.json")


@pytest.mark.parametrize("workload", [c["name"] for c in BENCH["workloads"]])
def test_every_cell_runs_the_one_body(workload):
    cell = hs.find_cell(BENCH, workload)
    run = hs.driver_of(hs.cell_files(BENCH, cell)).run
    if run is train.run:
        family = train.DENSE
    else:
        assert isinstance(run, functools.partial) and run.func is train.run
        assert not run.args and set(run.keywords) == {"family"}
        family = run.keywords["family"]
    train.check_family(family)


def test_no_second_run_body():
    drivers = os.path.join(hs.BENCH_DIR, "drivers")
    bodies = []
    for name in sorted(os.listdir(drivers)):
        if name.endswith(".py"):
            with open(os.path.join(drivers, name)) as f:
                tree = ast.parse(f.read())
            bodies += [name for node in ast.walk(tree)
                       if isinstance(node, ast.FunctionDef)
                       and node.name == "run"]
    assert bodies == ["train.py"]


def toy_model_config(config, *, dtype, param_dtype, attn_impl,
                     remat_policy, max_seq_len):
    return common.model_config(config, dtype=dtype, param_dtype=param_dtype,
                               attn_impl=attn_impl, remat_policy=remat_policy)


def test_a_toy_family_is_accepted():
    toy = types.SimpleNamespace(**dict(vars(train.DENSE),
                                       model_config=toy_model_config))
    train.check_family(toy)
    train.check_family(types.SimpleNamespace(
        **vars(toy), layer_kinds=lambda config, layer: ("full", "dense"),
        gradient_readings=lambda table: {}))


@pytest.mark.parametrize("broken", [
    {"build_lora": None},                                    # missing
    {"model_config": common.model_config},                   # no max_seq_len
    {"build_params": lambda cfg, config, seed, mesh: None},  # no quant_kind
    {"layer_kinds": lambda config: None},                    # too few
    {"gradient_readings": lambda: {}},
])
def test_a_family_off_the_contract_is_refused(broken):
    bad = types.SimpleNamespace(**dict(vars(train.DENSE), **broken))
    with pytest.raises(hs.BenchFailure):
        train.check_family(bad)
    # run refuses it before it reads a thing of the run
    with pytest.raises(hs.BenchFailure):
        train.run({}, family=bad)

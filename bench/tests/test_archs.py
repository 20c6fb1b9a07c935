"""Every configuration names its architecture, every architecture module
gives the harness what it uses, and a configuration that names none, names
one that is not there, or carries keys its architecture does not build is
refused, never built as something else."""
import glob
import json
import os

import pytest

import run
from conftest import BENCH, ROOT, arch, benchmark, copy_checkout

# what the harness takes from an architecture's module
NAMES = ("model_config", "Reference", "Shape", "PALLAS_OPS", "SMALL")
ARCHS = sorted(os.path.basename(p)[:-3]
               for p in glob.glob(os.path.join(BENCH, "archs", "*.py")))


def _config(entry):
    with open(os.path.join(ROOT, entry["file"])) as f:
        return json.load(f)


@pytest.mark.parametrize("entry", benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_every_config_names_an_arch_that_exists(entry):
    name = _config(entry)["arch"]
    assert name in ARCHS
    module = arch(name)
    module.model_config(_config(entry))


@pytest.mark.parametrize("name", ARCHS)
def test_every_arch_gives_the_harness_its_names(name):
    module = arch(name)
    for n in NAMES:
        assert hasattr(module, n), n
    assert callable(module.model_config)
    assert callable(module.Shape.from_config)
    assert callable(module.Reference.logits)
    assert module.PALLAS_OPS and all(isinstance(op, str)
                                     for op in module.PALLAS_OPS)
    assert module.SMALL


def test_missing_arch_stops_the_run():
    config = _config(benchmark()["configs"][0])
    del config["arch"]
    with pytest.raises(SystemExit, match=r"bench/archs/<arch>\.py"):
        run.arch_module(config)


@pytest.mark.parametrize("name", ["no_such_arch", "../configs/x", ""])
def test_unknown_arch_stops_the_run(name):
    config = dict(_config(benchmark()["configs"][0]), arch=name)
    with pytest.raises(SystemExit, match="bench/archs/"):
        run.arch_module(config)


def test_cell_of_a_config_without_arch_stops(tmp_path):
    """Through ``cell_spec``, as a run starts: the message names the file
    the harness looked for."""
    root = copy_checkout(tmp_path)
    entry = benchmark()["configs"][0]
    config = _config(entry)
    config["arch"] = "dense_mla"
    (root / entry["file"]).write_text(json.dumps(config))
    workload = next(w["name"] for w in benchmark()["workloads"]
                    if w["config"] == entry["name"])
    with pytest.raises(SystemExit, match=r"bench/archs/dense_mla\.py"):
        run.cell_spec(workload, root=str(root))


@pytest.mark.parametrize("key,value", [
    ("kv_lora_rank", 512), ("n_routed_experts", 64),
    ("num_experts_per_tok", 6), ("sliding_window", 4096),
    ("q_lora_rank", None)])
def test_dense_refuses_keys_of_other_architectures(key, value):
    config = _config(benchmark()["configs"][0])
    config[key] = value
    with pytest.raises(ValueError, match=key):
        arch("dense_gqa").model_config(config)


@pytest.mark.parametrize("key,value", [("hidden_act", "gelu"),
                                       ("torch_dtype", "float32")])
def test_dense_refuses_what_it_does_not_build(key, value):
    config = dict(_config(benchmark()["configs"][0]), **{key: value})
    with pytest.raises(ValueError):
        arch("dense_gqa").model_config(config)

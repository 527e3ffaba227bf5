"""The benchmark finds its configurations, traffic mixes and metrics by
name, and ``BENCHMARK.json`` keeps to the benchmark's naming rules."""

import json
import os
import re
import shutil

import pytest

from bench import arch, run, traffic
from bench import trace as tr
from bench.roofline import kernels

ROOT = run.ROOT
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bench():
    return run.load_benchmark()


def test_every_cell_resolves(bench):
    for cell in bench["workloads"]:
        c, config, mix = run.find_cell(bench, cell["name"])
        assert c is cell or c == cell
        assert config["name"] == cell["config"]
        traffic.validate(mix)
        run.metrics_for(bench, cell["name"], False)


def test_every_metric_has_a_reader(bench):
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert callable(run.reader(m["name"]))


def test_names_and_units(bench):
    names = [c["name"] for c in bench["configs"]]
    names += [w["name"] for w in bench["workloads"]]
    names += [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    names += [w["traffic"] for w in bench["workloads"]]
    names += [k for c in bench["configs"] for k in c["reduced"]]
    for n in names:
        assert NAME.match(n), n
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["per_layer"]:
        # each listed cell reports the end-to-end metric the metric moves
        moved = e2e[m["moves"]]
        for cell in m.get("workloads", cells):
            assert cell in moved.get("workloads", cells), (m["name"], cell)


def test_config_files_are_the_programs(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        # raises on a difference
        cfg = run.program_config(config, arch.of(config))
        assert cfg.num_layers == config["num_hidden_layers"]
        assert set(c["reduced"]) <= set(config["reduced"])


def test_a_new_traffic_file_is_found(bench, tmp_path):
    src = os.path.join(run.HERE, "traffic")
    tdir = tmp_path / "traffic"
    shutil.copytree(src, tdir)
    with open(os.path.join(src, "decode.json")) as f:
        mix = json.load(f)
    mix["clients"] = 8
    (tdir / "decode_half.json").write_text(json.dumps(mix))
    cell = {"name": "olmoe8.decode-half", "config": "olmoe-1b-7b-l8",
            "traffic": "decode_half", "chips": 1, "why": "test"}
    added = dict(bench, workloads=bench["workloads"] + [cell])
    _, _, found = run.find_cell(added, "olmoe8.decode-half",
                                traffic_dir=str(tdir))
    assert found["clients"] == 8


def test_a_new_metric_file_is_found(tmp_path):
    (tmp_path / "answer.py").write_text("def read(run):\n    return 42.0\n")
    assert run.reader("answer", str(tmp_path))({}) == 42.0


def test_unknown_cell_raises(bench):
    with pytest.raises(KeyError, match="no workload"):
        run.find_cell(bench, "no.such.cell")


ARCH_API = ("leaf_specs", "logits", "program_keys", "moe_layers", "widths",
            "model_flops")


def test_every_config_names_an_architecture(bench):
    for c in bench["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            config = json.load(f)
        mod = arch.of(config)
        assert all(callable(getattr(mod, fn)) for fn in ARCH_API)
        assert set(mod.moe_layers(config)) <= set(
            range(config["num_hidden_layers"]))


def test_an_unknown_architecture_raises():
    with pytest.raises(KeyError, match="no architecture"):
        arch.load("no_such_arch")


def test_every_kernel_file_declares_its_work():
    assert set(tr.KERNEL_MODULES) == {"moe_decode", "moe_gmm",
                                      "flash_decode_paged"}
    for name, mod in tr.KERNEL_MODULES.items():
        assert name in mod.OPS and all(tr.KERNELS[op] == name
                                       for op in mod.OPS)
        assert mod.STEP in ("decode", "chunk")
        assert mod.LAYERS in ("all", "moe")
        assert callable(mod.work)


def test_a_new_kernel_file_is_found(tmp_path):
    (tmp_path / "new_decode_attention.py").write_text(
        'OPS = ("new_decode_attention",)\nSTEP = "decode"\n'
        'LAYERS = "all"\n\n\ndef work(w, call, ks, dtype):\n'
        '    return 1.0, 2.0\n')
    found = kernels.load_all(str(tmp_path))
    assert list(found) == ["new_decode_attention"]
    assert found["new_decode_attention"].work({}, {}, None, "bf16") == (
        1.0, 2.0)

"""``BENCHMARK.json`` and the files the harness finds by name."""
import importlib
import json
import os
import re
import shutil
import subprocess
import sys

import pytest

from bench.harness import ROOT, Cell, load_json

SPEC = load_json("BENCHMARK.json")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_top_level_keys():
    assert set(SPEC) == {"command", "paths", "run_seconds", "configs",
                         "workloads", "end_to_end", "per_layer"}
    assert SPEC["paths"] == ["bench"]
    assert 1 <= SPEC["run_seconds"] <= 51
    for word in SPEC["command"]:
        assert not word.startswith("/") and ".." not in word


def test_names_and_entry_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in SPEC[k]]
    assert all(NAME.match(n) for n in names)
    for k in ("configs", "workloads"):
        assert len({x["name"] for x in SPEC[k]}) == len(SPEC[k])
    metrics = SPEC["end_to_end"] + SPEC["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    for c in SPEC["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
    for w in SPEC["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200 and w["chips"] in (1, 4)
    for m in SPEC["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert 0.01 <= m["bound"] <= 0.25
    for m in SPEC["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source",
                                          "layer", "moves"}
        assert m["moves"] in {e["name"] for e in SPEC["end_to_end"]}


@pytest.mark.parametrize("cfg", SPEC["configs"], ids=lambda c: c["name"])
def test_config_file_states_the_reduction(cfg):
    """The file holds the sizes as run; ``reduced`` names each cut, never a
    width; the reference module it names exists."""
    config = load_json(cfg["file"])
    assert config["name"] == cfg["name"]
    assert sorted(config["reduced"]) == sorted(cfg["reduced"])
    widths = ("hidden_size", "intermediate_size", "head_dim",
              "num_attention_heads", "num_key_value_heads")
    assert not set(cfg["reduced"]) & set(widths)
    ref = importlib.import_module(f"bench.configs.{config['reference']}")
    # what the harness must know of the family, the reference states
    assert callable(ref.step_flops) and callable(ref.init_weights)
    assert set(ref.PROGRAM_KEYS) <= set(config["sizes"])
    assert ref.PROGRAM_FAMILY and ref.PROGRAM_LEAVES


@pytest.mark.parametrize("wl", SPEC["workloads"], ids=lambda w: w["name"])
def test_every_cell_finds_its_files(wl):
    cell = Cell.find(wl["name"])
    assert cell.traffic["global_batch"] > 0 and cell.traffic["seq_len"] > 0
    assert cell.limits and set(cell.limits) <= {"loss_gap", "grad_gap", "change_gap"}
    assert cell.per_layer
    for name in cell.per_layer:
        assert callable(importlib.import_module(f"bench.metrics.{name}").read)


def test_peaks_table_is_keyed_by_device_kind():
    peaks = load_json("bench", "peaks.json")
    assert "TPU v5 lite" in peaks["devices"] and peaks["source"]
    v5e = peaks["devices"]["TPU v5 lite"]
    assert v5e == {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                   "hbm_bytes": 16e9}


def test_command_runs_from_the_paths():
    assert os.path.isfile(os.path.join(ROOT, SPEC["command"][1]))
    assert json.dumps(SPEC).__len__() < 64 * 1024


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "smollm-360m.train-2k",
         "--seed", str(2 ** 31 + 3), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_without_a_tpu_it_exits_nonzero_and_prints_no_result():
    r = _run_cli(ROOT)
    assert r.returncode != 0 and r.stdout == ""
    assert "no TPU" in r.stderr


def test_benchmark_files_alone_do_not_run(tmp_path):
    """A checkout that holds only BENCHMARK.json and bench/ has no program."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = _run_cli(tmp_path)
    assert r.returncode != 0 and r.stdout == ""

"""The harness finds every piece of a cell by name, and a run that cannot
give a result prints none."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT


def benchmark():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


CELLS = [w["name"] for w in benchmark()["workloads"]]
METRICS = [m["name"] for m in benchmark()["per_layer"]]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_pieces_found_by_name(cell):
    c = harness.load_cell(cell)
    assert c.spec["arch"] == c.spec["arch"].strip()
    assert {"setup_s"} <= {m["name"] for m in c.end_to_end}
    assert len(c.end_to_end) >= 2 and c.per_layer
    assert 0 < float(c.check["max_logit_gap"]["limit"])
    assert harness.max_len_of(c.mix) > 0


@pytest.mark.parametrize("cell", CELLS)
def test_configuration_file_matches_the_program(cell):
    c = harness.load_cell(cell)
    cfg = harness.model_config(c.spec)
    assert cfg.name == c.spec["arch"]
    from repro.serve_engine.ladder import build_ladder
    ladder = build_ladder(c.spec["ladder_bits"], d=float(cfg.d_model))
    for op in ladder:
        pt = c.spec["operating_points"][str(op.bits)]
        assert (pt["r"], pt["b_x_tilde"]) == (op.r, op.b_x_tilde)


@pytest.mark.parametrize("metric", METRICS)
def test_each_metric_has_a_reader(metric):
    read = harness.load_reader(metric)
    assert callable(read)


def test_the_program_runs_the_file_s_sizes():
    c = harness.load_cell(CELLS[0])
    cfg = harness.model_config(c.spec)
    for k in harness.MODEL_KEYS:
        if k in c.spec:
            assert getattr(cfg, k) == c.spec[k], k


@pytest.mark.parametrize("name", [None, "no_such_family", "../harness"])
def test_an_unknown_family_is_refused(name):
    from bench import families, weights
    spec = dict(harness.load_cell(CELLS[0]).spec, reference=name)
    with pytest.raises(ValueError):
        families.load(spec)
    with pytest.raises(ValueError):
        weights.make_params(spec, 0)


def _run(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", CELLS[0], "--seed",
         "0", "--seconds", "10", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    p = _run(ROOT)
    assert p.returncode != 0
    assert "correct" not in p.stdout


def test_bare_checkout_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _run(str(tmp_path))
    assert p.returncode != 0
    assert "correct" not in p.stdout


@pytest.mark.parametrize("entry", benchmark()["configs"],
                         ids=lambda c: c["name"])
def test_configuration_file_lists_what_it_changed(entry):
    """BENCHMARK.json's `reduced` is the file's, and each key it names is
    set in the file, as run."""
    with open(os.path.join(ROOT, entry["file"])) as f:
        spec = json.load(f)
    assert spec["reduced"] == entry["reduced"]
    for k in entry["reduced"]:
        assert k in spec

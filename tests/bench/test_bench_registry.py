"""The chip benchmark's files are found by name from ``BENCHMARK.json``,
keep to its contract, and the run refuses a host without a TPU."""
import json
import os
import re
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from benchmarks.chip import harness, weights  # noqa: E402

BENCH = harness.benchmark()
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


@pytest.mark.parametrize("name", CELLS)
def test_cell_files_are_found_by_name(name):
    c = harness.cell(name)
    assert c["config"]["name"] == c["workload"]["config"]
    assert c["traffic"]["name"] == c["workload"]["traffic"]
    assert callable(c["loop"].run)
    assert c["per_layer"], "every cell reports a per-layer metric"
    e2e = {m["name"] for m in c["end_to_end"]}
    assert "setup_s" in e2e and len(e2e) >= 2
    for m in c["per_layer"]:
        assert m["moves"] in e2e
        assert callable(harness.load_module("metrics", m["name"]).read)
    assert set(c["limits"]) >= {"unverified_steps"}


def test_a_new_file_is_found_by_name(tmp_path, monkeypatch):
    """A later cell adds files and an entry, and edits none."""
    for kind in ("configs", "traffic", "loops", "metrics"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / "m.json").write_text(json.dumps({"name": "m"}))
    (tmp_path / "traffic" / "t.json").write_text(
        json.dumps({"name": "t", "kind": "k"}))
    (tmp_path / "loops" / "k.py").write_text("def run(*a):\n    return 7\n")
    (tmp_path / "metrics" / "x.y.py").write_text(
        "def read(ctx):\n    return ctx['v']\n")
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    bench = {"workloads": [{"name": "m.t", "config": "m", "traffic": "t",
                            "chips": 1}],
             "end_to_end": [{"name": "setup_s", "unit": "s"}],
             "per_layer": [{"name": "x.y", "unit": "%",
                            "workloads": ["m.t"]}]}
    c = harness.cell("m.t", bench)
    assert c["loop"].run() == 7
    assert harness.per_layer(c["per_layer"], {"v": 3.5}) == {
        "x.y": {"value": 3.5, "unit": "%"}}
    assert harness.per_layer(c["per_layer"], {"v": None}) == {}


def test_benchmark_json_contract():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
    assert os.path.isfile(os.path.join(ROOT, BENCH["command"][1]))
    assert 1 <= BENCH["run_seconds"] <= 51
    names = [x["name"] for k in ("configs", "workloads", "end_to_end",
                                 "per_layer") for x in BENCH[k]]
    assert len(names) == len(set(names))
    assert all(NAME.match(n) for n in names)
    cells = {w["name"] for w in BENCH["workloads"]}
    for c in BENCH["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert harness.load_json(path)["name"] == c["name"]
        assert set(c["reduced"]) <= set(harness.load_json(path)["reduced"])
    for w in BENCH["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert e2e["setup_s"]["bound"] <= 0.25
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", cells)) <= cells
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e


@pytest.mark.parametrize("tie", [True, False])
def test_weights_have_the_programs_layout(tie):
    import jax

    from repro.models import model as M
    c = harness.load_json(harness.find("configs", "opt-1.3b-l4"))
    c = dict(c, n_layers=2, tie_embeddings=tie)
    want = jax.eval_shape(lambda: M.init_params(harness.arch(c),
                                                jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: weights.make(c, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)


def test_weights_follow_the_seed():
    import numpy as np
    c = {"n_layers": 1, "d_model": 8, "n_heads": 2, "n_kv_heads": 2,
         "head_dim": 4, "d_ff": 16, "vocab_size": 10,
         "param_dtype": "float32"}
    a, b = weights.make(c, 2 ** 40 + 1), weights.make(c, 2 ** 40 + 1)
    d = weights.make(c, 1)
    np.testing.assert_array_equal(a["head"]["w"], b["head"]["w"])
    assert not np.array_equal(a["head"]["w"], d["head"]["w"])


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "chip", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, env=env,
        cwd=ROOT, timeout=300)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"metrics"' not in p.stdout

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
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


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


CONFIG_FILES = sorted(f[:-5] for f in os.listdir(os.path.join(
    harness.HERE, "configs")) if f.endswith(".json"))


def _layouts_agree(c):
    """``weights.make`` and the program's ``init_params`` give the same tree
    of shapes and dtypes for configuration ``c``."""
    import jax

    from repro.models import model as M
    want = jax.eval_shape(lambda: M.init_params(harness.arch(c),
                                                jax.random.PRNGKey(0)))
    got = jax.eval_shape(lambda: weights.make(c, 3))
    assert jax.tree.structure(got) == jax.tree.structure(want)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    return got


@pytest.mark.parametrize("tie", [True, False])
@pytest.mark.parametrize("name", CONFIG_FILES)
def test_weights_have_the_programs_layout(name, tie):
    c = harness.load_json(harness.find("configs", name))
    _layouts_agree(dict(c, n_layers=2, tie_embeddings=tie))


def test_weights_follow_the_seed():
    import numpy as np
    c = {"reference": "dense_decoder", "n_layers": 1, "d_model": 8,
         "n_heads": 2, "n_kv_heads": 2, "head_dim": 4, "d_ff": 16,
         "vocab_size": 10, "param_dtype": "float32"}
    a, b = weights.make(c, 2 ** 40 + 1), weights.make(c, 2 ** 40 + 1)
    d = weights.make(c, 1)
    np.testing.assert_array_equal(a["head"]["w"], b["head"]["w"])
    assert not np.array_equal(a["head"]["w"], d["head"]["w"])


PINNED = {
    # the train cell's widths at one layer, its head tied to the embedding
    "opt-1.3b-l4.layers-1": dict(
        harness.load_json(harness.find("configs", "opt-1.3b-l4")),
        n_layers=1),
    "small-untied": {"reference": "dense_decoder", "n_layers": 2,
                     "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
                     "head_dim": 16, "d_ff": 96, "vocab_size": 300,
                     "tie_embeddings": False, "param_dtype": "bfloat16"},
}


@pytest.mark.parametrize("case", sorted(PINNED))
def test_weights_are_pinned(case):
    """`weights.make` gives bitwise the weights that the digests pin."""
    import hashlib

    import jax
    import numpy as np
    pinned = harness.load_json(os.path.join(DATA, "weight_digests.json"))
    got = {}
    leaves = jax.tree_util.tree_flatten_with_path(
        weights.make(PINNED[case], pinned["seed"]))[0]
    for path, leaf in leaves:
        a = np.asarray(leaf)
        got["/".join(k.key for k in path)] = hashlib.sha256(
            f"{a.dtype} {a.shape}".encode() + a.tobytes()).hexdigest()
    assert got == pinned["digests"][case]


def _opt_arch(config):
    """The ``ArchConfig`` that the OPT files were run with, built by hand
    from its fifteen keys."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=config["name"], family=config["family"],
        source=config["source"], n_layers=int(config["n_layers"]),
        d_model=int(config["d_model"]), n_heads=int(config["n_heads"]),
        n_kv_heads=int(config["n_kv_heads"]),
        d_head=int(config["head_dim"]), d_ff=int(config["d_ff"]),
        vocab_size=int(config["vocab_size"]),
        norm_eps=float(config["norm_eps"]),
        rope_theta=float(config["rope_theta"]),
        tie_embeddings=bool(config["tie_embeddings"]),
        dtype=config["dtype"], param_dtype=config["param_dtype"])


@pytest.mark.parametrize("name", ["opt-1.3b-l4", "opt-13b-l2"])
def test_arch_of_an_opt_file_is_unchanged(name):
    import dataclasses
    c = harness.load_json(harness.find("configs", name))
    got, want = harness.arch(c), _opt_arch(c)
    assert got == want
    for f in dataclasses.fields(want):
        assert type(getattr(got, f.name)) is type(getattr(want, f.name))


@pytest.mark.parametrize("key", ["n_expert", "reference"])
def test_a_bad_configuration_key_is_an_error(key):
    """A misspelt model key is never run as a dense model, and a file must
    name its reference."""
    c = harness.load_json(harness.find("configs", "opt-1.3b-l4"))
    c = dict(c, n_expert=8) if key == "n_expert" else {
        k: v for k, v in c.items() if k != key}
    with pytest.raises(KeyError, match=repr(key)):
        harness.arch(c)
    if key == "reference":
        from benchmarks.chip import flops
        for fn in (lambda: weights.make(c, 1),
                   lambda: flops.decode_flops(c, [8])):
            with pytest.raises(KeyError, match=repr(key)):
                fn()


# Two architectures that the OPT cells do not run, each planted as a
# configuration file and a reference module of its own.
PLANTED = {
    "moe-mla": ({
        "name": "moe-mla", "family": "moe", "source": "test",
        "reference": "moe_mla", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 4, "head_dim": 16, "d_ff": 128,
        "vocab_size": 300, "tie_embeddings": False, "norm_eps": 1e-6,
        "mla": True, "kv_lora_rank": 16, "q_lora_rank": 0,
        "rope_head_dim": 8, "moe": True, "n_experts": 8, "moe_top_k": 2,
        "moe_d_ff": 32, "n_shared_experts": 1, "dtype": "bfloat16",
        "param_dtype": "bfloat16", "context_length": 256}, '''
import math


def padded_vocab(c):
    return 256 * math.ceil(c["vocab_size"] / 256)


def leaf_specs(c):
    L, d, H, hd = c["n_layers"], c["d_model"], c["n_heads"], c["head_dim"]
    r, rd, E, ff = (c["kv_lora_rank"], c["rope_head_dim"], c["n_experts"],
                    c["moe_d_ff"])
    sff, V = c["n_shared_experts"] * ff, padded_vocab(c)

    def dense(*shape):
        return (L,) + shape, ("normal", 1 / math.sqrt(shape[-2]))

    return {
        ("embed", "tok"): ((V, d), ("normal", 0.02)),
        ("layers", "ln1", "scale"): ((L, d), ("ones",)),
        ("layers", "attn", "w_q"): dense(d, H * (hd + rd)),
        ("layers", "attn", "w_dkv"): dense(d, r + rd),
        ("layers", "attn", "kv_norm", "scale"): ((L, r), ("ones",)),
        ("layers", "attn", "w_uk"): dense(r, H * hd),
        ("layers", "attn", "w_uv"): dense(r, H * hd),
        ("layers", "attn", "wo"): dense(H * hd, d),
        ("layers", "ln2", "scale"): ((L, d), ("ones",)),
        ("layers", "moe", "router"): dense(d, E) + ("float32",),
        ("layers", "moe", "w_gate"): dense(E, d, ff),
        ("layers", "moe", "w_up"): dense(E, d, ff),
        ("layers", "moe", "w_down"): dense(E, ff, d),
        ("layers", "moe", "shared", "w_gate"): dense(d, sff),
        ("layers", "moe", "shared", "w_up"): dense(d, sff),
        ("layers", "moe", "shared", "w_down"): dense(sff, d),
        ("final_norm", "scale"): ((d,), ("ones",)),
        ("head", "w"): ((d, V), ("normal", 1 / math.sqrt(d))),
    }


def matmul_params(c):
    """Active: MLA's projections, the router, top-k and shared experts."""
    L, d, H, hd = c["n_layers"], c["d_model"], c["n_heads"], c["head_dim"]
    r, rd, ff = c["kv_lora_rank"], c["rope_head_dim"], c["moe_d_ff"]
    attn = d * H * (hd + rd) + d * (r + rd) + 2 * r * H * hd + H * hd * d
    experts = (c["moe_top_k"] + c["n_shared_experts"]) * 3 * d * ff
    return L * (attn + d * c["n_experts"] + experts) + d * c["vocab_size"]


def train_flops_per_token(c, seq):
    L, H, hd, rd = c["n_layers"], c["n_heads"], c["head_dim"], c["rope_head_dim"]
    return 6.0 * matmul_params(c) + 6.0 * L * H * (2 * hd + rd) * seq


def decode_flops(c, contexts):
    L, r, rd = c["n_layers"], c["kv_lora_rank"], c["rope_head_dim"]
    return sum(2.0 * matmul_params(c) + 2.0 * L * c["n_heads"]
               * (2 * r + rd) * n for n in contexts)
'''),
    "dense-qkv-bias": ({
        "name": "dense-qkv-bias", "family": "dense", "source": "test",
        "reference": "biased", "n_layers": 2, "d_model": 64,
        "n_heads": 4, "n_kv_heads": 2, "head_dim": 16, "d_ff": 96,
        "vocab_size": 300, "tie_embeddings": True, "qkv_bias": True,
        "qk_norm": True, "dtype": "float32",
        "param_dtype": "float32"}, '''
import math


def padded_vocab(c):
    return 256 * math.ceil(c["vocab_size"] / 256)


def leaf_specs(c):
    L, d, H, K, hd = (c["n_layers"], c["d_model"], c["n_heads"],
                      c["n_kv_heads"], c["head_dim"])
    ff, V = c["d_ff"], padded_vocab(c)

    def dense(n, q):
        return (L, n, q), ("normal", 1 / math.sqrt(n))

    return {
        ("embed", "tok"): ((V, d), ("normal", 0.02)),
        ("layers", "ln1", "scale"): ((L, d), ("ones",)),
        ("layers", "attn", "wq"): dense(d, H * hd),
        ("layers", "attn", "wk"): dense(d, K * hd),
        ("layers", "attn", "wv"): dense(d, K * hd),
        ("layers", "attn", "wo"): dense(H * hd, d),
        ("layers", "attn", "bq"): ((L, H * hd), ("zeros",)),
        ("layers", "attn", "bk"): ((L, K * hd), ("zeros",)),
        ("layers", "attn", "bv"): ((L, K * hd), ("zeros",)),
        ("layers", "attn", "q_norm", "scale"): ((L, hd), ("ones",)),
        ("layers", "attn", "k_norm", "scale"): ((L, hd), ("ones",)),
        ("layers", "ln2", "scale"): ((L, d), ("ones",)),
        ("layers", "mlp", "w_gate"): dense(d, ff),
        ("layers", "mlp", "w_up"): dense(d, ff),
        ("layers", "mlp", "w_down"): dense(ff, d),
        ("final_norm", "scale"): ((d,), ("ones",)),
    }


def train_flops_per_token(c, seq):
    return 1000.0 + seq


def decode_flops(c, contexts):
    return 2000.0 + sum(contexts)
'''),
}


@pytest.mark.parametrize("planted", sorted(PLANTED))
def test_a_new_architecture_is_added_as_files(planted, tmp_path,
                                              monkeypatch):
    """A configuration of another architecture and its reference module
    are new files; the harness builds the program's arch, weights and
    counts for them with no edit to its own."""
    import jax
    import numpy as np

    from benchmarks.chip import flops
    config, source = PLANTED[planted]
    for kind in ("configs", "references"):
        (tmp_path / kind).mkdir()
    (tmp_path / "configs" / f"{planted}.json").write_text(json.dumps(config))
    (tmp_path / "references" / f"{config['reference']}.py").write_text(
        source)
    monkeypatch.setattr(harness, "HERE", str(tmp_path))
    c = harness.load_json(harness.find("configs", planted))
    cfg = harness.arch(c)
    for k, v in c.items():
        if k not in harness.DESCRIPTIVE_KEYS:
            assert getattr(cfg, harness.FIELD_OF_KEY.get(k, k)) == v
    got = _layouts_agree(c)
    ref = harness.reference(c)
    assert flops.train_flops_per_token(c, 64) == \
        ref.train_flops_per_token(c, 64)
    assert flops.decode_flops(c, [5, 9]) == ref.decode_flops(c, [5, 9])
    p = weights.make(c, 11)
    if planted == "moe-mla":
        assert cfg.moe and cfg.mla and (cfg.n_experts, cfg.moe_top_k) == (8, 2)
        assert got["layers"]["moe"]["router"].dtype == np.float32
        assert p["layers"]["attn"]["w_q"].dtype == jax.numpy.bfloat16
        # the program's own analytic count of active parameters, less
        # the embedding lookups
        n = cfg.active_params() - cfg.vocab_size * cfg.d_model
        assert ref.matmul_params(c) == n
    else:
        assert cfg.qkv_bias and cfg.qk_norm and not cfg.moe
        assert not np.any(np.asarray(p["layers"]["attn"]["bq"]))
        assert np.all(np.asarray(p["layers"]["attn"]["q_norm"]["scale"]) == 1)


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

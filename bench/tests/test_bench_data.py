"""The benchmark is data: names and units are well formed, every file a
cell needs is found by name, and a new mix, configuration, kind or metric
is a new file."""

import json
import os
import re
import subprocess
import sys

import pytest

from bench import harness
from bench.tests.tiny_root import BENCH, ROOT, make_root, run

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")


@pytest.fixture(scope="module")
def bm():
    return harness.load_benchmark(ROOT)


def test_benchmark_json_keys_names_and_units(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench"]
    assert isinstance(bm["run_seconds"], int) and 1 <= bm["run_seconds"] <= 51
    names = []
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        names += [c["name"]] + c["reduced"]
        assert c["file"].startswith("bench/")
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
        names += [w["name"], w["config"], w["traffic"]]
    for m in bm["end_to_end"] + bm["per_layer"]:
        names.append(m["name"])
        assert UNIT.match(m["unit"]), m["unit"]
        assert m["better"] in ("lower", "higher")
    for m in bm["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound",
                                          "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bm["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better",
                                          "source", "layer", "moves"}
        assert m["moves"] in {e["name"] for e in bm["end_to_end"]}
    for n in names:
        assert NAME.match(n), n
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        ns = [x["name"] for x in bm[group]]
        assert len(ns) == len(set(ns)), group
    assert "setup_s" in {m["name"] for m in bm["end_to_end"]}


def test_every_cell_finds_its_files_and_reports(bm):
    for w in bm["workloads"]:
        cfg_entry = harness.config_entry(bm, w["config"])
        config = harness.load_json(os.path.join(ROOT, cfg_entry["file"]))
        assert config["name"] == w["config"]
        assert os.path.isfile(os.path.join(os.path.dirname(os.path.join(
            ROOT, cfg_entry["file"])), config["reference"]))
        traffic = harness.load_json(harness.traffic_file(ROOT, w["traffic"]))
        assert os.path.isfile(harness.kind_file(ROOT, traffic["kind"]))
        e2e, layer = harness.cell_metrics(bm, w["name"])
        assert "setup_s" in {m["name"] for m in e2e} and len(e2e) >= 2
        assert layer
        for m in layer:
            assert os.path.isfile(harness.metric_file(ROOT, m["name"]))


def test_new_files_are_found_by_name(tmp_path):
    """A mix, a configuration, a kind and a metric added as files (and
    entries) are found with no file of the harness changed."""
    root = str(tmp_path)
    os.makedirs(os.path.join(root, "bench"))
    for sub in ("configs", "traffic", "kinds", "metrics"):
        os.makedirs(os.path.join(root, "bench", sub))
    with open(os.path.join(root, "bench/kinds/echo.py"), "w") as f:
        f.write("def make(ctx):\n    return ('echo', ctx)\n")
    with open(os.path.join(root, "bench/metrics/echo.count.py"), "w") as f:
        f.write("def read(r):\n    return len(r.layer)\n")
    with open(os.path.join(root, "bench/traffic/echo.mix.json"), "w") as f:
        json.dump({"kind": "echo"}, f)
    with open(os.path.join(root, "bench/configs/echo.json"), "w") as f:
        json.dump({"name": "echo-cfg", "reference": "echo_ref.py"}, f)
    with open(os.path.join(root, "bench/configs/echo_ref.py"), "w") as f:
        f.write("ANSWER = 42\n")
    bm = {"configs": [{"name": "echo-cfg", "file": "bench/configs/echo.json"}],
          "workloads": [{"name": "echo.cell", "config": "echo-cfg",
                         "traffic": "echo.mix", "chips": 1}],
          "end_to_end": [{"name": "setup_s"}, {"name": "echo_rate"}],
          "per_layer": [{"name": "echo.count", "moves": "echo_rate"}]}
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    bm = harness.load_benchmark(root)
    cell = harness.cell_entry(bm, "echo.cell")
    cfg_entry = harness.config_entry(bm, cell["config"])
    config = harness.load_json(os.path.join(root, cfg_entry["file"]))
    assert harness.reference_module(root, cfg_entry, config).ANSWER == 42
    traffic = harness.load_json(harness.traffic_file(root, cell["traffic"]))
    kind = harness.load_module(harness.kind_file(root, traffic["kind"]))
    assert kind.make("ctx") == ("echo", "ctx")
    _, layer = harness.cell_metrics(bm, "echo.cell")
    reader = harness.load_module(harness.metric_file(root, layer[0]["name"]))
    assert reader.read(harness.Reading(None, {"a": 1}, 1, {})) == 1
    with pytest.raises(FileNotFoundError):
        harness.traffic_file(root, "absent")
    with pytest.raises(ValueError):
        harness.kind_file(root, "../escape")


def test_serving_config_of_another_shape_is_new_files(tmp_path):
    """A serving configuration with a gated SiLU MLP, a tied head and no
    biases is added as its own files (configuration, its reference, a mix)
    and entries, and its cell runs correct."""
    import shutil
    root = make_root(str(tmp_path))
    cfgs = os.path.join(root, "bench", "configs")
    cfg = harness.load_json(os.path.join(cfgs, "sc2-tiny.json"))
    cfg.update(name="gated-tiny", reference="gated_tiny_ref.py",
               hidden_act="silu", gated_mlp=True, use_bias=False,
               tie_word_embeddings=True)
    cfg["program"] = dict(cfg["program"], qkv_bias=False, gated_mlp=True,
                          activation="silu", tie_embeddings=True)
    with open(os.path.join(cfgs, "gated-tiny.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(BENCH, "configs", "dense_decoder_ref.py"),
                os.path.join(cfgs, "gated_tiny_ref.py"))
    traffic = os.path.join(root, "bench", "traffic")
    shutil.copy(os.path.join(traffic, "tiny.serve.json"),
                os.path.join(traffic, "gated.serve.json"))
    bm = harness.load_benchmark(root)
    bm["configs"].append({"name": "gated-tiny", "source": "test",
                          "file": "bench/configs/gated-tiny.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "gated.serve", "config": "gated-tiny",
                            "traffic": "gated.serve", "chips": 1,
                            "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "tiny.serve" in m.get("workloads", []):
            m["workloads"].append("gated.serve")
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    rc, res, err = run(root, "gated.serve", seconds=2.0)
    assert rc == 0 and res["correct"], err
    assert res["failed"] == 0


def test_serving_traffic_repeats_from_the_seed():
    from bench.harness import load_module
    gen = load_module(os.path.join(BENCH, "kinds", "serve_open.py")).generate
    t = harness.load_json(os.path.join(BENCH, "traffic", "code.r80.json"))
    a = gen(t, 50, 2 ** 31 + 12345, 49152)
    b = gen(t, 50, 2 ** 31 + 12345, 49152)
    c = gen(t, 50, 7, 49152)
    assert len(a) == round(t["rate_per_s"] * 50)
    assert [(d, p.tolist(), o) for d, p, o in a] == \
        [(d, p.tolist(), o) for d, p, o in b]
    # the mix fixes its order: another seed replays the same trace with
    # other token ids
    assert [(d, p.shape, o) for d, p, o in a] == \
        [(d, p.shape, o) for d, p, o in c]
    assert any(p.tolist() != q.tolist() for (_, p, _), (_, q, _) in zip(a, c))
    # another order: the same sizes and gaps, shuffled
    d = gen(dict(t, order_seed=t["order_seed"] + 1), 50, 7, 49152)
    assert sorted(p.shape[1] for _, p, _ in a) == \
        sorted(p.shape[1] for _, p, _ in d)
    assert sorted(o for *_, o in a) == sorted(o for *_, o in d)
    def gaps(r):
        return sorted([r[0][0]] + [y[0] - x[0] for x, y in zip(r, r[1:])])
    assert gaps(a) == pytest.approx(gaps(d), abs=1e-9)
    assert [p.shape[1] for _, p, _ in a] != [p.shape[1] for _, p, _ in d]
    assert all(t["prompt"]["min"] <= p.shape[1] <= t["prompt"]["max"]
               for _, p, _ in a)
    assert a[-1][0] < 50


def test_linalg_operands_repeat_from_the_seed():
    import jax
    ref = harness.load_module(os.path.join(BENCH, "configs",
                                           "linalg_f32_ref.py"))
    k1 = harness.seed_key(2 ** 31 + 5)
    x = ref.operands(k1, 64, ("a", "u", "s"))
    y = ref.operands(harness.seed_key(2 ** 31 + 5), 64, ("a", "u", "s"))
    z = ref.operands(harness.seed_key(5), 64, ("a",))
    for k in x:
        assert bool((x[k] == y[k]).all())
    assert not bool((x["a"] == z["a"]).all())
    assert float(jax.numpy.abs(jax.numpy.tril(x["u"], -1)).max()) == 0.0


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    """On a host with no TPU the run prints nothing on stdout and exits
    non-zero; so it does in a directory holding only the benchmark."""
    import shutil
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    args = ["--workload", "linalg.mix.n16384", "--seed", "1", "--seconds",
            "1", "--trace", "0"]
    p = subprocess.run([sys.executable, os.path.join(BENCH, "run.py")] + args,
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""
    assert "no TPU" in p.stderr
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    p = subprocess.run([sys.executable, "bench/run.py"] + args,
                       cwd=tmp_path, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0 and p.stdout.strip() == ""

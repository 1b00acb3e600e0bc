"""The serving cell on the CPU at a reduced width: a whole run is correct,
the plain reference agrees with the program's model, its fp8 control is
not correct, and every fault planted under the timed path makes
``correct`` false."""

import json
import os

import numpy as np
import pytest

from bench import control, harness
from bench.tests.tiny_root import BENCH, TINY_MODEL, jax_state, make_root, run


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(str(tmp_path_factory.mktemp("root")))


@pytest.fixture(scope="module")
def busy_root(tmp_path_factory):
    """Sixteen requests due within a second: decode batches are full, so
    a fault in a batch row shows whatever the host's speed."""
    return make_root(str(tmp_path_factory.mktemp("busy")),
                     serve={"rate_per_s": 16.0, "check_requests": 16})


def tiny_config():
    with open(os.path.join(BENCH, "configs", "starcoder2-3b.json")) as f:
        cfg = json.load(f)
    cfg.update(TINY_MODEL)
    return cfg


def test_tiny_serve_runs_correct(root):
    rc, res, err = run(root, "tiny.serve", seconds=2.0)
    assert rc == 0 and res["correct"], err
    assert res["failed"] == 0 and res["attempted"] == 8
    assert set(res["metrics"]) == {"ttft_p50_s", "ttft_p75_s", "itl_p50_s",
                                   "setup_s"}
    assert res["window"]["compiled_in_window"] == []
    assert list(res)[-1] == "checks"


def test_tiny_serve_traced_run_reports_layer_metrics(root, monkeypatch):
    from bench import trace
    from bench.tests.tiny_root import cpu_trace_load
    monkeypatch.setattr(trace, "load", cpu_trace_load)
    rc, res, err = run(root, "tiny.serve", seconds=2.0, trace=1)
    assert rc == 0 and res["correct"], err
    assert {"serve_mfu", "sched.step_host_ms", "serve.itl_p99_s",
            "device_idle.serve"} <= set(res["metrics"])
    assert 0 < res["metrics"]["serve_mfu"]["value"] < 100
    assert res["device"]["window_s"] == pytest.approx(1.0, abs=0.3)


def test_reference_agrees_with_the_program_model():
    """The plain reference, given the benchmark's weights, computes the
    program's logits (float32, reduced width)."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    ref = harness.load_module(os.path.join(BENCH, "configs",
                                           "dense_decoder_ref.py"))
    kind = harness.load_module(os.path.join(BENCH, "kinds", "serve_open.py"))
    cfg = tiny_config()
    mcfg = kind.program_config(cfg)
    params = ref.weights(harness.seed_key(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0,
                                cfg["vocab_size"])
    hidden, _ = tf.decoder_forward_train(params, mcfg, tokens)
    want = np.asarray(tf.lm_logits(params, mcfg, hidden))
    pos = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    got = np.asarray(ref.logits_at(params, cfg, tokens, pos))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)
    low = np.asarray(ref.logits_at(params, cfg, tokens, pos, "fp8"))
    assert 1e-3 < np.abs(low - got).max() < 1.0


GATED = dict(hidden_act="silu", gated_mlp=True, use_bias=False,
             tie_word_embeddings=False)
GATED_PROGRAM = dict(qkv_bias=False, gated_mlp=True, activation="silu",
                     tie_embeddings=False)


def test_reference_agrees_with_the_program_model_gated_untied():
    """The same agreement for a gated SiLU MLP, no biases and an untied
    head."""
    import jax
    import jax.numpy as jnp
    from repro.models import transformer as tf
    ref = harness.load_module(os.path.join(BENCH, "configs",
                                           "dense_decoder_ref.py"))
    kind = harness.load_module(os.path.join(BENCH, "kinds", "serve_open.py"))
    cfg = dict(tiny_config(), **GATED)
    cfg["program"] = dict(cfg["program"], **GATED_PROGRAM)
    mcfg = kind.program_config(cfg)
    params = ref.weights(harness.seed_key(4), cfg)
    assert "lm_head" in params and "gate" in params["groups"][0][0]["mlp"]
    tokens = jax.random.randint(jax.random.PRNGKey(2), (2, 40), 0,
                                cfg["vocab_size"])
    hidden, _ = tf.decoder_forward_train(params, mcfg, tokens)
    want = np.asarray(tf.lm_logits(params, mcfg, hidden))
    pos = jnp.broadcast_to(jnp.arange(40)[None], (2, 40))
    got = np.asarray(ref.logits_at(params, cfg, tokens, pos))
    np.testing.assert_allclose(got, want, atol=2e-4, rtol=2e-4)


def test_control_run_comes_out_not_correct(root):
    """A run whose comparison reads the tokens the fp8 reference puts
    first fails the limit through the harness's own comparison."""
    rc, res, err = run(root, "tiny.serve", seconds=2.0, control=1)
    assert rc == 0 and res["correct"] is False, err
    c = res["checks"]["served.logit_gap"]
    assert c["value"] > c["limit"], c


def test_control_is_not_correct(root):
    """At each served position the fp8 reference's first token lies below
    the float32 reference's best by more than the limit; the program's
    tokens do not."""
    limit = 1e-3
    with jax_state():
        rows = control.readings(["--workload", "tiny.serve", "--seeds", "5",
                                 "2147483653", "--seconds", "2"], root=root,
                                require_tpu=False,
                                out=open(os.devnull, "w"))
    for row in rows:
        assert row["served.logit_gap"] <= limit, row
        assert row["control.served.logit_gap"] > 3 * limit, row
        # the nested samples: the first eight requests are the cell's own
        assert row["first8"][:2] == [row["served.logit_gap"],
                                     row["control.served.logit_gap"]], row


def test_sweep_reads_each_rate(root):
    """One window per offered rate in one process; a rate the tiny model
    cannot keep up with shows a last third above the first."""
    from bench import sweep
    with jax_state():
        rows = sweep.sweep(["--workload", "tiny.serve", "--seed", "9",
                            "--rates", "2", "40", "--seconds", "1"],
                           root=root, require_tpu=False,
                           out=open(os.devnull, "w"))
    assert [r["rate"] for r in rows] == [2, 40]
    assert [r["n"] for r in rows] == [2, 40]
    assert all(r["failed"] == 0 for r in rows), rows
    assert rows[1]["ttft_last_third_s"] > rows[1]["ttft_first_third_s"]


def _patch(monkeypatch, name, wrap):
    from repro.serving.scheduler import ModelBackend
    orig = getattr(ModelBackend, name)
    monkeypatch.setattr(ModelBackend, name, wrap(orig))


def _token_altered(orig):
    calls = {"n": 0}

    def sample(self, rs, logits):
        tok = orig(self, rs, logits)
        calls["n"] += 1
        return (tok + 1) % logits.shape[-1] if calls["n"] % 5 == 0 else tok
    return sample


def _vstep(transform):
    def wrap(orig):
        def vstep(self):
            fn = orig(self)

            def step(params, tok, caches):
                logits, new = fn(params, tok, caches)
                return transform(logits, new, caches)
            return step
        return vstep
    return wrap


FAULTS = {
    # a token altered where it is produced
    "token_altered": ("_sample", _token_altered),
    # a decode step that returns its cache state unchanged
    "state_unchanged": ("_vstep", _vstep(lambda lg, new, old: (lg, old))),
    # half of the decode batch left out: its rows get the first row's logits
    "half_batch_left_out": ("_vstep", _vstep(
        lambda lg, new, old: (lg.at[lg.shape[0] // 2:].set(lg[0]), new))),
}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_makes_the_run_not_correct(busy_root, monkeypatch, fault):
    name, wrap = FAULTS[fault]
    _patch(monkeypatch, name, wrap)
    rc, res, err = run(busy_root, "tiny.serve", seconds=1.0)
    assert rc == 0 and res is not None
    assert res["correct"] is False, res["checks"]

"""Chip smoke test: the model-guided linalg loop and one full-width server.

    python chip_smoke.py               # one TPU: linalg + serving phases
    python chip_smoke.py --four-chips  # one 2x2 host: the distributed grid

One process drives every chip it uses (a second process cannot open a chip
this one holds).  The script stops, with a non-zero exit and no result,
when JAX finds no TPU: it never falls back to the CPU.  Every phase prints
its compile seconds, wall seconds after ``block_until_ready``, residual,
plan and peak device memory; any failure exits non-zero.  The last stdout
line is ``{"ok": true, "device": {...}}``.

Phases on one chip:

* linalg: ``repro.linalg.matmul`` / ``trsm`` / ``cholesky`` at n = 16384
  in float32, planned by a fresh tuner whose plan cache lives in a
  temporary directory and executed by ``dispatch`` with Pallas locals.
  Each result is checked against a jnp reference computed at "highest"
  precision, and each compiled executable must hold ``tpu_custom_call``
  (the Pallas kernels, not a jnp stand-in).
* serving: starcoder2-3b at its published widths in bfloat16 with random
  weights from the seed; 4 requests of 128 prompt tokens and 32 new tokens
  through ``Engine.generate`` (Scheduler + ModelBackend).  Every sampled
  logit must be finite, and every generated token must equal the argmax
  of a teacher-forced forward pass over the prompt and the tokens served
  before it, up to a bfloat16 tie.

``--four-chips`` runs only the 2D process grid (g=2, c=1): the dispatched
matmul / TRSM / Cholesky and every 2D variant with and without overlap,
each compared with a one-device reference.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import math
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

#: Matrix edge of the linalg phase: 1 GiB per float32 operand, the size
#: the bring-up proof is made at.
N = 16384

#: Relative Frobenius residual bound of the float32 linalg results.  The
#: problems are built well conditioned (cond < ~10), so a backward-stable
#: float32 computation lands near sqrt(n) * eps = 1.5e-5 at n = 16384; the
#: bound leaves a factor of ~6 for the blocked accumulation order.
LINALG_RTOL = 1e-4

#: Logit gap, relative to the largest logit of the row, under which two
#: bfloat16 logits count as tied: the teacher-forced pass and the served
#: prefill and decode steps run different programs, and bfloat16 carries 8
#: significant bits, so their argmax may differ only inside it.
TIE_RTOL = 2.0 ** -7


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_gib(dev) -> float:
    stats = dev.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) / 2 ** 30


def rel_residual(got, want) -> float:
    """||got - want||_F / ||want||_F, reduced in float32 on the device."""
    got = got.astype(jnp.float32)
    want = want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def hi_dot(a, b):
    return jnp.dot(a, b, precision=jax.lax.Precision.HIGHEST)


# -- inputs -------------------------------------------------------------------

def make_inputs(n: int, seed: int, sharding):
    """A, B (standard normal), U = 4 I + strict-upper N(0, 1/n) (cond ~2)
    and SPD S = G G^T / n + I (eigenvalues in [1, 5]), made on the device
    directly in ``sharding``."""
    def build(key):
        ka, kb, ku, kg = jax.random.split(key, 4)
        a = jax.random.normal(ka, (n, n), jnp.float32)
        b = jax.random.normal(kb, (n, n), jnp.float32)
        u = (jnp.triu(jax.random.normal(ku, (n, n), jnp.float32), 1)
             / math.sqrt(n) + 4.0 * jnp.eye(n, dtype=jnp.float32))
        g = jax.random.normal(kg, (n, n), jnp.float32)
        s = hi_dot(g, g.T) / n + jnp.eye(n, dtype=jnp.float32)
        return a, b, u, s

    out = jax.jit(build, out_shardings=(sharding,) * 4)(
        jax.random.PRNGKey(seed))
    return jax.block_until_ready(out)


def residual(op: str, out, operands) -> float:
    """matmul: ||C - AB||/||AB||; trsm: ||XU - B||/||B||;
    cholesky: ||A - LL^T||/||A|| (L must be lower-triangular)."""
    with jax.default_matmul_precision("highest"):
        if op == "matmul":
            a, b = operands
            return rel_residual(out, hi_dot(a, b))
        if op == "trsm":
            u, b = operands
            return rel_residual(hi_dot(out, u), b)
        (a,) = operands
        if float(jnp.abs(jnp.triu(out, 1)).max()) != 0.0:
            raise AssertionError("cholesky factor is not lower-triangular")
        return rel_residual(hi_dot(out, out.T), a)


# -- linalg -------------------------------------------------------------------

OPS = ("matmul", "trsm", "cholesky")


def run_plan(op: str, plan, operands, devices, *, dispatched: bool,
             tuner=None):
    """Compile the plan's executor ahead of time (its program must hold a
    Pallas kernel), then run it through the normal entry point.  Returns
    (output, compile seconds, wall seconds)."""
    from repro import linalg
    from repro.tuner import dispatch

    fn, mesh = dispatch.executor(plan, devices)
    spec = NamedSharding(mesh, P("row", "col"))
    placed = [jax.device_put(x, spec) for x in operands]
    t0 = time.perf_counter()
    compiled = fn.lower(*placed).compile()
    compile_s = time.perf_counter() - t0
    on_tpu = devices[0].platform == "tpu"
    if on_tpu and "tpu_custom_call" not in compiled.as_text():
        raise AssertionError(f"{op}: compiled executor holds no Pallas "
                             "kernel (tpu_custom_call)")
    entry = getattr(linalg, op)
    t0 = time.perf_counter()
    if dispatched:
        out = entry(*operands, devices=devices, tuner=tuner,
                    local_kernel="pallas")
    else:
        out = dispatch.execute(plan, *operands, devices=devices)
    out = jax.block_until_ready(out)
    return out, compile_s, time.perf_counter() - t0


def plan_text(plan) -> str:
    return (f"algo={plan.algo} variant={plan.variant} g={plan.g} "
            f"c={plan.c} tiles={json.dumps(plan.tiles, sort_keys=True)}")


def check(op: str, variant: str, res: float) -> None:
    if not res < LINALG_RTOL:
        raise AssertionError(f"{op} {variant}: residual {res:.3e} >= "
                             f"{LINALG_RTOL:.0e}")


def linalg_phase(n: int, seed: int, devices, *, all_variants: bool) -> None:
    """Dispatched matmul / TRSM / Cholesky on ``devices``; with
    ``all_variants`` also every variant the grid admits, forced through
    ``dispatch.execute``.  Residuals are taken against a reference on the
    first device."""
    from repro.tuner import PlanCache, Tuner, dispatch, feasible_grids

    ref_dev = devices[0]
    with tempfile.TemporaryDirectory(prefix="plans-") as plan_dir:
        tuner = Tuner(cache=PlanCache(plan_dir))
        plans = {op: tuner.plan(op, n, devices=devices, dtype="float32",
                                local_kernel="pallas") for op in OPS}
        for op, plan in plans.items():
            if plan.c != 1:
                raise AssertionError(f"{op}: expected a 2D grid, got "
                                     f"{plan_text(plan)}")
        _, mesh = dispatch.executor(plans["matmul"], devices)
        a, b, u, s = make_inputs(n, seed,
                                 NamedSharding(mesh, P("row", "col")))
        log(f"linalg: n={n} float32 on {len(devices)} device(s); "
            "bytes_in_use after distribute: " + " ".join(
                f"{d.id}:{(d.memory_stats() or {}).get('bytes_in_use', 0)}"
                for d in devices))
        operands = {"matmul": (a, b), "trsm": (u, b), "cholesky": (s,)}
        ref_ops = {op: tuple(jax.device_put(x, ref_dev) for x in xs)
                   for op, xs in operands.items()} \
            if len(devices) > 1 else operands
        for op in OPS:
            plan = plans[op]
            runs = [(plan, True)]
            if all_variants:
                algos = ("cannon", "summa") if op == "matmul" else (op,)
                for algo in algos:
                    for p, c, g in feasible_grids(len(devices), algo):
                        kind = "2d" if c == 1 else "2.5d"
                        for variant in tuner.registry.variants(algo):
                            if not variant.startswith(kind):
                                continue
                            if (algo, variant, g, c) == (plan.algo,
                                                         plan.variant,
                                                         plan.g, plan.c):
                                continue
                            runs.append((dataclasses.replace(
                                plan, algo=algo, variant=variant, p=p, c=c,
                                g=g), False))
            for pl, dispatched in runs:
                out, compile_s, wall_s = run_plan(
                    op, pl, operands[op], devices, dispatched=dispatched,
                    tuner=tuner)
                res = residual(op, jax.device_put(out, ref_dev), ref_ops[op])
                del out
                how = "dispatched" if dispatched else "forced"
                log(f"linalg {op} [{how}] {plan_text(pl)} "
                    f"compile_s={compile_s:.2f} wall_s={wall_s:.4f} "
                    f"residual={res:.3e} (tol {LINALG_RTOL:.0e}) "
                    f"peak_gib={peak_gib(devices[0]):.2f}")
                check(op, pl.variant, res)


# -- serving ------------------------------------------------------------------

def serving_phase(cfg, seed: int, *, batch: int = 4, prompt_len: int = 128,
                  new_tokens: int = 32, max_cache_len: int = 512) -> None:
    """``batch`` requests through ``Engine.generate`` on a model with
    random weights: finite sampled logits, and generated tokens that match
    a teacher-forced forward pass over the served sequence."""
    from repro.models import build_model
    from repro.models import transformer as tf
    from repro.serving import Engine, ServeConfig

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(jax.jit(model.init)(
        jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree.leaves(params))
    log(f"serving: {cfg.name} {cfg.dtype} params={n_params} "
        f"init_s={time.perf_counter() - t0:.2f} "
        f"peak_gib={peak_gib(jax.devices()[0]):.2f}")
    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                       (batch, prompt_len)), jnp.int32)

    engine = Engine(model, params, ServeConfig(
        max_new_tokens=new_tokens, max_cache_len=max_cache_len))
    # every logits row the scheduler samples from passes through the
    # backend's _sample: record whether each one is finite, on the first
    # (compiling) call only, so the timed call runs the engine unpatched
    engine._make_scheduler(batch, None)
    backend = engine._backend
    finite = []
    sample = backend._sample

    def checked_sample(rs, logits):
        finite.append(jnp.all(jnp.isfinite(logits)))
        return sample(rs, logits)

    backend._sample = checked_sample
    t0 = time.perf_counter()
    out = jax.block_until_ready(engine.generate(prompts, seed=seed))
    first_s = time.perf_counter() - t0
    del backend._sample
    t0 = time.perf_counter()
    out2 = jax.block_until_ready(engine.generate(prompts, seed=seed))
    wall_s = time.perf_counter() - t0
    if out.shape != (batch, prompt_len + new_tokens):
        raise AssertionError(f"serving: output shape {out.shape}")
    if not bool(jnp.all(out == out2)):
        raise AssertionError("serving: greedy generation is not repeatable")
    n_rows = len(finite)
    if n_rows != batch * new_tokens or not bool(jnp.all(jnp.stack(finite))):
        raise AssertionError(f"serving: {n_rows} sampled logits rows "
                             f"(want {batch * new_tokens}), finite="
                             f"{bool(jnp.all(jnp.stack(finite)))}")

    # teacher-forced pass over prompt + served tokens: position t's logits
    # predict token t + 1, so every generated token (prefill and each
    # decode step through the KV cache) is checked against its argmax
    forward = jax.jit(lambda p, t: tf.lm_logits(
        p, cfg, tf.decoder_forward_train(p, cfg, t)[0][:, prompt_len - 1:-1]))
    ref = jax.block_until_ready(forward(params, out)).astype(jnp.float32)
    if not bool(jnp.all(jnp.isfinite(ref))):
        raise AssertionError("serving: teacher-forced logits not finite")
    ref_np = np.asarray(ref)                              # (batch, new, V)
    want = ref_np.argmax(axis=-1)
    got = np.asarray(out[:, prompt_len:])
    gaps = (np.take_along_axis(ref_np, want[..., None], -1)
            - np.take_along_axis(ref_np, got[..., None], -1))[..., 0]
    scale = np.abs(ref_np).max(axis=-1)
    bad = (got != want) & (gaps > TIE_RTOL * scale)
    if bad.any():
        i, t = np.argwhere(bad)[0]
        raise AssertionError(
            f"serving: request {i} token {t} is {got[i, t]}, teacher-forced "
            f"argmax {want[i, t]} (logit gap {gaps[i, t]:.4g}, scale "
            f"{scale[i, t]:.4g}); {int(bad.sum())} such tokens")
    log(f"serving: requests={batch} prompt={prompt_len} new={new_tokens} "
        f"max_cache_len={max_cache_len} "
        f"compile_s={first_s - wall_s:.2f} (first call less second) "
        f"wall_s={wall_s:.2f} "
        f"tokens_per_s={batch * new_tokens / wall_s:.1f} "
        f"sampled_rows={n_rows} all_finite=True "
        f"token_matches={int((got == want).sum())}/{batch * new_tokens} "
        f"first_token_matches={int((got[:, 0] == want[:, 0]).sum())}/{batch} "
        f"max_rel_gap={float((gaps / scale).max()):.3g} "
        f"peak_gib={peak_gib(jax.devices()[0]):.2f}")


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2D process grid on four chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX platform {devices[0].platform!r}); "
              "nothing was run", file=sys.stderr)
        return 2
    want = 4 if args.four_chips else 1
    if len(devices) < want:
        print(f"chip_smoke: needs {want} TPU devices, found "
              f"{len(devices)}", file=sys.stderr)
        return 2
    devices = devices[:want]

    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.abspath(__file__)), "src"))
    from repro.compile_cache import enable_compile_cache
    log(f"device: {devices[0].device_kind} x{len(devices)}; compile cache "
        f"{enable_compile_cache()}")

    t0 = time.perf_counter()
    if args.four_chips:
        linalg_phase(N, args.seed, devices, all_variants=True)
    else:
        linalg_phase(N, args.seed, devices, all_variants=False)
        from repro.configs import get
        serving_phase(get("starcoder2-3b"), args.seed)
    log(f"total_s={time.perf_counter() - t0:.1f}")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Closed loop of dense linear-algebra calls through ``repro.linalg``.

One caller makes back-to-back calls of the traffic's ``ops`` round-robin
at size ``n``, in whole rounds, each through the model-guided entry (``repro.linalg.matmul``
/ ``trsm`` / ``cholesky`` with a fresh ``Tuner`` and Pallas locals) and
each ended by ``block_until_ready``, on operands built once in set-up on
the cell's devices.  After each call, outside its timing, the harness
reads a projection of the answer on a fresh random probe; once the window
has closed, the reference works out each projection from the operands and
every call is compared.  With ``--control 1`` the reference in bf16_3x
makes the calls in the program's place, and the same comparison must fail.

Traffic parameters: ``ops`` (list), ``n``, ``probe_cols``, and ``limits``
(the largest relative residual of each operation's projections, and
``cholesky.upper``, the largest entry of L above its diagonal).
"""

from __future__ import annotations

import math
import time

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from bench import work
from bench.harness import Check, seed_key

#: bf16 MXU passes of one float32 product at Precision.HIGHEST
F32_HIGHEST_PASSES = 6

#: the reference's precision one below float32 at HIGHEST: the control
CONTROL_PRECISION = "bf16_3x"


class LinalgLoop:
    def __init__(self, ctx):
        self.ctx = ctx
        t = ctx.traffic
        self.ops = list(t["ops"])
        self.n = int(t["n"])
        self.cols = int(t["probe_cols"])
        self.limits = dict(t["limits"])
        self.ref = ctx.reference
        self.calls = []            # one dict per timed call
        self.readings = []         # (op, call index, reading) per call
        self.attempted = self.failed = 0
        self.layer = {}
        self._resid = None

    # -- set-up ------------------------------------------------------------------
    def setup(self):
        from repro import linalg
        from repro.tuner import PlanCache, Tuner, dispatch

        ctx, n = self.ctx, self.n
        cfg = ctx.config
        self.tuner = Tuner(cache=PlanCache(ctx.work_dir + "/plans"))
        self.plans = {op: self.tuner.plan(op, n, devices=ctx.devices,
                                          dtype=cfg["dtype"],
                                          local_kernel=cfg["local_kernel"])
                      for op in self.ops}
        _, mesh = dispatch.executor(self.plans[self.ops[0]], ctx.devices)
        self.sharding = NamedSharding(mesh, P("row", "col"))
        self.reseed(ctx.seed)
        self.entries = {op: getattr(linalg, op) for op in self.ops}
        self._read = jax.jit(self.ref.reading, static_argnums=(0, 4))
        for op in self.ops:                      # compile every program
            out = self._call(op)
            jax.block_until_ready(self._reading(op, out, 0))
            del out
        print(f"linalg: n={n} {cfg['dtype']} on {len(ctx.devices)} "
              f"chip(s); float32 products at Precision.HIGHEST take "
              f"{F32_HIGHEST_PASSES} bf16 MXU passes; plans: " + "; ".join(
                  f"{op} {p.algo} {p.variant} g={p.g} c={p.c} "
                  f"tiles={p.tiles}" for op, p in self.plans.items()),
              flush=True)

    def reseed(self, seed):
        """Operands and probes of ``seed``, made on the cell's devices."""
        self.operands = None
        names = {k for op in self.ops for k in self.ref.OPERANDS[op]}
        key = seed_key(seed)
        self.operands = self.ref.operands(jax.random.fold_in(key, 0), self.n,
                                          names, self.sharding)
        self.probe_key = jax.random.fold_in(key, 1)

    def _call(self, op):
        if self.ctx.control:
            return jax.block_until_ready(self.ref.compute(
                op, self.operands, CONTROL_PRECISION))
        args = [self.operands[k] for k in self.ref.OPERANDS[op]]
        return jax.block_until_ready(self.entries[op](
            *args, devices=self.ctx.devices, tuner=self.tuner,
            local_kernel=self.ctx.config["local_kernel"]))

    def _reading(self, op, out, i):
        return self._read(op, out, self.operands,
                          jax.random.fold_in(self.probe_key, i), self.cols)

    # -- the window ----------------------------------------------------------------
    def window(self, seconds):
        ctx = self.ctx
        ctx.trace_begin()
        t_end = time.perf_counter() + seconds
        i = 0
        # whole rounds only, so every window holds the operations in the
        # same proportion
        while i % len(self.ops) or time.perf_counter() < t_end:
            op = self.ops[i % len(self.ops)]
            with ctx.span(f"bench.call.{op}"):
                t0 = time.perf_counter()
                out = self._call(op)
                t1 = time.perf_counter()
            with ctx.span("bench.reading"):
                r = jax.block_until_ready(self._reading(op, out, i))
            del out
            self.calls.append({"op": op, "n": self.n, "start": t0,
                               "wall_s": t1 - t0,
                               "flops": work.linalg_flops(op, self.n)})
            self.readings.append((op, i, r))
            i += 1
        ctx.trace_end()

    def finish(self):
        pass

    def release(self):
        pass

    # -- results -------------------------------------------------------------------
    def end_to_end(self):
        flops = sum(c["flops"] for c in self.calls)
        wall = sum(c["wall_s"] for c in self.calls)
        return {"linalg_tflops": flops / wall / 1e12}

    def _residuals(self, op, r, i):
        """The numbers compared for one reading of call ``i``."""
        if self._resid is None:
            self._resid = jax.jit(lambda proj, ops, key, op: self.ref.residual(
                proj, self.ref.expected(op, ops, key, self.cols)),
                static_argnums=(3,))
        vals = {f"{op}.residual": float(self._resid(
            r["proj"], self.operands, jax.random.fold_in(self.probe_key, i),
            op))}
        if op == "cholesky":
            vals["cholesky.upper"] = float(r["upper"])
        return vals

    def control(self, seed, seconds):
        """One call of each operation by the program and by the reference
        in bf16_3x (Precision.HIGH), on the operands of ``seed``."""
        self.reseed(seed)
        row = {}
        for op in self.ops:
            out = self._call(op)
            row.update(self._residuals(op, self._reading(op, out, 0), 0))
            del out
            out = jax.block_until_ready(self.ref.compute(
                op, self.operands, CONTROL_PRECISION))
            row.update({"control." + k: v for k, v in self._residuals(
                op, self._reading(op, out, 0), 0).items()})
            del out
        return row

    def summary(self) -> str:
        parts = []
        for op in self.ops:
            walls = [c["wall_s"] for c in self.calls if c["op"] == op]
            if walls:
                parts.append(f"{op} {len(walls)} calls, wall mean "
                             f"{sum(walls) / len(walls):.4f} s min "
                             f"{min(walls):.4f} s max {max(walls):.4f} s")
        return "linalg: " + "; ".join(parts)

    def checks(self):
        print(self.summary(), flush=True)
        worst = {f"{op}.residual": 0.0 for op in self.ops}
        if "cholesky" in self.ops:
            worst["cholesky.upper"] = 0.0
        self.attempted = len(self.readings)
        self.failed = 0
        for op, i, r in self.readings:
            vals = self._residuals(op, r, i)
            bad = False
            for name, v in vals.items():
                worst[name] = v if not math.isfinite(v) else max(worst[name],
                                                                 v)
                bad |= not (math.isfinite(v) and v <= self.limits[name])
            self.failed += bad
        # the per-layer readers: every call, whether its span was traced
        self.layer = {
            "calls": [dict(c, traced=self.ctx.in_trace(c["start"]),
                           predicted_s=self.plans[c["op"]].predicted["total"])
                      for c in self.calls],
            "tiles": {op: p.tiles for op, p in self.plans.items()},
        }
        return [Check(name, worst[name], float(self.limits[name]))
                for name in worst]


def make(ctx):
    return LinalgLoop(ctx)

"""Open-loop serving through ``Scheduler.submit`` / ``step`` with a
``ModelBackend``.

Requests arrive on the wall clock at the traffic's rate, whether or not
earlier ones have finished, and each is timed from when it was due: its
time to first token is when the first token reached the host (the harness
fetches each step's tokens, as a server streaming them would) less its due
time, and the gaps between its tokens are taken the same way.  After the
window, arrivals stop and the requests due in it are let finish, up to
``drain_s``; one that errs or does not finish counts as failed and its
time to first token runs to the end of the drain.

Every seed gets the same set of sizes and arrival gaps:
``n = round(rate * seconds)`` requests; gaps at the n stratified quantiles
of an exponential of mean 1/rate, scaled so that the n-th request is due
half a mean gap before the window closes; prompt and output lengths at the n
stratified quantiles of lognormals (``median``, ``sigma``), clipped to
[``min``, ``max``]; each list shuffled by the mix's ``order_seed``, so
that every run replays one trace: an order drawn from the run's seed would
decide which long outputs decode together and which prefills stall them,
and so move the tails from seed to seed.  Token ids are uniform over the
vocabulary, from the run's seed.  Outputs are greedy and run to their
fixed length.

After the window a sample of finished requests, drawn from the seed with
the longest always in it, is compared with the configuration's plain
reference: the widest gap by which a served token's reference logit lies
below the reference's best at its position (``served.logit_gap``).  With
``--control 1`` the number read is that of the tokens the reference in
fp8 puts first at the same positions, and it must fail the limit.
"""

from __future__ import annotations

import collections
import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import work
from bench.harness import Check, seed_key, seed_rng

#: the reference's precision one below bfloat16: the control
CONTROL_PRECISION = "fp8"


def _lognormal_quantiles(n, spec):
    from statistics import NormalDist
    z = [NormalDist().inv_cdf((k + 0.5) / n) for k in range(n)]
    vals = [spec["median"] * math.exp(spec["sigma"] * q) for q in z]
    return [int(min(spec["max"], max(spec["min"], round(v)))) for v in vals]


def generate(traffic: dict, seconds: float, seed: int, vocab: int):
    """The requests of one run: (due offset s, prompt ids, output length)."""
    n = max(1, round(traffic["rate_per_s"] * seconds))
    rng = seed_rng(seed, 1)
    order = seed_rng(traffic["order_seed"], 1)
    gaps = [-math.log(1.0 - (k + 0.5) / n) / traffic["rate_per_s"]
            for k in range(n)]
    prompt = _lognormal_quantiles(n, traffic["prompt"])
    output = _lognormal_quantiles(n, traffic["output"])
    gaps, prompt, output = (list(order.permutation(x)) for x in
                            (gaps, prompt, output))
    # request j is due after gaps 0..j; the last half a mean gap before
    # the window closes
    due = np.cumsum(gaps) * (seconds * (n - 0.5) / n / sum(gaps))
    return [(float(due[j]),
             rng.integers(0, vocab, (1, int(prompt[j])), dtype=np.int32),
             int(output[j])) for j in range(n)]


def program_config(cfg: dict):
    """The program's ModelConfig for the configuration file: the widths
    from its published keys, the architecture from its ``program`` group."""
    from repro.configs.base import ModelConfig
    eps = next(cfg[k] for k in ("norm_epsilon", "rms_norm_eps") if k in cfg)
    return ModelConfig(**{
        "name": cfg["name"], "n_layers": cfg["num_hidden_layers"],
        "d_model": cfg["hidden_size"], "n_heads": cfg["num_attention_heads"],
        "n_kv_heads": cfg["num_key_value_heads"],
        "d_ff": cfg["intermediate_size"], "vocab_size": cfg["vocab_size"],
        "head_dim": cfg.get("head_dim"), "norm_eps": eps,
        "rope_theta": cfg["rope_theta"], "dtype": cfg["torch_dtype"],
        "remat": False, **cfg["program"]})


class Stream:
    """One request as the client sees it."""

    def __init__(self, rid, due, prompt, n_out):
        self.rid, self.due, self.prompt, self.n_out = rid, due, prompt, n_out
        self.times: list = []
        self.tokens: list = []


class ServeOpen:
    def __init__(self, ctx):
        self.ctx = ctx
        self.t = ctx.traffic
        self.cfg = ctx.config
        self.ref = ctx.reference
        self.serving = self.cfg["serving"]
        self.streams: list = []
        self.steps: list = []
        self.attempted = self.failed = 0
        self.layer = {}
        self._trace_at = None
        self._next = 0

    # -- set-up ------------------------------------------------------------------
    def setup(self):
        from repro.models import build_model
        from repro.serving.cost import cost_model_for
        from repro.serving.scheduler import ModelBackend, SchedulerConfig
        from repro.tuner import PlanCache, Tuner
        from repro.tuner.registry import DEFAULT_REGISTRY, machine_for_devices

        ctx = self.ctx
        self.ref.check_config(self.cfg)
        self.mcfg = program_config(self.cfg)
        self.model = build_model(self.mcfg)
        self.params = self.load_weights(ctx.seed)
        self.backend = ModelBackend(
            self.model, self.params,
            max_cache_len=self.serving["max_cache_len"],
            tuner=Tuner(cache=PlanCache(ctx.work_dir + "/plans")))
        machine = DEFAULT_REGISTRY.machine(
            machine_for_devices(ctx.devices)).machine
        self.cost = cost_model_for(self.mcfg, machine)
        self.scfg = SchedulerConfig(
            max_cache_len=self.serving["max_cache_len"],
            max_batch=self.serving["max_batch"])
        self.warm_up()

    def load_weights(self, seed):
        params = self.ref.weights(jax.random.fold_in(seed_key(seed), 0),
                                  self.cfg, device=self.ctx.devices[0])
        want = jax.eval_shape(self.model.init, jax.random.PRNGKey(0))
        got = jax.eval_shape(lambda p: p, params)
        if jax.tree.structure(want) != jax.tree.structure(got) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(want), jax.tree.leaves(got))):
            raise ValueError("the program's parameter layout differs from "
                             "the benchmark's weights")
        return jax.block_until_ready(params)

    def scheduler(self):
        from repro.serving.policy import FIFOPolicy
        from repro.serving.scheduler import Scheduler
        if self.serving["policy"] != "fifo":
            raise ValueError(self.serving["policy"])
        return Scheduler(self.backend, self.cost, self.scfg,
                         policy=FIFOPolicy())

    def warm_up(self):
        """Every program and host-side shape the window uses: for each
        prefill chunk size the traffic's prompt lengths get, prompts that
        end on a whole chunk, on a one-token tail, and on a tail after two
        chunks (a chunk that continues a cache); and decode batches of
        every size from ``max_batch`` down to 1."""
        p = self.t["prompt"]
        chunks = sorted({self.backend.chunk_granularity(n)
                         for n in range(p["min"], p["max"] + 1)})
        lengths = [n for c in chunks for n in (c, c + 1, 2 * c + 1)
                   if n <= p["max"] and self.backend.chunk_granularity(n) == c]
        b = max(self.serving["max_batch"], len(lengths))
        vocab = self.cfg["vocab_size"]
        streams = [Stream(f"warm{k}", 0.0,
                          np.full((1, lengths[k % len(lengths)]), k % vocab,
                                  np.int32), k + 2) for k in range(b)]
        self._drive(self.scheduler(), streams, record=False)

    # -- the loop ------------------------------------------------------------------
    def _submit(self, sched, s):
        from repro.serving.scheduler import Request
        with self.ctx.span("bench.submit"):
            sched.submit(Request(rid=s.rid, prompt=s.prompt,
                                 max_new_tokens=s.n_out, temperature=0.0))

    def _step(self, sched, by_rid, record):
        ctx = self.ctx
        t0 = time.perf_counter()
        with ctx.span("bench.step"):
            rep = sched.step()
        t1 = time.perf_counter()
        if rep is None:
            return
        touched = [r for r, _ in rep.plan.prefill] + list(rep.plan.decode)
        new = []
        for rid in touched:
            rs = sched.active.get(rid) or sched.finished[rid]
            s = by_rid[rid]
            new += [(s, tok) for tok in rs.out[len(s.tokens):]]
        with ctx.span("bench.tokens"):
            host = jax.device_get([tok for _, tok in new])
        t2 = time.perf_counter()
        for (s, _), tok in zip(new, host):
            s.tokens.append(int(np.asarray(tok).reshape(-1)[0]))
            s.times.append(t2)
        if record:
            positions = []
            for rid, n in rep.plan.prefill:
                end = sched.active.get(rid) or sched.finished[rid]
                positions += range(end.prefill_pos - n, end.prefill_pos)
            for rid in rep.plan.decode:
                s = by_rid[rid]
                positions.append(s.prompt.shape[1] + len(s.tokens) - 2)
            self.steps.append({
                "start": t0, "end": t1, "wall_s": t1 - t0,
                "prefill_tokens": sum(n for _, n in rep.plan.prefill),
                "decode_batch": len(rep.plan.decode),
                "flops": work.decoder_token_flops(self.flop_cfg, positions)})

    def _drive(self, sched, streams, *, record, t_start=None, until=None,
               j=0, hold=False):
        """Submit each stream when due, from the ``j``-th in due order on,
        and step until every stream is done or the clock passes ``until``;
        with ``hold``, wait for ``until`` even when all are done.  Returns
        how many streams have been submitted."""
        by_rid = {s.rid: s for s in streams}
        t_start = time.perf_counter() if t_start is None else t_start
        pending = sorted(streams, key=lambda s: s.due)
        while True:
            now = time.perf_counter()
            if until is not None and now >= until:
                return j
            self._trace_clock(now)
            while j < len(pending) and t_start + pending[j].due <= now:
                self._submit(sched, pending[j])
                j += 1
            if sched.waiting or sched.active:
                self._step(sched, by_rid, record)
                continue
            if j == len(pending) and not hold:
                return j
            nxt = until if j == len(pending) else t_start + pending[j].due
            wait = nxt - time.perf_counter()
            if wait > 0:
                with self.ctx.span("bench.wait"):
                    time.sleep(wait)

    def _trace_clock(self, now):
        if self._trace_at is None:
            return
        lo, hi = self._trace_at
        if now >= lo:
            self.ctx.trace_begin()
        if now >= hi:
            self.ctx.trace_end()

    # -- the window ----------------------------------------------------------------
    def window(self, seconds):
        m = self.mcfg
        self.flop_cfg = {"d_model": m.d_model, "head_dim": m.hd,
                         "n_heads": m.n_heads, "n_kv_heads": m.n_kv_heads,
                         "d_ff": m.d_ff, "gated_mlp": m.gated_mlp,
                         "n_layers": m.n_layers, "vocab_size": m.vocab_size}
        reqs = generate(self.t, seconds, self.ctx.seed, m.vocab_size)
        self.streams = [Stream(f"r{j}", due, p, n)
                        for j, (due, p, n) in enumerate(reqs)]
        self.sched = self.scheduler()
        self.t_start = time.perf_counter()
        self.t_end = self.t_start + seconds
        lo = self.t_start + float(self.t.get("trace_start_s", 0.0))
        hi = lo + float(self.t.get("trace_seconds", seconds))
        self._trace_at = (lo, min(hi, self.t_end))
        self._next = self._drive(self.sched, self.streams, record=True,
                                 t_start=self.t_start, until=self.t_end,
                                 hold=True)

    def finish(self):
        self.ctx.trace_end()
        self._trace_at = None
        # the drain is timed from the window's end, less the time a traced
        # run spent writing its trace
        self._drive(self.sched, self.streams, record=True,
                    t_start=self.t_start, j=self._next,
                    until=self.t_end + float(self.t["drain_s"])
                    + self.ctx.trace_pause_s)
        self.t_done = time.perf_counter()

    def release(self):
        self.sched = None
        self.backend._state.clear()
        self.backend._dummy = None
        gc.collect()

    # -- results -------------------------------------------------------------------
    def _ttfts(self):
        return [(s.times[0] if s.times else self.t_done) - (self.t_start + s.due)
                for s in self.streams]

    def _gaps(self):
        return [b - a for s in self.streams for a, b in zip(s.times,
                                                            s.times[1:])]

    def end_to_end(self):
        ttft, gaps = self._ttfts(), self._gaps()
        return {"ttft_p50_s": work.percentile(ttft, 50),
                "ttft_p75_s": work.percentile(ttft, 75),
                "itl_p50_s": work.percentile(gaps, 50)}

    def summary(self) -> str:
        ttft, gaps = self._ttfts(), self._gaps()
        itl = " ".join(f"p{q} {work.percentile(gaps, q):.4f}"
                       for q in (10, 25, 50, 75, 90, 95, 99)) if gaps else "-"
        batches = collections.Counter(st["decode_batch"] for st in self.steps
                                      if not st["prefill_tokens"])
        return (f"serve: requests {len(self.streams)} due in the window, "
                f"{sum(len(s.tokens) == s.n_out for s in self.streams)} "
                f"finished; ttft p50 {statistics.median(ttft):.4f} s "
                f"p75 {work.percentile(ttft, 75):.4f} s p90 "
                f"{work.percentile(ttft, 90):.4f} s over {len(ttft)} "
                f"requests; itl {itl} s over {len(gaps)} gaps; "
                f"drain {self.t_done - self.t_end:.2f} s; steps "
                f"{len(self.steps)} (prefill "
                f"{sum(1 for st in self.steps if st['prefill_tokens'])}, "
                f"decode by batch {dict(sorted(batches.items()))})")

    def sample(self, k=None):
        """Finished requests to compare: the longest, then the others in
        an order drawn from the seed, ``k`` (the mix's ``check_requests``)
        in all."""
        done = [s for s in self.streams if len(s.tokens) == s.n_out]
        if not done:
            return []
        k = int(self.t["check_requests"]) if k is None else k
        longest = max(done, key=lambda s: s.prompt.shape[1] + s.n_out)
        rest = [s for s in done if s is not longest]
        order = seed_rng(self.ctx.seed, 2).permutation(len(rest))
        return [longest] + [rest[i] for i in order[:k - 1]]

    def teacher_forced(self, streams):
        """(tokens (B, S), positions (B, T), served (B, T), mask (B, T)) of
        prompt + served tokens, padded to one shape for every run."""
        p, o = self.t["prompt"], self.t["output"]
        s_len = work.round_up(p["max"] + o["max"] - 1, 128)
        t_len = o["max"]
        b = len(streams)
        tokens = np.zeros((b, s_len), np.int32)
        pos = np.zeros((b, t_len), np.int32)
        served = np.zeros((b, t_len), np.int32)
        mask = np.zeros((b, t_len), bool)
        for i, s in enumerate(streams):
            n_p, n_o = s.prompt.shape[1], len(s.tokens)
            seq = np.concatenate([s.prompt[0], np.asarray(s.tokens[:-1],
                                                          np.int32)])
            tokens[i, :len(seq)] = seq
            pos[i, :n_o] = np.arange(n_p - 1, n_p - 1 + n_o)
            served[i, :n_o] = s.tokens
            mask[i, :n_o] = True
        return (jnp.asarray(tokens), jnp.asarray(pos), jnp.asarray(served),
                jnp.asarray(mask))

    def gap_readings(self, streams, precision=None):
        """Widest served-token gap of each stream against the float32
        reference; with ``precision``, also that of the tokens the
        reference in that precision puts first (the control)."""
        tokens, pos, served, mask = self.teacher_forced(streams)
        ref = self.ref.logits_at(self.params, self.cfg, tokens, pos, "f32")
        out = {"served": np.asarray(jnp.max(
            self.ref.gaps(ref, served, mask), axis=1))}
        if precision is not None:
            low = self.ref.logits_at(self.params, self.cfg, tokens, pos,
                                     precision)
            out["control"] = np.asarray(jnp.max(self.ref.gaps(
                ref, jnp.argmax(low, -1).astype(jnp.int32), mask), axis=1))
        return out

    def control(self, seed, seconds):
        """A window at the cell's load on the weights and traffic of
        ``seed``; the widest served-token gap of the program, and that of
        the reference in fp8, on the same sample; and both again on the
        first 8 and 16 requests of a sample of every finished request."""
        self.backend.params = self.params = None
        gc.collect()
        self.params = self.backend.params = self.load_weights(seed)
        self.ctx.seed = seed
        self.steps = []
        self.window(seconds)
        self.finish()
        print(self.summary(), flush=True)
        sample = self.sample(len(self.streams))
        self.release()
        g = self.gap_readings(sample, precision=CONTROL_PRECISION)
        k = int(self.t["check_requests"])
        row = {"served.logit_gap": float(g["served"][:k].max()),
               "control.served.logit_gap": float(g["control"][:k].max()),
               "compared_tokens": sum(len(s.tokens) for s in sample[:k])}
        for n in (8, 16, len(sample)):
            row[f"first{n}"] = [float(g["served"][:n].max()),
                                float(g["control"][:n].max()),
                                sum(len(s.tokens) for s in sample[:n])]
        row["failed"] = sum(len(s.tokens) != s.n_out for s in self.streams)
        return row

    def checks(self):
        print(self.summary(), flush=True)
        self.attempted = len(self.streams)
        self.failed = sum(len(s.tokens) != s.n_out for s in self.streams)
        sample = self.sample()
        gap = math.inf
        if sample and self.ctx.control:
            gap = float(self.gap_readings(sample, CONTROL_PRECISION)[
                "control"].max())
        elif sample:
            gap = float(self.gap_readings(sample)["served"].max())
        # the gaps between tokens that came before a trace's recording
        # stopped: its stop holds the loop
        stop = self.ctx.trace_host[1] or math.inf
        self.layer = {
            "steps": [dict(st, traced=self.ctx.in_trace(st["start"])
                           and self.ctx.in_trace(st["end"]))
                      for st in self.steps],
            "itl_gaps": [b - a for s in self.streams
                         for a, b in zip(s.times, s.times[1:]) if b <= stop],
        }
        return [Check("served.logit_gap", gap,
                      float(self.t["limits"]["served.logit_gap"]))]


def make(ctx):
    return ServeOpen(ctx)

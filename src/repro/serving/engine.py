"""Single-call generation facade over the continuous-batching scheduler.

``Engine.generate`` is now a thin wrapper: each prompt row becomes one
:class:`~.scheduler.Request`, the batch is submitted to a
:class:`~.scheduler.Scheduler` over a :class:`~.scheduler.ModelBackend`
(per-request caches, vmapped batched decode), and the scheduler's
admission/compose/evict loop runs it to completion.  One code path
serves both the one-shot API and the streaming trace-replay harness, so
the single-request semantics the tests pin down (greedy determinism,
chunked-prefill equivalence, ring-buffer safety) are exactly the
semantics of the continuous-batching engine.

Generation for a request ends at ``max_new_tokens`` or earlier on an
EOS / stop token (``ServeConfig.eos_id`` / ``stop_ids``); early-stopped
rows are right-padded so the output shape stays ``(B, S + max_new)``.
No decode step runs after a request's last token — the scheduler evicts
on completion instead of stepping once more and discarding the logits.

With telemetry recording on (``REPRO_TELEMETRY=1`` /
``repro.telemetry.enable()``) every ``generate`` call emits one measured
run — prefill and decode as separate phases, blocked to completion — and
the scheduler additionally emits one ``serve_step`` record per step with
the cost model's prediction attached, feeding the refit loop."""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..models import Model


@dataclasses.dataclass
class ServeConfig:
    max_new_tokens: int = 32
    temperature: float = 0.0           # 0 = greedy
    max_cache_len: int = 4096
    prefill_chunk: Optional[int] = None  # None: ask the tuner; 1: per-token
    eos_id: Optional[int] = None       # generation stops when sampled
    stop_ids: Tuple[int, ...] = ()     # additional per-request stop tokens
    pad_id: Optional[int] = None       # fill for early-stopped rows
                                       # (default: eos_id, else 0)


def make_serve_step(model: Model):
    """The jittable decode step: (params, tokens, caches, memory) ->
    (logits, new_caches).  tokens is (B, 1) for generation or (B, chunk)
    during chunked prefill."""

    def serve_step(params, tokens, caches, memory=None):
        return model.decode_step(params, tokens, caches, memory)

    return serve_step


class Engine:
    def __init__(self, model: Model, params,
                 cfg: Optional[ServeConfig] = None):
        self.model = model
        self.params = params
        self.cfg = cfg if cfg is not None else ServeConfig()
        self._step = jax.jit(make_serve_step(model))
        self._backend = None           # built lazily, reused across calls

    def _prefill_chunk(self, seq_len: int) -> int:
        # architecture gate first: recurrent decode paths and sliding-window
        # ring buffers are strictly one-token, whatever the config asks for
        if not self.model.supports_chunked_prefill:
            return 1
        if self.cfg.prefill_chunk is not None:
            return max(1, self.cfg.prefill_chunk)
        from ..tuner import default_tuner
        return default_tuner().prefill_chunk(seq_len)

    def _timer(self, seq_len: int):
        """A telemetry PhaseTimer tagged for this engine, or None when
        recording is off (the only cost paid on the fast path)."""
        from .. import telemetry
        if not telemetry.enabled():
            return None
        from ..tuner.plan import machine_fingerprint
        from ..tuner.registry import DEFAULT_REGISTRY, machine_for_devices
        devs = jax.devices()
        name = machine_for_devices(devs)
        fp = machine_fingerprint(DEFAULT_REGISTRY.machine(name).machine,
                                 devs[0].platform, devs[0].device_kind,
                                 len(devs))
        arch = getattr(getattr(self.model, "cfg", None), "name",
                       type(self.model).__name__)
        return telemetry.PhaseTimer(
            "serve", variant=str(arch), n=seq_len,
            p=len(devs), machine=name, fingerprint=fp, kind="serve",
            meta={"max_new_tokens": self.cfg.max_new_tokens})

    def _make_scheduler(self, batch: int, phase_timer):
        from ..tuner.registry import DEFAULT_REGISTRY, machine_for_devices
        from .cost import cost_model_for
        from .policy import FIFOPolicy
        from .scheduler import ModelBackend, Scheduler, SchedulerConfig

        if self._backend is None:
            self._backend = ModelBackend(
                self.model, self.params,
                max_cache_len=self.cfg.max_cache_len,
                prefill_chunk=self.cfg.prefill_chunk, step=self._step)
        machine = DEFAULT_REGISTRY.machine(machine_for_devices()).machine
        cost = cost_model_for(self.model.cfg, machine)
        scfg = SchedulerConfig(max_cache_len=self.cfg.max_cache_len,
                               max_batch=max(batch, 1),
                               max_active=max(batch, 1))
        return Scheduler(self._backend, cost, scfg, policy=FIFOPolicy(),
                         phase_timer=phase_timer)

    def generate(self, prompts: jax.Array, *,
                 batch_inputs: Optional[Dict[str, Any]] = None,
                 seed: int = 0) -> jax.Array:
        """prompts: (B, S) int32.  Returns (B, S + max_new) tokens;
        rows that hit an EOS/stop token early are padded to shape."""
        from .scheduler import Request

        b, s = prompts.shape
        cfg = self.cfg
        if cfg.max_new_tokens <= 0:
            return prompts
        pt = self._timer(s)
        memory = None
        if batch_inputs:
            memory = self.model.encode_memory(self.params, batch_inputs)

        sched = self._make_scheduler(b, pt)
        rids = []
        for i in range(b):
            rids.append(sched.submit(Request(
                rid=f"g{i}", prompt=prompts[i:i + 1],
                max_new_tokens=cfg.max_new_tokens,
                eos_id=cfg.eos_id, stop_ids=tuple(cfg.stop_ids),
                memory=None if memory is None else memory[i:i + 1],
                temperature=cfg.temperature, seed=seed + i)))
        sched.run()
        if pt is not None:
            pt.emit()

        pad = cfg.pad_id if cfg.pad_id is not None \
            else (cfg.eos_id if cfg.eos_id is not None else 0)
        rows = []
        for rid in rids:
            toks = sched.finished[rid].out
            gen = jnp.concatenate(
                [jnp.asarray(t, jnp.int32).reshape(1, 1) for t in toks],
                axis=1)
            if gen.shape[1] < cfg.max_new_tokens:
                gen = jnp.pad(gen,
                              ((0, 0), (0, cfg.max_new_tokens - gen.shape[1])),
                              constant_values=pad)
            rows.append(gen)
        return jnp.concatenate([prompts, jnp.concatenate(rows, axis=0)],
                               axis=1)

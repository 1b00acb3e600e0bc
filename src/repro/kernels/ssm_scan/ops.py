"""jit'd wrapper: (B, H, S, D) API, sequence padding (log_a padding uses 0
= no decay, k padding 0 contributes nothing), head folding."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from ..common import TilePlan, pad_axes, resolve_interpret, tile_block
from .ref import ssm_scan_ref
from .ssm_scan import ssm_scan_pallas


@functools.partial(jax.jit, static_argnames=("interpret", "tiles"))
def ssm_scan(q: jax.Array, k: jax.Array, v: jax.Array, log_a: jax.Array, *,
             interpret: Optional[bool] = None,
             tiles: Optional[TilePlan] = None) -> jax.Array:
    """q, k: (B, H, S, DK); v: (B, H, S, DV); log_a: (B, H, S).

    ``tiles`` is an ssm_scan :class:`TilePlan` (dim bs); the sequence is
    padded to its chunk multiple so the chosen chunk runs as-is.
    """
    b, h, s, dk = q.shape
    dv = v.shape[-1]
    if s < 128:
        return ssm_scan_ref(q.reshape(b * h, s, dk), k.reshape(b * h, s, dk),
                            v.reshape(b * h, s, dv),
                            log_a.reshape(b * h, s)).reshape(b, h, s, dv)
    bs = tile_block(tiles, "ssm_scan", "bs", 256)
    s_mult = bs if tiles is not None else 128
    qp = pad_axes(q, {2: s_mult})
    kp = pad_axes(k, {2: s_mult})
    vp = pad_axes(v, {2: s_mult})
    lap = pad_axes(log_a, {2: s_mult})
    sp = qp.shape[2]
    y = ssm_scan_pallas(qp.reshape(b * h, sp, dk), kp.reshape(b * h, sp, dk),
                        vp.reshape(b * h, sp, dv),
                        lap.reshape(b * h, sp), bs=bs,
                        interpret=resolve_interpret(interpret))
    return y.reshape(b, h, sp, dv)[:, :, :s, :]

"""jit'd wrapper: (B, H, S, D) API, head-dim padding to 128-multiples,
sequence padding to the tile plan's block multiples, GQA folding."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from ..common import TilePlan, pad_axes, resolve_interpret, tile_block
from .flash_attention import flash_attention_pallas
from .ref import flash_attention_ref


@functools.partial(jax.jit, static_argnames=("causal", "interpret", "tiles"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, interpret: Optional[bool] = None,
                    tiles: Optional[TilePlan] = None) -> jax.Array:
    """q: (B, H, S, D); k, v: (B, KV, S, D).  Returns (B, H, S, D).

    ``tiles`` is a flash_attention :class:`TilePlan` (dims bq/bkv);
    sequences are padded to its block multiples so the chosen blocks run
    as-is (without a plan, padding stops at the 128 lane tile and the
    kernel halves its default 256 blocks until they divide).
    """
    b, h, s, d = q.shape
    _, kv, skv, _ = k.shape
    scale = d ** -0.5  # scale by the *true* head dim before padding
    if s < 128 or skv < 128 or (causal and s != skv):
        # tiny shapes, or causal cross-length (decode) -> oracle path
        return flash_attention_ref(q.reshape(b * h, s, d),
                                   k.reshape(b * kv, skv, d),
                                   v.reshape(b * kv, skv, d),
                                   causal=causal, scale=scale).reshape(b, h, s, d)
    bq = tile_block(tiles, "flash_attention", "bq", 256)
    bkv = tile_block(tiles, "flash_attention", "bkv", 256)
    # pad sequences to the plan's blocks (plain 128 when no plan — the
    # kernel's divisibility halving then recovers today's behaviour)
    sq_mult = bq if tiles is not None else 128
    skv_mult = bkv if tiles is not None else 128
    qp = pad_axes(q, {2: sq_mult, 3: 128})
    kp = pad_axes(k, {2: skv_mult, 3: 128})
    vp = pad_axes(v, {2: skv_mult, 3: 128})
    sp, dp = qp.shape[2], qp.shape[3]
    skvp = kp.shape[2]
    out = flash_attention_pallas(
        qp.reshape(b * h, sp, dp), kp.reshape(b * kv, skvp, dp),
        vp.reshape(b * kv, skvp, dp), causal=causal, scale=scale,
        bq=bq, bkv=bkv, kv_len=skv,
        interpret=resolve_interpret(interpret))
    return out.reshape(b, h, sp, dp)[:, :, :s, :d]

"""Plain reference of a dense decoder configuration as it is run.

The forward pass in straightforward ``jax.numpy``, float32 at
``Precision.HIGHEST``, with no cache, no batching of requests and no
kernels; it imports nothing of the program.  Per layer:

    x = x + Wo . attn(rope(Wq n1(x) + bq), rope(Wk n1(x) + bk), Wv n1(x) + bv)
    x = x + Wdown . mlp(n2(x))

with ``n1``/``n2`` RMS norms with a learned scale, rotary embeddings on
split halves at ``rope_theta``, grouped-query attention (query head h reads
key/value head h // (heads / kv_heads)), causal, scale 1/sqrt(head_dim);
then a final RMS norm and the output head.  The configuration's keys
choose among the variants written here, and anything else is refused:

* ``hidden_act``: ``gelu_pytorch_tanh`` or ``silu``;
* ``gated_mlp`` (absent: false): mlp(h) = act(Wgate h) * (Wup h), as in
  Llama, else act(Wup h);
* ``use_bias``: ``"qkv_only"`` (biases on q, k and v) or false;
* ``tie_word_embeddings``: the head is the embedding table transposed;
* ``norm_type`` ``rms_norm`` and ``sliding_window`` null.

The weights are made here, on the device, from the seed, in the layout the
program takes (``weights``), and the reference reads them from that tree:
it is the format of the program's input, not anything the program made.
The reference upcasts one layer at a time, so a float32 copy of the whole
model never exists.  ``precision="fp8"`` is the control: every weight and
activation product in float8 (e4m3, one scale per row of the activations
and per output column of the weights), the precision below bfloat16.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax import lax

HI = lax.Precision.HIGHEST
FP8_MAX = 448.0


ACTIVATIONS = {"gelu_pytorch_tanh": lambda x: jax.nn.gelu(x, approximate=True),
               "silu": jax.nn.silu}


def dims(cfg: dict) -> dict:
    heads = cfg["num_attention_heads"]
    return {"d": cfg["hidden_size"], "ff": cfg["intermediate_size"],
            "layers": cfg["num_hidden_layers"], "heads": heads,
            "kv": cfg["num_key_value_heads"],
            "hd": cfg.get("head_dim") or cfg["hidden_size"] // heads,
            "vocab": cfg["vocab_size"],
            "eps": next(cfg[k] for k in ("norm_epsilon", "rms_norm_eps")
                        if k in cfg),
            "theta": cfg["rope_theta"],
            "gated": bool(cfg.get("gated_mlp", False)),
            "qkv_bias": cfg["use_bias"] == "qkv_only",
            "tied": bool(cfg["tie_word_embeddings"])}


def check_config(cfg: dict) -> None:
    """The variants this reference writes down; anything else is a
    different model."""
    allowed = {"norm_type": ("rms_norm",), "use_bias": ("qkv_only", False),
               "hidden_act": tuple(ACTIVATIONS),
               "tie_word_embeddings": (True, False),
               "sliding_window": (None,)}
    for k, vs in allowed.items():
        if cfg.get(k) not in vs:
            raise ValueError(f"reference written for {k} in {vs!r}, config "
                             f"has {cfg.get(k)!r}")


# -- weights ------------------------------------------------------------------

def weights(key, cfg: dict, device=None):
    """All weights in the configuration's ``torch_dtype``, made on the
    device in one call: projections and the embedding table normal with
    variance 1/d_in (the table's rows have the hidden size, so a tied head
    gives logits of unit scale), biases N(0, 0.01), norm scales
    1 + N(0, 0.01).  Layout: the program's parameter tree, layers stacked
    on a leading axis."""
    m = dims(cfg)
    dtype = jnp.dtype(cfg["torch_dtype"])
    d, ff, L, h, kv, hd, v = (m["d"], m["ff"], m["layers"], m["heads"],
                              m["kv"], m["hd"], m["vocab"])

    def build(key):
        ks = iter(jax.random.split(key, 16))

        def proj(shape, fan_in):
            return (jax.random.normal(next(ks), shape, jnp.float32)
                    / math.sqrt(fan_in)).astype(dtype)

        def small(shape, base=0.0):
            return (base + 0.1 * jax.random.normal(next(ks), shape,
                                                   jnp.float32)).astype(dtype)

        def qkv(width):
            p = {"w": proj((L, d, width), d)}
            if m["qkv_bias"]:
                p["b"] = small((L, width))
            return p

        mlp = {"up": {"w": proj((L, d, ff), d)},
               "down": {"w": proj((L, ff, d), ff)}}
        if m["gated"]:
            mlp["gate"] = {"w": proj((L, d, ff), d)}
        layer = {
            "norm1": {"scale": small((L, d), 1.0)},
            "attn": {"wq": qkv(h * hd), "wk": qkv(kv * hd),
                     "wv": qkv(kv * hd),
                     "wo": {"w": proj((L, h * hd, d), h * hd)}},
            "norm2": {"scale": small((L, d), 1.0)},
            "mlp": mlp,
        }
        out = {"embed": {"w": proj((v, d), d)},
               "final_norm": {"scale": small((d,), 1.0)},
               "groups": [(layer,)]}
        if not m["tied"]:
            out["lm_head"] = {"w": proj((d, v), d)}
        return out

    out = None
    if device is not None:
        out = jax.sharding.SingleDeviceSharding(device)
    return jax.jit(build, out_shardings=out)(key)


# -- arithmetic -----------------------------------------------------------------

def _q8(x, axis):
    """x rounded to float8 (e4m3) under one scale per slice along
    ``axis``; the barrier keeps the compiler from folding the round trip
    away where it allows excess precision."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / FP8_MAX, 1.0)
    q = lax.optimization_barrier((x / s).astype(jnp.float8_e4m3fn))
    return q.astype(jnp.float32) * s


def matmul(x, w, precision: str):
    """x (..., k) @ w (k, n) in float32, or both in fp8 for the control."""
    w = w.astype(jnp.float32)
    if precision == "fp8":
        x, w = _q8(x, -1), _q8(w, 0)
    elif precision != "f32":
        raise ValueError(precision)
    return jnp.dot(x, w, precision=HI)


def rmsnorm(x, scale, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(jnp.float32)


def rope(x, theta):
    """x (S, H, hd) at positions 0..S-1, halves rotated."""
    s, _, hd = x.shape
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _attention(q, k, v, m):
    """One sequence: q (S, H, hd), k/v (S, KV, hd); causal."""
    s = q.shape[0]
    group = m["heads"] // m["kv"]
    k = jnp.repeat(k, group, axis=1)
    v = jnp.repeat(v, group, axis=1)
    sc = jnp.einsum("qhd,khd->hqk", q, k, precision=HI) / math.sqrt(m["hd"])
    causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    p = jax.nn.softmax(jnp.where(causal, sc, -jnp.inf), axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v, precision=HI)


def _layer(x, stack, l, cfg, precision):
    """Layer ``l`` of the stacked weights on x (B, S, d)."""
    m = dims(cfg)
    w = jax.tree.map(lambda a: a[l].astype(jnp.float32), stack)
    b, s, _ = x.shape
    n1 = rmsnorm(x, w["norm1"]["scale"], m["eps"])
    a = w["attn"]

    def proj(p, heads):
        y = matmul(n1, p["w"], precision) + p.get("b", 0.0)
        return y.reshape(b, s, heads, m["hd"])

    q, k, v = (proj(a["wq"], m["heads"]), proj(a["wk"], m["kv"]),
               proj(a["wv"], m["kv"]))
    q = jax.vmap(lambda t: rope(t, m["theta"]))(q)
    k = jax.vmap(lambda t: rope(t, m["theta"]))(k)
    o = lax.map(lambda qkv: _attention(*qkv, m), (q, k, v))
    x = x + matmul(o.reshape(b, s, -1), a["wo"]["w"], precision)
    n2 = rmsnorm(x, w["norm2"]["scale"], m["eps"])
    act = ACTIVATIONS[cfg["hidden_act"]]
    up = matmul(n2, w["mlp"]["up"]["w"], precision)
    if m["gated"]:
        h = act(matmul(n2, w["mlp"]["gate"]["w"], precision)) * up
    else:
        h = act(up)
    return x + matmul(h, w["mlp"]["down"]["w"], precision)


def _embed(params, tokens):
    return params["embed"]["w"][tokens].astype(jnp.float32)


def _head(params, x, cfg, precision):
    m = dims(cfg)
    n = rmsnorm(x, params["final_norm"]["scale"], m["eps"])
    head = params["embed"]["w"].T if m["tied"] else params["lm_head"]["w"]
    return matmul(n, head, precision)


class _Frozen(dict):
    """A configuration usable as a static jit argument."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


_layer_jit = jax.jit(_layer, static_argnums=(3, 4))
_embed_jit = jax.jit(_embed)
_head_jit = jax.jit(_head, static_argnums=(2, 3))


def logits_at(params, cfg: dict, tokens, positions, precision: str = "f32",
              rows: int = 2):
    """Logits (B, T, vocab) at ``positions`` (B, T) of the sequences
    ``tokens`` (B, S), computed layer by layer, ``rows`` sequences at a
    time so that the float32 activations of long sequences fit."""
    frozen = _Frozen(cfg)
    stack = params["groups"][0][0]
    out = []
    for r0 in range(0, tokens.shape[0], rows):
        x = _embed_jit(params, tokens[r0:r0 + rows])
        for l in range(dims(cfg)["layers"]):
            x = _layer_jit(x, stack, l, frozen, precision)
        pick = jnp.take_along_axis(x, positions[r0:r0 + rows, :, None],
                                   axis=1)
        out.append(_head_jit(params, pick, frozen, precision))
    return jnp.concatenate(out)


def gaps(ref_logits, tokens, mask):
    """How far each token's reference logit lies below the reference's
    best at the same position; 0 where ``mask`` is false."""
    best = jnp.max(ref_logits, axis=-1)
    got = jnp.take_along_axis(ref_logits, tokens[..., None], -1)[..., 0]
    return jnp.where(mask, best - got, 0.0)

"""Error-feedback int8 gradient compression for the cross-pod (DCN) axis.

Within a pod, gradients reduce over ICI at full precision (cheap).  Across
pods the DCN is the scarce resource — the paper's bandwidth-degradation
lesson — so the pod-axis mean is computed on int8-quantized gradients with
per-tensor scales and an error-feedback buffer that re-injects the
quantization residual next step (Seide et al. 2014 / Karimireddy et al.
2019 — guarantees convergence matching uncompressed SGD asymptotically).

Implementation: shard_map over the 'pod' axis; each pod quantizes its
local mean gradient, all-gathers the int8 payload (pods x bytes instead of
2 x bytes x fp32 for a ring all-reduce), dequantizes and averages locally.
"""

from __future__ import annotations

from typing import Any, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P



def _quantize(x, *, dtype=jnp.int8):
    amax = jnp.max(jnp.abs(x)) + 1e-12
    scale = amax / 127.0
    q = jnp.clip(jnp.round(x / scale), -127, 127).astype(dtype)
    return q, scale


def _dequantize(q, scale):
    return q.astype(jnp.float32) * scale


def compress_pod_mean(grads: Any, err: Any, mesh) -> Tuple[Any, Any]:
    """Mean over the 'pod' mesh axis with int8 + error feedback.

    grads: pytree of *pod-local* gradient arrays (already reduced over the
    in-pod data axis, replicated within the pod).  err: matching residual
    buffers.  Returns (mean_grads, new_err)."""
    if "pod" not in mesh.shape or mesh.shape["pod"] == 1:
        return grads, err
    npods = mesh.shape["pod"]

    def one(g, e):
        def body(gl, el):
            x = gl.astype(jnp.float32) + el
            q, scale = _quantize(x)
            new_e = x - _dequantize(q, scale)
            qs = jax.lax.all_gather(q, "pod")                 # (npods, ...)
            ss = jax.lax.all_gather(scale, "pod")             # (npods,)
            deq = qs.astype(jnp.float32) * ss.reshape(
                (npods,) + (1,) * gl.ndim)
            return jnp.mean(deq, axis=0).astype(gl.dtype), new_e

        spec = P()  # replicated over pod inside each pod's shards
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(spec, spec), out_specs=(spec, spec),
                             check_vma=False)(g, e)

    flat_g, treedef = jax.tree.flatten(grads)
    flat_e = jax.tree.leaves(err)
    out_g, out_e = [], []
    for g, e in zip(flat_g, flat_e):
        mg, ne = one(g, e)
        out_g.append(mg)
        out_e.append(ne)
    return jax.tree.unflatten(treedef, out_g), jax.tree.unflatten(treedef, out_e)


def init_error_buffers(grads_shape_tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, jnp.float32),
                        grads_shape_tree)

"""Cross-pod gradient compression with error feedback (DESIGN.md §4).

The pod axis is the slow DCN link: compressing the cross-pod gradient
exchange to int8 (blockwise absmax scales) cuts its wire bytes 2x vs bf16 /
4x vs fp32.  Int8 summation would overflow, so the exchange is an
all-gather of int8 shards + local dequant-mean; error feedback accumulates
the quantization residual into the next step so compression noise does not
bias convergence (1-bit-Adam/EF-SGD style).

Implemented with shard_map over the 'pod' axis; within-pod reduction stays
full precision.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def _quant_block(x, block: int = 256):
    flat = x.reshape(-1)
    pad = (-flat.size) % block
    if pad:
        flat = jnp.pad(flat, (0, pad))
    blocks = flat.reshape(-1, block)
    scale = jnp.maximum(jnp.max(jnp.abs(blocks), 1, keepdims=True), 1e-12) / 127.0
    q = jnp.clip(jnp.round(blocks / scale), -127, 127).astype(jnp.int8)
    return q, scale


def _dequant_block(q, scale, shape):
    flat = (q.astype(jnp.float32) * scale).reshape(-1)
    n = 1
    for dim in shape:
        n *= dim
    return flat[:n].reshape(shape)


def compressed_crosspod_mean(grad: jax.Array, err: jax.Array, mesh,
                             block: int = 256):
    """Mean-reduce ``grad`` across the 'pod' axis with int8 wire format.

    err is this pod's error-feedback buffer (same shape as grad).
    Returns (mean_grad, new_err).  Without a 'pod' axis: identity.
    """
    if "pod" not in mesh.axis_names:
        return grad, err

    def body(g, e):
        # g, e are the per-pod (replicated within pod) values
        target = g.astype(jnp.float32) + e
        q, scale = _quant_block(target, block)
        sent = _dequant_block(q, scale, g.shape)
        new_err = target - sent           # residual stays local (EF)
        qg = jax.lax.all_gather(q, "pod")          # int8 on the wire
        sg = jax.lax.all_gather(scale, "pod")      # fp32 scales (tiny)
        npod = qg.shape[0]
        total = jnp.zeros(g.shape, jnp.float32)
        for i in range(npod):
            total = total + _dequant_block(qg[i], sg[i], g.shape)
        return (total / npod).astype(g.dtype), new_err

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    return fn(grad, err)


def tree_compressed_crosspod_mean(grads, errs, mesh, block: int = 256):
    leaves_g, treedef = jax.tree_util.tree_flatten(grads)
    leaves_e = treedef.flatten_up_to(errs)
    outs = [compressed_crosspod_mean(g, e, mesh, block)
            for g, e in zip(leaves_g, leaves_e)]
    new_g = jax.tree_util.tree_unflatten(treedef, [o[0] for o in outs])
    new_e = jax.tree_util.tree_unflatten(treedef, [o[1] for o in outs])
    return new_g, new_e

"""GPipe-style pipeline parallelism over a named mesh axis.

The production 2-pod mesh uses the 'pod' axis for data parallelism (DCN
favors overlappable gradient all-reduce over critical-path activations —
DESIGN.md §4), so PP is an *optional* layout: stages mapped onto a mesh
axis, microbatches streamed through with `lax.ppermute`, bubbles handled by
masking.  Backward works by plain autodiff through the schedule (ppermute
transposes to the reverse permute), so `jax.grad` of a pipelined loss is
pipeline-parallel training with no extra machinery.

Schedule: classic GPipe fill-drain — T = M + S - 1 ticks for M microbatches
over S stages; bubble fraction (S-1)/T.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def gpipe_apply(stage_fn, stage_params, x, *, mesh, axis: str = "stage",
                microbatches: int = 4):
    """Apply ``stage_fn`` through S pipeline stages.

    stage_fn: (params_one_stage, h [mb, ...]) -> h [mb, ...] (same shape)
    stage_params: pytree with leading dim S (sharded over ``axis``)
    x: [B, ...] with B % microbatches == 0
    Returns stage_S-1(...stage_0(x)) == a sequential scan over stages.
    """
    nstages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    b = x.shape[0]
    assert b % microbatches == 0
    mbs = x.reshape((microbatches, b // microbatches) + x.shape[1:])
    t_total = microbatches + nstages - 1
    perm = [(i, (i + 1) % nstages) for i in range(nstages)]

    def spmd(params_stage, mb_stream):
        params_local = jax.tree_util.tree_map(lambda a: a[0], params_stage)
        stage = jax.lax.axis_index(axis)

        def tick(carry, t):
            buf, outs = carry
            # stage 0 ingests microbatch t while t < M; later stages take
            # the handed-over activation
            m_idx = jnp.clip(t, 0, microbatches - 1)
            mb_t = jax.lax.dynamic_index_in_dim(mb_stream, m_idx, 0,
                                                keepdims=False)
            inp = jnp.where(stage == 0, mb_t, buf)
            out = stage_fn(params_local, inp)
            # the last stage emits microbatch t-(S-1) when it is valid
            w_idx = jnp.clip(t - (nstages - 1), 0, microbatches - 1)
            valid = (t >= nstages - 1) & (stage == nstages - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, w_idx, 0,
                                               keepdims=False)
            upd = jnp.where(valid, out, cur)
            outs = jax.lax.dynamic_update_index_in_dim(outs, upd, w_idx, 0)
            buf_next = jax.lax.ppermute(out, axis, perm)
            return (buf_next, outs), None

        buf0 = jnp.zeros_like(mb_stream[0])
        outs0 = jnp.zeros_like(mb_stream)
        (buf, outs), _ = jax.lax.scan(
            tick, (buf0, outs0), jnp.arange(t_total, dtype=jnp.int32))
        # only the last stage holds real outputs; broadcast to all stages
        mask = (stage == nstages - 1).astype(outs.dtype)
        return jax.lax.psum(outs * mask, axis)

    in_specs = (jax.tree_util.tree_map(lambda _: P(axis), stage_params),
                P())
    fn = jax.shard_map(spmd, mesh=mesh, in_specs=in_specs, out_specs=P(),
                       check_vma=False)
    outs = fn(stage_params, mbs)
    return outs.reshape((b,) + x.shape[1:])


def bubble_fraction(num_stages: int, microbatches: int) -> float:
    """GPipe idle fraction: (S-1)/(M+S-1)."""
    return (num_stages - 1) / (microbatches + num_stages - 1)

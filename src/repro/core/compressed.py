"""Hardware-compressed representation of slided 2:4 windows (paper §4.3).

cuSPARSELt stores a 2:4 operand as the two non-zero values per window plus
2-bit position metadata.  We keep the same information — one value and one
in-window position per window slot — in a *slot-planar* layout that the
TPU kernel can decode with elementwise operations only:

* ``values``  [..., P, G, out] — plane ``p = j*M + t`` holds slot ``t`` of
  window ``j`` for every (group, output row); pad = 0
* ``indices`` [..., P, G, out] — int8 in-window positions (0..N-1)

with ``P = w*M`` slots per L-group and ``G = K/L`` groups.  Inside a
scanned stack of units the operand may carry the whole ``[U, P, G, out]``
stacks plus a traced unit index ``layer``: the kernel then reads that
unit's tiles in place, and :meth:`CompressedSlided.unstacked` is the one
unit's operand for every other consumer.  Each plane is a
2-D ``[G, out]`` array, so decompression is a compare-and-select per plane
(``kernels.slide_matmul``) rather than a reshape of the lane dimension, and
``out`` is the minor dim, which is what the TPU's default layout keeps
row-major (a minor dim of K/L, e.g. 480, would make XLA pick a transposed
layout and copy the operand before every kernel call).

For the (2N-2):2N family the compressed value count is exactly the source
non-zero budget (``P*G == dec.compressed_len(K) == density*K``): the slide
expansion incurs **no storage overhead** (§4.3).  ``pack_meta``/
``unpack_meta`` bit-pack the 2-bit indices 16-per-int32 for HBM-bandwidth
accounting.

Under the 'w4' precision recipe (``repro.core.precision``) the int4 values
are additionally nibble-packed two per byte (``packed=True``): byte plane
``q`` holds plane ``2q`` in its low nibble and plane ``2q+1`` in its high
nibble (the two slots of window ``q``), so ``values`` is ``[..., P/2, G,
out]`` and slices along G or ``out`` stay congruent with the unpacked
planes — ``split_k``/``split_out`` shard packed operands exactly like
unpacked ones.  ``indices`` are never nibble-packed.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from .patterns import SlideDecomposition
from . import packer


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class CompressedSlided:
    """Pytree carrying the compressed operand + static decomposition info."""

    values: jax.Array   # [..., P, G, out] ([..., P/2, G, out] bytes if packed)
    indices: jax.Array  # [..., P, G, out] int8 in-window positions
    k: int              # original contraction length
    z: int
    l: int
    m: int
    n: int
    packed: bool = False  # True: values nibble-packed (int4 'w4' recipe)
    # set: values/indices are [U, ...] stacks and this is the unit's index
    layer: jax.Array | None = None

    def tree_flatten(self):
        return ((self.values, self.indices, self.layer),
                (self.k, self.z, self.l, self.m, self.n, self.packed))

    @classmethod
    def tree_unflatten(cls, aux, children):
        values, indices, layer = children
        return cls(values, indices, *aux, layer=layer)

    def unstacked(self) -> "CompressedSlided":
        """The operand of unit ``layer`` alone (a copy of its slice of the
        stacks); ``self`` when the operand is not a stack."""
        if self.layer is None:
            return self
        return dataclasses.replace(self, values=self.values[self.layer],
                                   indices=self.indices[self.layer],
                                   layer=None)

    @property
    def decomposition(self) -> SlideDecomposition:
        from .patterns import Pattern, HardwarePattern

        return SlideDecomposition(Pattern(self.z, self.l), HardwarePattern(self.m, self.n))

    @property
    def nbytes_values(self) -> int:
        return int(np.prod(self.values.shape)) * self.values.dtype.itemsize

    @property
    def nbytes_meta_packed(self) -> int:
        # 2-bit indices, 16 per int32 word
        return (int(np.prod(self.indices.shape)) + 15) // 16 * 4

    @property
    def slots(self) -> int:
        """Per-row compressed slot count P*G (pack-agnostic)."""
        return self.indices.shape[-3] * self.indices.shape[-2]

    @property
    def out_features(self) -> int:
        return self.indices.shape[-1]

    def values_unpacked(self) -> jax.Array:
        """Per-slot int8 values regardless of nibble packing."""
        if not self.packed:
            return self.values
        return unpack_planes(self.values)


def pack_planes(v: jax.Array) -> jax.Array:
    """Nibble-pack value planes pairwise: [..., P, G, out] -> [..., P/2, G,
    out], plane ``2q`` in the low nibble of byte plane ``q``."""
    return jnp.moveaxis(packer.pack_nibbles(jnp.moveaxis(v, -3, -1)), -1, -3)


def unpack_planes(p: jax.Array) -> jax.Array:
    """Inverse of :func:`pack_planes` (sign-extending int4 -> int8)."""
    return jnp.moveaxis(packer.unpack_nibbles(jnp.moveaxis(p, -3, -1)), -1, -3)


def compress(w_slided: jax.Array, dec: SlideDecomposition,
             pack_values: bool = False) -> CompressedSlided:
    """Pack a slided (hardware-compliant) tensor into values + metadata.

    ``pack_values=True`` (the 'w4' recipe) additionally nibble-packs the
    int8-ranged values two per byte; window structure is computed on the
    per-slot values first, so packing is pure relayout.
    """
    n, m, nw = dec.hw.n, dec.hw.m, dec.num_windows
    g = w_slided.shape[-1] // (nw * n)
    # [..., G, w*n, out]: every window position is a [G, out] plane, so the
    # selection below is elementwise over planes (a sort over a minor dim
    # of 4 would be padded to 128 lanes on a TPU: 9 GB for one FFN weight)
    wt = jnp.swapaxes(w_slided, -1, -2)
    wt = wt.reshape(wt.shape[:-2] + (g, nw * n) + wt.shape[-1:])
    vals, idx = [], []
    for j in range(nw):
        x = [wt[..., j * n + i, :] for i in range(n)]
        nz = [xi != 0 for xi in x]
        # slot of position i: non-zeros first in position order, then the
        # zeros in position order (pad slots point at zero positions)
        nnz = sum(v.astype(jnp.int32) for v in nz)
        rank, nz_before = [], jnp.zeros_like(nnz)
        for i in range(n):
            rank.append(jnp.where(nz[i], nz_before, nnz + i - nz_before))
            nz_before = nz_before + nz[i].astype(jnp.int32)
        for t in range(m):  # exactly one position has rank t
            v, p = x[0], jnp.zeros(x[0].shape, jnp.int8)
            for i in range(1, n):
                v = jnp.where(rank[i] == t, x[i], v)
                p = jnp.where(rank[i] == t, jnp.int8(i), p)
            vals.append(v)
            idx.append(p)
    vals = jnp.stack(vals, axis=-3)  # [..., w*m, G, out]
    if pack_values:
        vals = pack_planes(vals)
    return CompressedSlided(
        values=vals, indices=jnp.stack(idx, axis=-3),
        k=g * dec.source.l, z=dec.source.z, l=dec.source.l, m=m, n=n,
        packed=pack_values,
    )


def _window_view(c: CompressedSlided):
    """[..., out, G, w, M] values and indices (the row-major window view)."""
    dec = c.decomposition
    nw, m = dec.num_windows, c.m

    def rows(a):  # [..., P, G, out] -> [..., out, G, w, m]
        a = jnp.moveaxis(a, (-3, -2, -1), (-1, -2, -3))
        return a.reshape(a.shape[:-1] + (nw, m))

    g = c.indices.shape[-2]
    return rows(c.values_unpacked()), rows(c.indices), dec, g, nw


def decompress_slided(c: CompressedSlided) -> jax.Array:
    """Inverse of ``compress``: [..., out, gamma*K] slided dense windows."""
    vals, idx, dec, g, nw = _window_view(c)
    onehot = jax.nn.one_hot(idx.astype(jnp.int32), dec.hw.n, dtype=vals.dtype)
    wv = jnp.einsum("...m,...mn->...n", vals, onehot)
    lead = vals.shape[:-3]
    return wv.reshape(lead + (g * nw * dec.hw.n,))


def decompress_original(c: CompressedSlided) -> jax.Array:
    """Scatter compressed values straight back to the original K layout.

    == packer.unslide(decompress_slided(c)); exact because Algorithm 2 assigns
    each source non-zero to exactly one window slot.  This is the weight path
    of the TPU-optimized matmul (DESIGN.md §2).
    """
    vals, idx, dec, g, nw = _window_view(c)
    # in-group source position: s*j + idx  (j = window index)
    j = jnp.arange(nw, dtype=jnp.int32)[:, None]
    pos = dec.hw.stride * j + idx.astype(jnp.int32)  # [..., g, w, m]
    onehot = jax.nn.one_hot(pos, c.l, dtype=vals.dtype)
    grp = jnp.einsum("...wm,...wml->...l", vals, onehot)  # [..., g, l]
    lead = vals.shape[:-3]
    return grp.reshape(lead + (g * c.l,))


def split_out(c: CompressedSlided, shards: int) -> list[CompressedSlided]:
    """Column-parallel sharding: slice the output dim into ``shards`` equal
    contiguous blocks (tensor-parallel serving, DESIGN.md §9).

    Each shard is a self-contained :class:`CompressedSlided` over the full
    contraction length ``k``; ``decompress_*`` of shard ``i`` equals rows
    ``[i*out/shards, (i+1)*out/shards)`` of the unsharded decompression.
    Requires ``out % shards == 0``.
    """
    out = c.out_features
    if out % shards:
        raise ValueError(f"cannot split out dim of shape "
                         f"{c.values.shape} into {shards} shards")
    step = out // shards
    return [CompressedSlided(
        c.values[..., i * step:(i + 1) * step],
        c.indices[..., i * step:(i + 1) * step],
        c.k, c.z, c.l, c.m, c.n, c.packed, c.layer) for i in range(shards)]


def split_k(c: CompressedSlided, shards: int) -> list[CompressedSlided]:
    """Row-parallel sharding: slice the *contraction* dim into ``shards``
    contiguous blocks of whole L-groups (tensor-parallel serving,
    DESIGN.md §9).

    Every plane is indexed by group on its G axis, so a contiguous slice
    of G is exactly a contiguous slice of K: no window group ever
    straddles a shard, packed or not.  Shard ``i`` satisfies
    ``decompress_original(shard_i) ==
    decompress_original(c)[..., i*k/shards:(i+1)*k/shards]`` and carries
    ``k/shards`` as its local contraction length (the kernels recover K
    from shapes, so local shards drop straight into ``linear.apply``).
    Requires ``(k/shards) % L == 0``.
    """
    if c.k % shards or (c.k // shards) % c.l:
        raise ValueError(
            f"cannot split k={c.k} into {shards} shards of whole L={c.l} "
            f"groups (pattern group would straddle a shard boundary)")
    g_step = (c.k // shards) // c.l          # groups per shard
    return [CompressedSlided(
        c.values[..., i * g_step:(i + 1) * g_step, :],
        c.indices[..., i * g_step:(i + 1) * g_step, :],
        c.k // shards, c.z, c.l, c.m, c.n, c.packed, c.layer)
        for i in range(shards)]


def pack_meta(indices: jax.Array) -> jax.Array:
    """Bit-pack int8 2-bit indices into int32 words (16 per word)."""
    flat = indices.reshape(indices.shape[:-1] + (-1,))
    n = flat.shape[-1]
    pad = (-n) % 16
    if pad:
        flat = jnp.pad(flat, [(0, 0)] * (flat.ndim - 1) + [(0, pad)])
    grp = flat.reshape(flat.shape[:-1] + ((n + pad) // 16, 16)).astype(jnp.int32)
    shifts = (2 * jnp.arange(16, dtype=jnp.int32))
    return jnp.sum(grp << shifts, axis=-1, dtype=jnp.int32)


def unpack_meta(words: jax.Array, count: int) -> jax.Array:
    """Inverse of ``pack_meta``; returns int8 indices of length ``count``."""
    shifts = (2 * jnp.arange(16, dtype=jnp.int32))
    idx = (words[..., None] >> shifts) & 3
    idx = idx.reshape(words.shape[:-1] + (-1,))[..., :count]
    return idx.astype(jnp.int8)

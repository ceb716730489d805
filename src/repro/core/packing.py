"""Packed-buffer pytree transport: flatten a parameter pytree into ONE
contiguous ``(..., D)`` f32 buffer so the whole OTA uplink is a single
kernel chain per round instead of one per leaf.

The paper (Alg. 1) and the OTA literature (arXiv:1907.09769, 2508.17697)
treat the uplink as one flat d-dimensional analog vector — every worker's
full update occupies one analog channel use.  A :class:`PackSpec` is the
static (trace-time) description of that vector: per-leaf offsets/sizes into
the packed buffer, plus the shapes/dtypes needed to unpack the received
global model bit-compatibly.

Built once per model (shapes are static under jit, so "once" means once per
trace); ``pack``/``unpack`` lower to reshape+concatenate / slice+reshape —
pure layout ops XLA fuses into the neighbouring kernels.

Leaves may carry leading batch dims (the worker axis ``W``): a leaf of shape
``lead + spec.shapes[i]`` packs into ``lead + (sizes[i],)``; all leaves of
one ``pack`` call must share ``lead``.  Complex trees (duals λ, fading h)
pack planewise via :func:`pack_cplx` / :func:`unpack_cplx`.

Shard-local packing (:class:`ShardPackSpec`) is the model-parallel variant:
instead of one global concatenate (which would force GSPMD to reshard every
model-sharded leaf into the replicated packed layout each round), every
device packs only the leaf *shards* resident on it, and the global packed
buffer is simply the concatenation of the per-shard packs — sharded over
the mesh ``model`` axis, so no cross-shard data movement ever happens at
pack/unpack time.  Per-shard offsets compose into one global index space
(:func:`shard_perm`): scattering each shard's local pack to its canonical
offsets reconstructs the global :func:`pack` exactly.
"""
from __future__ import annotations

from typing import Any, List, NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core.cplx import Complex
from repro.obs.profiling import layer

Array = jax.Array
PyTree = Any


def _is_cplx(x) -> bool:
    return isinstance(x, Complex)


class PackSpec(NamedTuple):
    """Static layout of a pytree inside a flat packed buffer."""

    treedef: Any                          # pytree structure (Complex = leaf)
    shapes: Tuple[Tuple[int, ...], ...]   # per-leaf element shape (no batch dims)
    dtypes: Tuple[Any, ...]               # per-leaf dtype (for bit-compatible unpack)
    offsets: Tuple[int, ...]              # start of each leaf in the packed axis
    sizes: Tuple[int, ...]                # elements per leaf
    d: int                                # total packed length Σ sizes

    @property
    def n_leaves(self) -> int:
        return len(self.shapes)


def _leaf_meta(leaf, batch_dims: int):
    if isinstance(leaf, Complex):
        shape, dtype = leaf.re.shape, leaf.re.dtype
    else:
        shape, dtype = leaf.shape, leaf.dtype
    eshape = tuple(shape[batch_dims:])
    size = 1
    for s in eshape:
        size *= s
    return eshape, dtype, size


def build_packspec(tree: PyTree, batch_dims: int = 0) -> PackSpec:
    """Layout of ``tree``'s leaves (skipping ``batch_dims`` leading axes,
    e.g. 1 for worker-major ``(W, ...)`` trees) inside one packed vector."""
    leaves, treedef = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)
    shapes, dtypes, offsets, sizes = [], [], [], []
    off = 0
    for leaf in leaves:
        eshape, dtype, size = _leaf_meta(leaf, batch_dims)
        shapes.append(eshape)
        dtypes.append(dtype)
        offsets.append(off)
        sizes.append(size)
        off += size
    return PackSpec(treedef=treedef, shapes=tuple(shapes),
                    dtypes=tuple(dtypes), offsets=tuple(offsets),
                    sizes=tuple(sizes), d=off)


def _lead(spec: PackSpec, leaf: Array, i: int) -> Tuple[int, ...]:
    nb = leaf.ndim - len(spec.shapes[i])
    if nb < 0 or tuple(leaf.shape[nb:]) != spec.shapes[i]:
        raise ValueError(
            f"leaf {i} shape {leaf.shape} does not end with spec shape "
            f"{spec.shapes[i]}")
    return tuple(leaf.shape[:nb])


def _dus_pack(flat: List[Array], offsets, d: int) -> Array:
    """Write per-leaf flats into a zeroed ``lead + (d,)`` buffer at their
    static offsets.  Values are bit-identical to the historical
    ``jnp.concatenate`` (every element written exactly once, f32 in/out),
    but the update-slice chain lowers without the single-threaded
    concatenate XLA:CPU schedules at packed LLM widths (~2x faster at
    D≈400k, ROADMAP item 1)."""
    lead = flat[0].shape[:-1]
    for i, f in enumerate(flat[1:], 1):
        if f.shape[:-1] != lead:
            raise ValueError(f"leaf {i} leading dims {f.shape[:-1]} != "
                             f"leaf 0 leading dims {lead}")
    buf = jnp.zeros(lead + (d,), jnp.float32)
    for f, off in zip(flat, offsets):
        buf = jax.lax.dynamic_update_slice_in_dim(buf, f, off, axis=-1)
    return buf


@layer("ota_pack")
def pack(spec: PackSpec, tree: PyTree) -> Array:
    """``tree`` -> ``lead + (spec.d,)`` f32 buffer (row-major per leaf)."""
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    if len(leaves) != spec.n_leaves:
        raise ValueError(f"tree has {len(leaves)} leaves, spec expects "
                         f"{spec.n_leaves}")
    flat = [l.astype(jnp.float32).reshape(_lead(spec, l, i) + (-1,))
            for i, l in enumerate(leaves)]
    return flat[0] if len(flat) == 1 else _dus_pack(flat, spec.offsets, spec.d)


@layer("ota_pack")
def unpack(spec: PackSpec, buf: Array, cast: bool = True) -> PyTree:
    """``lead + (spec.d,)`` buffer -> pytree; ``cast=True`` restores the
    recorded leaf dtypes, ``cast=False`` keeps the buffer dtype (the analog
    path's f32)."""
    if buf.shape[-1] != spec.d:
        raise ValueError(f"buffer last dim {buf.shape[-1]} != spec.d {spec.d}")
    lead = buf.shape[:-1]
    out = []
    for i in range(spec.n_leaves):
        piece = jax.lax.slice_in_dim(buf, spec.offsets[i],
                                     spec.offsets[i] + spec.sizes[i], axis=-1)
        piece = piece.reshape(lead + spec.shapes[i])
        out.append(piece.astype(spec.dtypes[i]) if cast else piece)
    return jax.tree_util.tree_unflatten(spec.treedef, out)


@layer("ota_pack")
def pack_cplx(spec: PackSpec, tree: PyTree) -> Complex:
    """Complex-leaf tree -> Complex of packed planes."""
    flats = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    re = jax.tree_util.tree_unflatten(spec.treedef, [c.re for c in flats])
    im = jax.tree_util.tree_unflatten(spec.treedef, [c.im for c in flats])
    return Complex(pack(spec, re), pack(spec, im))


@layer("ota_pack")
def unpack_cplx(spec: PackSpec, buf: Complex) -> PyTree:
    """Complex packed planes -> tree of Complex leaves (f32: duals/fading
    always live in f32, never the parameter dtype)."""
    re = unpack(spec, buf.re, cast=False)
    im = unpack(spec, buf.im, cast=False)
    re_l = jax.tree_util.tree_flatten(re)[0]
    im_l = jax.tree_util.tree_flatten(im)[0]
    return jax.tree_util.tree_unflatten(
        spec.treedef, [Complex(r, i) for r, i in zip(re_l, im_l)])


# ---------------------------------------------------------------------------
# shard-local packing (model-parallel / fsdp meshes)
# ---------------------------------------------------------------------------

class ShardPackSpec(NamedTuple):
    """Static layout of a pytree packed *per (fsdp, model) shard*.

    The shard grid is 2D: ``n_fsdp x n_model`` shards, flattened fsdp-major —
    shard ``j = jf * n_model + jm`` owns the contiguous slice
    ``[j*d_local, (j+1)*d_local)`` of the global ``d_pad``-wide packed axis,
    so a ``(W, d_pad)`` plane sharded ``P(data, ("fsdp", "model"))`` keeps
    each shard's slice exactly resident.  ``n_fsdp == 1`` degenerates
    BITWISE to the historical 1D model-sharded layout (the pre-2D contract
    every existing parity test pins).

    Each leaf falls in one of four ownership classes, by which of its
    element dims the mesh shards:

    * **A** — ``shard_dims[i]`` AND ``fsdp_dims[i]`` both set: the resident
      ``1/(n_model*n_fsdp)`` block packs at ``local_offsets[i]``;
    * **B** — model dim only: per-model-shard local flats concatenate (leaf
      order) into a *B segment* of ``b_size`` elements, zero-padded to
      ``n_fsdp * b_chunk`` and split evenly over the fsdp shards;
    * **C** — fsdp dim only: symmetric — a per-fsdp-shard segment of
      ``c_size`` elements split evenly over the model shards;
    * **D** — replicated on both: ONE global segment of ``rep_size``
      elements split evenly over all ``n_shards`` shards.

    Per-shard layout: ``[A blocks | B chunk | C chunk | D chunk]``.  Every
    element is owned by exactly ONE shard; :func:`shard_perm` maps each
    shard-packed position to its canonical :class:`PackSpec` index and
    ``Σ_j scatter(pack_shard_local(j), perm_j) == pack(global)`` is pinned
    in ``tests/test_packing.py``.
    """

    spec: PackSpec                          # canonical global layout
    n_model: int                            # model-axis shards
    n_fsdp: int                             # fsdp-axis shards
    shard_dims: Tuple[Optional[int], ...]   # per-leaf model-sharded elem dim
    fsdp_dims: Tuple[Optional[int], ...]    # per-leaf fsdp-sharded elem dim
    local_offsets: Tuple[Optional[int], ...]  # class-A leaves: offset in shard
    a_local: int                            # elements of class-A leaves/shard
    b_leaves: Tuple[int, ...]               # class-B (model-only) leaf idxs
    b_offsets: Tuple[int, ...]              # offsets in the B segment
    b_size: int                             # B segment width per model shard
    b_chunk: int                            # ceil(b_size / n_fsdp)
    c_leaves: Tuple[int, ...]               # class-C (fsdp-only) leaf idxs
    c_offsets: Tuple[int, ...]              # offsets in the C segment
    c_size: int                             # C segment width per fsdp shard
    c_chunk: int                            # ceil(c_size / n_model)
    rep_leaves: Tuple[int, ...]             # class-D (replicated) leaf idxs
    rep_offsets: Tuple[int, ...]            # their offsets in the D segment
    rep_size: int                           # R: real replicated elements
    rep_chunk: int                          # ceil(R / n_shards)

    @property
    def n_shards(self) -> int:
        return self.n_model * self.n_fsdp

    @property
    def b_start(self) -> int:
        return self.a_local

    @property
    def c_start(self) -> int:
        return self.a_local + self.b_chunk

    @property
    def sharded_local(self) -> int:
        """Start of the D (replicated-segment) chunk — also the number of
        non-replicated elements per shard (the historical 1D field)."""
        return self.a_local + self.b_chunk + self.c_chunk

    @property
    def d_local(self) -> int:
        return self.sharded_local + self.rep_chunk

    @property
    def d_pad(self) -> int:
        return self.n_shards * self.d_local

    @property
    def b_pad(self) -> int:
        return self.n_fsdp * self.b_chunk

    @property
    def c_pad(self) -> int:
        return self.n_model * self.c_chunk

    @property
    def rep_pad(self) -> int:
        return self.n_shards * self.rep_chunk

    @property
    def has_padding(self) -> bool:
        return (self.b_pad != self.b_size or self.c_pad != self.c_size
                or self.rep_pad != self.rep_size)


def build_shard_packspec(tree: PyTree, shard_dims: Sequence[Optional[int]],
                         n_shards: int, batch_dims: int = 0, *,
                         fsdp_dims: Optional[Sequence[Optional[int]]] = None,
                         n_fsdp: int = 1) -> ShardPackSpec:
    """Shard-local layout of ``tree`` given each leaf's model-sharded
    element dim (``None`` = replicated over the model axis) and, for 2D
    (data x fsdp x model) meshes, its fsdp-sharded element dim.

    ``shard_dims``/``fsdp_dims`` align with the canonical flatten order
    (Complex = leaf); ``n_shards`` is the MODEL-axis shard count (historical
    name — the total shard count is ``n_shards * n_fsdp``).  Sharded dims
    must divide their axis size (GSPMD only shards them when they do —
    ``launch/shardings.param_pspec``).  ``n_fsdp == 1`` coerces
    ``fsdp_dims`` to all-``None`` so the 1D layout stays bitwise identical.
    """
    spec = build_packspec(tree, batch_dims=batch_dims)
    n_model = n_shards
    if len(shard_dims) != spec.n_leaves:
        raise ValueError(f"shard_dims has {len(shard_dims)} entries, tree "
                         f"has {spec.n_leaves} leaves")
    if fsdp_dims is None or n_fsdp == 1:
        fsdp_dims = (None,) * spec.n_leaves
    if len(fsdp_dims) != spec.n_leaves:
        raise ValueError(f"fsdp_dims has {len(fsdp_dims)} entries, tree "
                         f"has {spec.n_leaves} leaves")
    local_offsets: List[Optional[int]] = []
    b_leaves, b_offsets = [], []
    c_leaves, c_offsets = [], []
    rep_leaves, rep_offsets = [], []
    a_off = b_off = c_off = r_off = 0

    def _check(i, dim, n, axis_name):
        eshape = spec.shapes[i]
        if not (0 <= dim < len(eshape)):
            raise ValueError(f"leaf {i}: {axis_name} dim {dim} out of range "
                             f"for shape {eshape}")
        if eshape[dim] % n:
            raise ValueError(f"leaf {i}: dim {dim} of {eshape} not "
                             f"divisible by {n} {axis_name} shards")

    for i, (md, fd) in enumerate(zip(shard_dims, fsdp_dims)):
        if md is not None:
            _check(i, md, n_model, "model")
        if fd is not None:
            _check(i, fd, n_fsdp, "fsdp")
        if md is not None and fd is not None:
            if md == fd:
                raise ValueError(f"leaf {i}: model and fsdp shard the same "
                                 f"dim {md}")
            local_offsets.append(a_off)
            a_off += spec.sizes[i] // (n_model * n_fsdp)
        elif md is not None:
            local_offsets.append(None)
            b_leaves.append(i)
            b_offsets.append(b_off)
            b_off += spec.sizes[i] // n_model
        elif fd is not None:
            local_offsets.append(None)
            c_leaves.append(i)
            c_offsets.append(c_off)
            c_off += spec.sizes[i] // n_fsdp
        else:
            local_offsets.append(None)
            rep_leaves.append(i)
            rep_offsets.append(r_off)
            r_off += spec.sizes[i]
    b_chunk = -(-b_off // n_fsdp) if b_off else 0
    c_chunk = -(-c_off // n_model) if c_off else 0
    rep_chunk = -(-r_off // (n_model * n_fsdp)) if r_off else 0
    return ShardPackSpec(spec=spec, n_model=n_model, n_fsdp=n_fsdp,
                         shard_dims=tuple(shard_dims),
                         fsdp_dims=tuple(fsdp_dims),
                         local_offsets=tuple(local_offsets), a_local=a_off,
                         b_leaves=tuple(b_leaves), b_offsets=tuple(b_offsets),
                         b_size=b_off, b_chunk=b_chunk,
                         c_leaves=tuple(c_leaves), c_offsets=tuple(c_offsets),
                         c_size=c_off, c_chunk=c_chunk,
                         rep_leaves=tuple(rep_leaves),
                         rep_offsets=tuple(rep_offsets),
                         rep_size=r_off, rep_chunk=rep_chunk)


def _resident_eshape(sspec: ShardPackSpec, i: int) -> Tuple[int, ...]:
    """Element shape of leaf ``i``'s per-shard resident slice (model AND
    fsdp dims divided where sharded)."""
    eshape = list(sspec.spec.shapes[i])
    if sspec.shard_dims[i] is not None:
        eshape[sspec.shard_dims[i]] //= sspec.n_model
    if sspec.fsdp_dims[i] is not None:
        eshape[sspec.fsdp_dims[i]] //= sspec.n_fsdp
    return tuple(eshape)


def _flat(leaf: Array, eshape: Tuple[int, ...], i: int) -> Array:
    nb = leaf.ndim - len(eshape)
    if nb < 0 or tuple(leaf.shape[nb:]) != eshape:
        raise ValueError(f"leaf {i} shape {leaf.shape} does not end with "
                         f"expected shard-local shape {eshape}")
    return leaf.astype(jnp.float32).reshape(leaf.shape[:nb] + (-1,))


def _pad_seg(seg: Array, pad_to: int) -> Array:
    pad = pad_to - seg.shape[-1]
    if pad:
        seg = jnp.pad(seg, [(0, 0)] * (seg.ndim - 1) + [(0, pad)])
    return seg


def _seg_resident(sspec: ShardPackSpec, leaves, idxs, pad_to: int
                  ) -> Optional[Array]:
    """Zero-padded segment from RESIDENT leaf slices (shard-local context:
    each listed leaf already carries its per-shard shape)."""
    if not idxs:
        return None
    flats = [_flat(leaves[i], _resident_eshape(sspec, i), i) for i in idxs]
    seg = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=-1)
    return _pad_seg(seg, pad_to)


def rep_segment(sspec: ShardPackSpec, tree: PyTree) -> Optional[Array]:
    """Concatenate the fully-replicated (class-D) leaves into the
    zero-padded segment ``lead + (rep_pad,)`` (None when no leaf is
    replicated on every shard axis)."""
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    return _seg_resident(sspec, leaves, sspec.rep_leaves, sspec.rep_pad)


def b_segment(sspec: ShardPackSpec, tree: PyTree) -> Optional[Array]:
    """One model shard's B segment from its RESIDENT class-B slices."""
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    return _seg_resident(sspec, leaves, sspec.b_leaves, sspec.b_pad)


def c_segment(sspec: ShardPackSpec, tree: PyTree) -> Optional[Array]:
    """One fsdp shard's C segment from its RESIDENT class-C slices."""
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    return _seg_resident(sspec, leaves, sspec.c_leaves, sspec.c_pad)


def _chunk_at(seg: Array, idx, chunk: int) -> Array:
    return jax.lax.dynamic_slice_in_dim(seg, idx * chunk, chunk, axis=-1)


def rep_chunk_at(sspec: ShardPackSpec, seg: Array, shard_idx) -> Array:
    """Shard ``shard_idx``'s slice of the replicated segment (traced idx OK)."""
    return _chunk_at(seg, shard_idx, sspec.rep_chunk)


def _split_idx(sspec: ShardPackSpec, shard_idx):
    """Flat shard index -> (model_idx, fsdp_idx); fsdp-major, traced OK."""
    return shard_idx % sspec.n_model, shard_idx // sspec.n_model


@layer("ota_pack")
def pack_shard_local(sspec: ShardPackSpec, tree: PyTree, shard_idx) -> Array:
    """Pack ONE shard's resident data: every leaf arrives as the slice its
    PartitionSpec makes resident (class A sliced on both dims, B on the
    model dim, C on the fsdp dim, D whole — shard ``shard_idx`` keeps only
    its chunk of each segment).  This is what each device runs inside
    ``shard_map`` — no cross-device data ever moves.

    Returns ``lead + (d_local,)`` f32.
    """
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    if len(leaves) != sspec.spec.n_leaves:
        raise ValueError(f"tree has {len(leaves)} leaves, spec expects "
                         f"{sspec.spec.n_leaves}")
    jm, jf = _split_idx(sspec, shard_idx)
    parts, offsets = [], []
    for i, off in enumerate(sspec.local_offsets):
        if off is not None:
            parts.append(_flat(leaves[i], _resident_eshape(sspec, i), i))
            offsets.append(off)
    seg = _seg_resident(sspec, leaves, sspec.b_leaves, sspec.b_pad)
    if seg is not None:
        parts.append(_chunk_at(seg, jf, sspec.b_chunk))
        offsets.append(sspec.b_start)
    seg = _seg_resident(sspec, leaves, sspec.c_leaves, sspec.c_pad)
    if seg is not None:
        parts.append(_chunk_at(seg, jm, sspec.c_chunk))
        offsets.append(sspec.c_start)
    seg = _seg_resident(sspec, leaves, sspec.rep_leaves, sspec.rep_pad)
    if seg is not None:
        parts.append(_chunk_at(seg, shard_idx, sspec.rep_chunk))
        offsets.append(sspec.sharded_local)
    return parts[0] if len(parts) == 1 else _dus_pack(parts, offsets,
                                                      sspec.d_local)


def _seg_unpack(sspec: ShardPackSpec, seg, idxs, offs, out, cast: bool):
    lead = seg.shape[:-1]
    for i, off in zip(idxs, offs):
        piece = jax.lax.slice_in_dim(seg, off, off + sspec.spec.sizes[i],
                                     axis=-1)
        out[i] = piece.reshape(lead + sspec.spec.shapes[i])


@layer("ota_pack")
def unpack_shard_local(sspec: ShardPackSpec, buf: Array,
                       rep_seg: Optional[Array] = None,
                       cast: bool = False, *,
                       b_seg: Optional[Array] = None,
                       c_seg: Optional[Array] = None) -> PyTree:
    """One shard's ``lead + (d_local,)`` buffer -> local tree.

    Class-A leaves come back as their resident 2D blocks straight from the
    buffer; class B/C/D leaves are rebuilt from the FULL (cross-shard)
    ``b_seg``/``c_seg``/``rep_seg`` segments, which the ``shard_map`` caller
    reassembles with one small ``psum`` each of the scattered chunks
    (:func:`scatter_b_chunk` over the fsdp axis, :func:`scatter_c_chunk`
    over the model axis, :func:`scatter_rep_chunk` over both).  A segment
    may be omitted only when no leaf lives in it.  On 1D specs
    (``n_fsdp == 1``) ``b_seg`` IS each shard's ``[0, sharded_local)``
    prefix, so the caller passes ``shard_b_chunk`` back without any psum.
    """
    if buf.shape[-1] != sspec.d_local:
        raise ValueError(f"buffer last dim {buf.shape[-1]} != d_local "
                         f"{sspec.d_local}")
    if b_seg is None and sspec.b_leaves and sspec.n_fsdp == 1:
        b_seg = shard_b_chunk(sspec, buf)      # chunk == full segment in 1D
    if c_seg is None and sspec.c_leaves and sspec.n_model == 1:
        c_seg = shard_c_chunk(sspec, buf)
    for name, seg, idxs in (("rep_seg", rep_seg, sspec.rep_leaves),
                            ("b_seg", b_seg, sspec.b_leaves),
                            ("c_seg", c_seg, sspec.c_leaves)):
        if idxs and seg is None:
            raise ValueError(f"{name} required: tree has leaves in that "
                             "ownership class")
    lead = buf.shape[:-1]
    out: List[Optional[Array]] = [None] * sspec.spec.n_leaves
    for i, off in enumerate(sspec.local_offsets):
        if off is None:
            continue
        size = sspec.spec.sizes[i] // sspec.n_shards
        piece = jax.lax.slice_in_dim(buf, off, off + size, axis=-1)
        out[i] = piece.reshape(lead + _resident_eshape(sspec, i))
    if sspec.b_leaves:
        lead_b = b_seg.shape[:-1]
        for i, off in zip(sspec.b_leaves, sspec.b_offsets):
            size = sspec.spec.sizes[i] // sspec.n_model
            piece = jax.lax.slice_in_dim(b_seg, off, off + size, axis=-1)
            out[i] = piece.reshape(lead_b + _resident_eshape(sspec, i))
    if sspec.c_leaves:
        lead_c = c_seg.shape[:-1]
        for i, off in zip(sspec.c_leaves, sspec.c_offsets):
            size = sspec.spec.sizes[i] // sspec.n_fsdp
            piece = jax.lax.slice_in_dim(c_seg, off, off + size, axis=-1)
            out[i] = piece.reshape(lead_c + _resident_eshape(sspec, i))
    if sspec.rep_leaves:
        _seg_unpack(sspec, rep_seg, sspec.rep_leaves, sspec.rep_offsets,
                    out, cast)
    if cast:
        out = [p.astype(sspec.spec.dtypes[i]) for i, p in enumerate(out)]
    return jax.tree_util.tree_unflatten(sspec.spec.treedef, out)


def shard_rep_chunk(sspec: ShardPackSpec, buf: Array) -> Optional[Array]:
    """The D-segment tail of one shard's local buffer (None when no leaf is
    fully replicated)."""
    if not sspec.rep_leaves:
        return None
    return jax.lax.slice_in_dim(buf, sspec.sharded_local, sspec.d_local,
                                axis=-1)


def shard_b_chunk(sspec: ShardPackSpec, buf: Array) -> Optional[Array]:
    if not sspec.b_leaves:
        return None
    return jax.lax.slice_in_dim(buf, sspec.b_start,
                                sspec.b_start + sspec.b_chunk, axis=-1)


def shard_c_chunk(sspec: ShardPackSpec, buf: Array) -> Optional[Array]:
    if not sspec.c_leaves:
        return None
    return jax.lax.slice_in_dim(buf, sspec.c_start,
                                sspec.c_start + sspec.c_chunk, axis=-1)


def _scatter_chunk(chunk: Array, idx, width: int, pad: int) -> Array:
    lead = chunk.shape[:-1]
    seg = jnp.zeros(lead + (pad,), chunk.dtype)
    start = (0,) * len(lead) + (idx * width,)
    return jax.lax.dynamic_update_slice(seg, chunk, start)


def scatter_rep_chunk(sspec: ShardPackSpec, chunk: Array, shard_idx) -> Array:
    """Place shard ``shard_idx``'s D-segment chunk at its offset in a zeroed
    ``lead + (rep_pad,)`` segment — summing these over ALL shard axes (one
    ``psum``) rebuilds the full replicated segment."""
    return _scatter_chunk(chunk, shard_idx, sspec.rep_chunk, sspec.rep_pad)


def scatter_b_chunk(sspec: ShardPackSpec, chunk: Array, fsdp_idx) -> Array:
    """Place fsdp shard ``fsdp_idx``'s B chunk in a zeroed ``(b_pad,)``
    segment — a ``psum`` over the fsdp axis rebuilds one model shard's full
    B segment (identity when ``n_fsdp == 1``)."""
    return _scatter_chunk(chunk, fsdp_idx, sspec.b_chunk, sspec.b_pad)


def scatter_c_chunk(sspec: ShardPackSpec, chunk: Array, model_idx) -> Array:
    """Place model shard ``model_idx``'s C chunk in a zeroed ``(c_pad,)``
    segment — a ``psum`` over the model axis rebuilds one fsdp shard's full
    C segment."""
    return _scatter_chunk(chunk, model_idx, sspec.c_chunk, sspec.c_pad)


def shard_valid_mask(sspec: ShardPackSpec, shard_idx) -> Array:
    """(d_local,) bool: True where this shard's position holds a real
    element, False on the zero-padding tails of the B/C/D segments.
    Padding must never re-enter the air (a dual update would otherwise turn
    Θ garbage at padded positions into non-zero λ there)."""
    jm, jf = _split_idx(sspec, shard_idx)
    cols = jnp.arange(sspec.d_local)
    valid = cols < sspec.a_local
    in_b = (cols >= sspec.b_start) & (cols < sspec.c_start)
    valid |= in_b & (jf * sspec.b_chunk + (cols - sspec.b_start)
                     < sspec.b_size)
    in_c = (cols >= sspec.c_start) & (cols < sspec.sharded_local)
    valid |= in_c & (jm * sspec.c_chunk + (cols - sspec.c_start)
                     < sspec.c_size)
    in_d = cols >= sspec.sharded_local
    valid |= in_d & (shard_idx * sspec.rep_chunk
                     + (cols - sspec.sharded_local) < sspec.rep_size)
    return valid


# -- canonical-index maps (the packing <-> sketch-codec contract) -----------

def _resident_flat_index(sspec: ShardPackSpec, i: int, jm, jf) -> Array:
    """uint32 canonical PackSpec index of every element of leaf ``i``'s
    resident slice on shard (jm, jf) — built from broadcasted iotas with
    TRACED per-dim block offsets, so the hot path never materialises a
    host-side permutation (indices wrap mod 2^32 at >4G-param scale, the
    hashed codec's historical behaviour)."""
    eshape = sspec.spec.shapes[i]
    lshape = _resident_eshape(sspec, i)
    md, fd = sspec.shard_dims[i], sspec.fsdp_dims[i]
    idx = jnp.zeros(lshape, jnp.uint32)
    stride = 1
    for axis in range(len(lshape) - 1, -1, -1):
        ax = jax.lax.broadcasted_iota(jnp.uint32, lshape, axis)
        if axis == md:
            ax = ax + jnp.uint32(lshape[axis]) * jnp.asarray(
                jm, jnp.uint32)
        if axis == fd:
            ax = ax + jnp.uint32(lshape[axis]) * jnp.asarray(
                jf, jnp.uint32)
        idx = idx + ax * jnp.uint32(stride)
        stride *= eshape[axis]
    return (idx + jnp.uint32(sspec.spec.offsets[i])).reshape(-1)


def _seg_perm(sspec: ShardPackSpec, idxs, jm, jf, pad_to: int) -> Array:
    flats = [_resident_flat_index(sspec, i, jm, jf) for i in idxs]
    seg = flats[0] if len(flats) == 1 else jnp.concatenate(flats)
    return _pad_seg(seg, pad_to)


def b_segment_perm(sspec: ShardPackSpec, model_idx) -> Optional[Array]:
    """(b_pad,) uint32 canonical indices of model shard ``model_idx``'s B
    segment (0 on padding — pair with ``arange(b_pad) < b_size``)."""
    if not sspec.b_leaves:
        return None
    return _seg_perm(sspec, sspec.b_leaves, model_idx, 0, sspec.b_pad)


def c_segment_perm(sspec: ShardPackSpec, fsdp_idx) -> Optional[Array]:
    """(c_pad,) uint32 canonical indices of fsdp shard ``fsdp_idx``'s C
    segment."""
    if not sspec.c_leaves:
        return None
    return _seg_perm(sspec, sspec.c_leaves, 0, fsdp_idx, sspec.c_pad)


def rep_segment_perm(sspec: ShardPackSpec) -> Optional[Array]:
    """(rep_pad,) uint32 canonical indices of the global D segment (static)."""
    if not sspec.rep_leaves:
        return None
    return _seg_perm(sspec, sspec.rep_leaves, 0, 0, sspec.rep_pad)


def shard_perm_local(sspec: ShardPackSpec, shard_idx) -> Array:
    """(d_local,) uint32: canonical :class:`PackSpec` index of every
    position of ONE shard's local buffer, traced (``shard_idx`` may be a
    ``jax.lax.axis_index``).  Padding positions carry index 0 — mask them
    with :func:`shard_valid_mask`.  This is the contract the shard-local
    sketch codec hashes: each shard encodes/decodes its resident slice
    against the GLOBAL index space, so per-shard partial sketches sum into
    the one global codec."""
    jm, jf = _split_idx(sspec, shard_idx)
    parts = []
    for i, off in enumerate(sspec.local_offsets):
        if off is not None:
            parts.append(_resident_flat_index(sspec, i, jm, jf))
    if sspec.b_leaves:
        parts.append(_chunk_at(b_segment_perm(sspec, jm), jf, sspec.b_chunk))
    if sspec.c_leaves:
        parts.append(_chunk_at(c_segment_perm(sspec, jf), jm, sspec.c_chunk))
    if sspec.rep_leaves:
        parts.append(_chunk_at(rep_segment_perm(sspec), shard_idx,
                               sspec.rep_chunk))
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts)


def shard_perm(sspec: ShardPackSpec):
    """(d_pad,) int numpy array: canonical :class:`PackSpec` index of every
    shard-packed position (-1 on padding).  Host-side (O(d_pad) memory) —
    for tests and offline layout checks, not the hot path (which uses
    :func:`shard_perm_local`)."""
    import numpy as np

    spec = sspec.spec

    def leaf_idx(i, jm, jf):
        eshape = spec.shapes[i]
        idx = np.arange(spec.sizes[i]).reshape(eshape)
        sl = [slice(None)] * len(eshape)
        md, fd = sspec.shard_dims[i], sspec.fsdp_dims[i]
        if md is not None:
            c = eshape[md] // sspec.n_model
            sl[md] = slice(jm * c, (jm + 1) * c)
        if fd is not None:
            c = eshape[fd] // sspec.n_fsdp
            sl[fd] = slice(jf * c, (jf + 1) * c)
        return spec.offsets[i] + idx[tuple(sl)].reshape(-1)

    def seg_idx(idxs, jm, jf, pad):
        if not idxs:
            return np.zeros((0,), np.int64)
        seg = np.concatenate([leaf_idx(i, jm, jf) for i in idxs])
        return np.concatenate([seg, np.full(pad - seg.size, -1, np.int64)])

    rep_seg = seg_idx(sspec.rep_leaves, 0, 0, sspec.rep_pad)
    perm = np.full(sspec.d_pad, -1, np.int64)
    for j in range(sspec.n_shards):
        jm, jf = j % sspec.n_model, j // sspec.n_model
        base = j * sspec.d_local
        pos = base
        for i, off in enumerate(sspec.local_offsets):
            if off is None:
                continue
            flat = leaf_idx(i, jm, jf)
            perm[base + off:base + off + flat.size] = flat
            pos += flat.size
        b_seg = seg_idx(sspec.b_leaves, jm, 0, sspec.b_pad)
        perm[base + sspec.b_start:base + sspec.b_start + sspec.b_chunk] = \
            b_seg[jf * sspec.b_chunk:(jf + 1) * sspec.b_chunk]
        c_seg = seg_idx(sspec.c_leaves, 0, jf, sspec.c_pad)
        perm[base + sspec.c_start:base + sspec.c_start + sspec.c_chunk] = \
            c_seg[jm * sspec.c_chunk:(jm + 1) * sspec.c_chunk]
        perm[base + sspec.sharded_local:base + sspec.d_local] = \
            rep_seg[j * sspec.rep_chunk:(j + 1) * sspec.rep_chunk]
    return perm


def _slice_block(sspec: ShardPackSpec, leaf, i: int, jm: int, jf: int,
                 nb: int):
    """Global leaf -> its (jm, jf) resident block (host-side shard loops)."""
    piece = leaf
    md, fd = sspec.shard_dims[i], sspec.fsdp_dims[i]
    if md is not None:
        c = sspec.spec.shapes[i][md] // sspec.n_model
        piece = jax.lax.slice_in_dim(piece, jm * c, (jm + 1) * c,
                                     axis=nb + md)
    if fd is not None:
        c = sspec.spec.shapes[i][fd] // sspec.n_fsdp
        piece = jax.lax.slice_in_dim(piece, jf * c, (jf + 1) * c,
                                     axis=nb + fd)
    return piece


def _seg_global(sspec: ShardPackSpec, leaves, idxs, jm: int, jf: int,
                pad_to: int) -> Optional[Array]:
    if not idxs:
        return None
    flats = []
    for i in idxs:
        nb = leaves[i].ndim - len(sspec.spec.shapes[i])
        piece = _slice_block(sspec, leaves[i], i, jm, jf, nb)
        flats.append(piece.astype(jnp.float32).reshape(
            piece.shape[:nb] + (-1,)))
    seg = flats[0] if len(flats) == 1 else jnp.concatenate(flats, axis=-1)
    return _pad_seg(seg, pad_to)


def pack_shard_global(sspec: ShardPackSpec, tree: PyTree) -> Array:
    """GLOBAL tree -> the full ``lead + (d_pad,)`` shard-packed buffer
    (concatenation of every shard's local pack, fsdp-major).  Used at state
    *init* and in tests; the per-round path never materialises this
    concatenate — each device packs only its own shard inside
    ``shard_map``."""
    leaves = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    shards = []
    for j in range(sspec.n_shards):
        jm, jf = j % sspec.n_model, j // sspec.n_model
        parts = []
        for i, off in enumerate(sspec.local_offsets):
            if off is None:
                continue
            nb = leaves[i].ndim - len(sspec.spec.shapes[i])
            piece = _slice_block(sspec, leaves[i], i, jm, jf, nb)
            parts.append(piece.astype(jnp.float32).reshape(
                piece.shape[:nb] + (-1,)))
        seg = _seg_global(sspec, leaves, sspec.b_leaves, jm, 0, sspec.b_pad)
        if seg is not None:
            parts.append(jax.lax.slice_in_dim(
                seg, jf * sspec.b_chunk, (jf + 1) * sspec.b_chunk, axis=-1))
        seg = _seg_global(sspec, leaves, sspec.c_leaves, 0, jf, sspec.c_pad)
        if seg is not None:
            parts.append(jax.lax.slice_in_dim(
                seg, jm * sspec.c_chunk, (jm + 1) * sspec.c_chunk, axis=-1))
        seg = _seg_global(sspec, leaves, sspec.rep_leaves, 0, 0,
                          sspec.rep_pad)
        if seg is not None:
            parts.append(jax.lax.slice_in_dim(
                seg, j * sspec.rep_chunk, (j + 1) * sspec.rep_chunk,
                axis=-1))
        shards.append(parts[0] if len(parts) == 1
                      else jnp.concatenate(parts, axis=-1))
    return shards[0] if len(shards) == 1 \
        else jnp.concatenate(shards, axis=-1)


def unpack_shard_global(sspec: ShardPackSpec, buf: Array,
                        cast: bool = True) -> PyTree:
    """Full ``lead + (d_pad,)`` shard-packed buffer -> GLOBAL tree (the
    inverse of :func:`pack_shard_global`; tests / state export)."""
    if buf.shape[-1] != sspec.d_pad:
        raise ValueError(f"buffer last dim {buf.shape[-1]} != d_pad "
                         f"{sspec.d_pad}")
    lead = buf.shape[:-1]
    locs = [[jax.lax.slice_in_dim(
        buf, (jf * sspec.n_model + jm) * sspec.d_local,
        (jf * sspec.n_model + jm + 1) * sspec.d_local, axis=-1)
        for jm in range(sspec.n_model)] for jf in range(sspec.n_fsdp)]
    out: List[Optional[Array]] = [None] * sspec.spec.n_leaves
    for i, off in enumerate(sspec.local_offsets):
        if off is None:
            continue
        size = sspec.spec.sizes[i] // sspec.n_shards
        md, fd = sspec.shard_dims[i], sspec.fsdp_dims[i]
        rows = []
        for jf in range(sspec.n_fsdp):
            cols = []
            for jm in range(sspec.n_model):
                piece = jax.lax.slice_in_dim(locs[jf][jm], off, off + size,
                                             axis=-1)
                cols.append(piece.reshape(lead + _resident_eshape(sspec, i)))
            rows.append(cols[0] if len(cols) == 1
                        else jnp.concatenate(cols, axis=len(lead) + md))
        out[i] = rows[0] if len(rows) == 1 \
            else jnp.concatenate(rows, axis=len(lead) + fd)
    if sspec.b_leaves:
        for i, off in zip(sspec.b_leaves, sspec.b_offsets):
            size = sspec.spec.sizes[i] // sspec.n_model
            md = sspec.shard_dims[i]
            cols = []
            for jm in range(sspec.n_model):
                seg = jnp.concatenate(
                    [shard_b_chunk(sspec, locs[jf][jm])
                     for jf in range(sspec.n_fsdp)], axis=-1) \
                    if sspec.n_fsdp > 1 else shard_b_chunk(sspec, locs[0][jm])
                piece = jax.lax.slice_in_dim(seg, off, off + size, axis=-1)
                cols.append(piece.reshape(lead + _resident_eshape(sspec, i)))
            out[i] = cols[0] if len(cols) == 1 \
                else jnp.concatenate(cols, axis=len(lead) + md)
    if sspec.c_leaves:
        for i, off in zip(sspec.c_leaves, sspec.c_offsets):
            size = sspec.spec.sizes[i] // sspec.n_fsdp
            fd = sspec.fsdp_dims[i]
            rows = []
            for jf in range(sspec.n_fsdp):
                seg = jnp.concatenate(
                    [shard_c_chunk(sspec, locs[jf][jm])
                     for jm in range(sspec.n_model)], axis=-1) \
                    if sspec.n_model > 1 else shard_c_chunk(sspec, locs[jf][0])
                piece = jax.lax.slice_in_dim(seg, off, off + size, axis=-1)
                rows.append(piece.reshape(lead + _resident_eshape(sspec, i)))
            out[i] = rows[0] if len(rows) == 1 \
                else jnp.concatenate(rows, axis=len(lead) + fd)
    if sspec.rep_leaves:
        seg = jnp.concatenate(
            [shard_rep_chunk(sspec, locs[jf][jm])
             for jf in range(sspec.n_fsdp) for jm in range(sspec.n_model)],
            axis=-1) if sspec.n_shards > 1 \
            else shard_rep_chunk(sspec, locs[0][0])
        for i, off in zip(sspec.rep_leaves, sspec.rep_offsets):
            piece = jax.lax.slice_in_dim(seg, off, off + sspec.spec.sizes[i],
                                         axis=-1)
            out[i] = piece.reshape(lead + sspec.spec.shapes[i])
    if cast:
        out = [p.astype(sspec.spec.dtypes[i]) for i, p in enumerate(out)]
    return jax.tree_util.tree_unflatten(sspec.spec.treedef, out)


def pack_shard_global_cplx(sspec: ShardPackSpec, tree: PyTree) -> Complex:
    """Complex-leaf tree -> Complex of global shard-packed planes."""
    flats = jax.tree_util.tree_flatten(tree, is_leaf=_is_cplx)[0]
    re = jax.tree_util.tree_unflatten(sspec.spec.treedef,
                                      [c.re for c in flats])
    im = jax.tree_util.tree_unflatten(sspec.spec.treedef,
                                      [c.im for c in flats])
    return Complex(pack_shard_global(sspec, re), pack_shard_global(sspec, im))


def unpack_shard_global_cplx(sspec: ShardPackSpec, buf: Complex) -> PyTree:
    """Complex global shard-packed planes -> tree of Complex leaves (f32)."""
    re = unpack_shard_global(sspec, buf.re, cast=False)
    im = unpack_shard_global(sspec, buf.im, cast=False)
    re_l = jax.tree_util.tree_flatten(re)[0]
    im_l = jax.tree_util.tree_flatten(im)[0]
    return jax.tree_util.tree_unflatten(
        sspec.spec.treedef, [Complex(r, i) for r, i in zip(re_l, im_l)])

"""Packed client-delta layout: one flat lane-aligned buffer per *dtype
group* per round.

The round's D2D/D2S hot path is linear algebra over the *concatenation*
of every client's flattened delta, but the deltas live as a pytree, so a
leaf-wise implementation pays one pad -> kernel launch -> slice cycle per
leaf (dozens for an LM).  This module flattens the tree into per-dtype
``(n, P_pad_g)`` buffers -- each P_pad_g lane-aligned (multiple of 128)
-- so the fused mixing kernel launches **once per dtype group** (once per
round for the common dtype-homogeneous tree), and caches the layout
metadata per tree structure so repeated rounds pay zero host-side
re-planning.

Grouping by dtype is a communication-cost decision, not a convenience:
packing a mixed tree into ONE buffer forces ``jnp.result_type`` promotion
(fp32 if any leaf is fp32), which doubles the payload bytes of a
bf16-majority LM tree.  Per-dtype groups keep every leaf at its native
width, so the bytes-on-the-wire model in ``benchmarks.mixing_kernel``
transfers to mixed trees unchanged.  A dtype-homogeneous tree degenerates
to a single group whose buffer is bit-for-bit today's one-buffer layout.

    spec  = pack_spec(deltas)          # cached per (treedef, shapes, ...)
    spec  = pack_spec(deltas, shards=k)  # every P_pad_g also divisible
                                         # into k lane-aligned blocks
    bufs  = pack(deltas, spec)         # tuple of (n, P_pad_g), one concat
                                       # per group
    tree  = unpack(bufs, spec)         # exact inverse (slices + reshapes)
    tree1 = unpack_row(rows, spec)     # per-group (P_g,) aggregate rows
                                       # -> param tree

``pack``/``unpack`` are pure jnp and jit-safe (the spec is static
metadata); under jit XLA fuses the concat/slice with neighbors, and the
packed buffers are the layout the Pallas kernels stream directly.
Groups are ordered by first appearance in treedef order and leaves keep
treedef order inside their group, so the layout is deterministic.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

PyTree = Any

__all__ = ["GroupSpec", "GroupedPackSpec", "QuantSpec", "pack_spec",
           "pack", "unpack", "unpack_row", "apply_aggregate_row",
           "promoted_nbytes", "quantize_group", "dequantize_group",
           "quantize_packed", "dequantize_packed", "init_quant_state"]

_LANE = 128

QUANT_STORAGES = ("int8", "int4", "fp8")


@dataclasses.dataclass(frozen=True)
class QuantSpec:
    """Per-group payload quantization: how a packed ``(n, P_pad_g)``
    delta buffer is compressed for the wire.

    Every group buffer is split into column blocks of ``block`` values;
    each (client, block) pair gets one fp32 absmax scale
    ``s = max|x| / qmax`` and the block is stored as ``round(x / s)`` in
    the ``storage`` container:

      'int8'  -- one int8 per value, qmax 127 (~2x vs bf16, ~4x vs fp32)
      'int4'  -- two values packed per int8 byte (a block's first half
                 in the low nibbles, its second half in the high
                 nibbles), qmax 7 -- the aggressive knob (~4x vs bf16)
      'fp8'   -- float8_e4m3fn per value, qmax 448 (scale maps the block
                 absmax onto the fp8 dynamic range; rounding is the
                 cast's round-to-nearest, so ``rounding='stochastic'``
                 is rejected)

    ``rounding='stochastic'`` replaces round-to-nearest with the
    unbiased ``floor(y + u)``, ``u ~ U[0, 1)`` -- callers thread a PRNG
    key.  ``error_feedback`` keeps a client-side fp32 residual ``r``:
    each round quantizes ``x + r`` and carries ``r' = (x + r) -
    dequant(quantize(x + r))`` forward, so quantization error
    accumulates into later rounds instead of being dropped (the
    mechanism that keeps compressed runs tracking fp32 convergence).
    ``seed`` seeds the stochastic-rounding stream.

    Hashable and jit-static, like the pack spec that embeds it.
    """
    storage: str = "int8"
    block: int = 512
    rounding: str = "nearest"
    error_feedback: bool = True
    seed: int = 0

    def __post_init__(self):
        if self.storage not in QUANT_STORAGES:
            raise ValueError(
                f"storage must be one of {QUANT_STORAGES}, "
                f"got {self.storage!r}")
        if self.rounding not in ("nearest", "stochastic"):
            raise ValueError(
                "rounding must be 'nearest' or 'stochastic', "
                f"got {self.rounding!r}")
        if self.storage == "fp8" and self.rounding == "stochastic":
            raise ValueError(
                "stochastic rounding is defined on the integer grids "
                "only; fp8 storage rounds via the e4m3 cast")
        unit = 2 * _LANE if self.storage == "int4" else _LANE
        if self.block <= 0 or self.block % unit:
            raise ValueError(
                f"block must be a positive multiple of {unit} for "
                f"{self.storage!r} storage (lane alignment of the stored "
                f"container), got {self.block}")

    @property
    def qmax(self) -> float:
        return {"int8": 127.0, "int4": 7.0, "fp8": 448.0}[self.storage]

    @property
    def bits(self) -> int:
        """Stored bits per payload value (4 for the nibble-packed int4)."""
        return 4 if self.storage == "int4" else 8

    @property
    def storage_dtype(self):
        """Container dtype of the stored buffer (int8 holds two nibbles
        for 'int4')."""
        if self.storage == "fp8":
            if not hasattr(jnp, "float8_e4m3fn"):  # pragma: no cover
                raise ValueError(
                    "fp8 storage requires jnp.float8_e4m3fn (jax too old)")
            return jnp.dtype(jnp.float8_e4m3fn)
        return jnp.dtype(jnp.int8)

    def stored_cols(self, p: int) -> int:
        """Container columns holding ``p`` payload columns."""
        return p * self.bits // 8

    def as_dict(self) -> dict:
        return {"storage": self.storage, "block": self.block,
                "rounding": self.rounding,
                "error_feedback": self.error_feedback, "seed": self.seed}

    @classmethod
    def from_dict(cls, d: dict) -> "QuantSpec":
        return cls(storage=d["storage"], block=int(d["block"]),
                   rounding=d["rounding"],
                   error_feedback=bool(d["error_feedback"]),
                   seed=int(d["seed"]))


@dataclasses.dataclass(frozen=True)
class GroupSpec:
    """Static layout of ONE dtype group inside a packed delta tree.

    ``leaf_ids`` are the flat (treedef-order) indices of the leaves this
    group owns; ``shapes`` are their trailing shapes (client axis
    stripped); ``offsets[i]:offsets[i]+sizes[i]`` is leaf i's column
    range in the group's ``(n, padded)`` buffer.
    """
    dtype: Any
    leaf_ids: Tuple[int, ...]
    shapes: Tuple[Tuple[int, ...], ...]
    offsets: Tuple[int, ...]
    sizes: Tuple[int, ...]
    total: int          # P_g   -- sum of leaf sizes
    padded: int         # P_pad_g -- lane-aligned packed width

    @property
    def pad(self) -> int:
        return self.padded - self.total


@dataclasses.dataclass(frozen=True)
class GroupedPackSpec:
    """Static layout metadata for a packed delta tree: one ``GroupSpec``
    per distinct leaf dtype, ordered by first appearance in treedef
    order.  Hashable and jit-static, like the buffers it describes."""
    treedef: Any
    n_leaves: int
    groups: Tuple[GroupSpec, ...]
    quant: Optional[QuantSpec] = None

    @property
    def n_groups(self) -> int:
        return len(self.groups)

    @property
    def total(self) -> int:
        """Total real payload columns across groups."""
        return sum(g.total for g in self.groups)

    @property
    def padded(self) -> int:
        """Total packed columns across groups (sum of the P_pad_g)."""
        return sum(g.padded for g in self.groups)

    def nbytes(self, n: int) -> int:
        """Total packed payload bytes for ``n`` clients at the groups'
        native dtypes -- the quantity the per-dtype grouping exists to
        minimize, and the uncompressed baseline a ``quant`` spec is
        measured against."""
        return sum(n * g.padded * jnp.dtype(g.dtype).itemsize
                   for g in self.groups)

    def scales_nbytes(self, n: int) -> int:
        """Side-buffer bytes: one fp32 scale per (client, block)."""
        if self.quant is None:
            return 0
        return sum(n * (g.padded // self.quant.block) * 4
                   for g in self.groups)

    def quantized_nbytes(self, n: int) -> int:
        """Compressed bytes on the wire for ``n`` clients: the stored
        containers plus the fp32 scale side buffers.  Requires a
        ``quant`` spec."""
        if self.quant is None:
            raise ValueError("spec has no quant config; build one with "
                             "pack_spec(deltas, quant=QuantSpec(...))")
        return sum(n * self.quant.stored_cols(g.padded)
                   for g in self.groups) + self.scales_nbytes(n)


def promoted_nbytes(spec: GroupedPackSpec, n: int,
                    align: int = _LANE) -> int:
    """Bytes the pre-grouping ONE-buffer layout would ship for ``n``
    clients: every leaf cast to ``jnp.result_type`` of the tree (fp32 if
    any leaf is fp32), lane-aligned.  The comparison baseline for
    ``spec.nbytes`` -- used by benchmarks and the payload-bytes
    regression tests, so the legacy-layout model lives in one place."""
    dt = jnp.result_type(*[g.dtype for g in spec.groups])
    cols = ((spec.total + align - 1) // align) * align
    return n * cols * jnp.dtype(dt).itemsize


_SPEC_CACHE: Dict[Any, GroupedPackSpec] = {}


def pack_spec(deltas: PyTree, *, align: int = _LANE,
              shards: int = 1,
              quant: Optional[QuantSpec] = None) -> GroupedPackSpec:
    """Build (or fetch the cached) layout spec for a per-client delta tree
    whose leaves share a leading client axis ``n``.

    Leaves are partitioned into per-dtype groups; each group packs into
    its own lane-aligned ``(n, P_pad_g)`` buffer at the leaves' native
    dtype (no ``result_type`` promotion).

    ``shards`` requests shard-aligned padding: every ``P_pad_g`` becomes a
    multiple of ``align * shards`` so each group's buffer splits evenly
    into ``shards`` lane-aligned column blocks -- required by the
    worker-sharded fused path (``repro.fl.distributed`` mixing='fused_rs'),
    which reduce-scatters each group's aggregate row over the mesh 'data'
    axis.

    ``quant`` attaches a per-group quantization config (``QuantSpec``):
    every ``P_pad_g`` additionally becomes a multiple of ``quant.block``
    so the per-block scale arrays tile the buffers exactly (and, for
    'int4' storage, the nibble-packed container stays lane-aligned).
    Quantization itself is a separate step (``quantize_packed``) -- the
    spec only fixes the layout and byte accounting.
    """
    if shards < 1:
        raise ValueError(f"shards must be >= 1, got {shards}")
    leaves, treedef = jax.tree.flatten(deltas)
    if not leaves:
        raise ValueError("pack_spec: empty delta tree")
    shapes = tuple(tuple(l.shape[1:]) for l in leaves)
    dtypes = tuple(jnp.dtype(l.dtype) for l in leaves)
    key = (treedef, shapes, dtypes, align, shards, quant)
    spec = _SPEC_CACHE.get(key)
    if spec is not None:
        return spec

    by_dtype: Dict[Any, list] = {}
    for i, dt in enumerate(dtypes):         # dict preserves first-seen order
        by_dtype.setdefault(dt, []).append(i)

    unit = align * shards
    if quant is not None:
        unit = int(np.lcm(unit, quant.block))
    groups = []
    for dt, ids in by_dtype.items():
        gshapes = tuple(shapes[i] for i in ids)
        sizes = tuple(int(np.prod(s, dtype=np.int64)) for s in gshapes)
        offsets = tuple(int(o) for o in np.cumsum((0,) + sizes[:-1]))
        total = int(sum(sizes))
        padded = ((total + unit - 1) // unit) * unit
        groups.append(GroupSpec(dtype=dt, leaf_ids=tuple(ids),
                                shapes=gshapes, offsets=offsets,
                                sizes=sizes, total=total, padded=padded))
    spec = GroupedPackSpec(treedef=treedef, n_leaves=len(leaves),
                           groups=tuple(groups), quant=quant)
    _SPEC_CACHE[key] = spec
    return spec


def _validate_tree(leaves, treedef, spec: GroupedPackSpec) -> None:
    if treedef != spec.treedef or len(leaves) != spec.n_leaves:
        raise ValueError(
            "pack: delta tree does not match the spec it was built for: "
            f"spec has {spec.n_leaves} leaves / treedef {spec.treedef}, "
            f"got {len(leaves)} leaves / treedef {treedef}. Build a fresh "
            "spec with pack_spec(deltas).")
    for g in spec.groups:
        for i, shp in zip(g.leaf_ids, g.shapes):
            leaf = leaves[i]
            if tuple(leaf.shape[1:]) != shp or \
                    jnp.dtype(leaf.dtype) != jnp.dtype(g.dtype):
                raise ValueError(
                    f"pack: leaf {i} has trailing shape "
                    f"{tuple(leaf.shape[1:])} / dtype {leaf.dtype}, but the "
                    f"spec expects {shp} / {jnp.dtype(g.dtype)}. Build a "
                    "fresh spec with pack_spec(deltas).")


def pack(deltas: PyTree, spec: GroupedPackSpec
         ) -> Tuple[jnp.ndarray, ...]:
    """Flatten the delta tree into per-dtype ``(n, P_pad_g)`` buffers
    (one per spec group, in group order).

    Raises ``ValueError`` if the tree's structure, trailing shapes, or
    dtypes do not match the spec -- a mismatched spec would otherwise
    silently scramble the layout.
    """
    leaves, treedef = jax.tree.flatten(deltas)
    _validate_tree(leaves, treedef, spec)
    n = leaves[0].shape[0]
    bufs = []
    for g in spec.groups:
        flat = [leaves[i].reshape(n, -1) for i in g.leaf_ids]
        if g.pad:
            flat.append(jnp.zeros((n, g.pad), g.dtype))
        bufs.append(jnp.concatenate(flat, axis=1))
    return tuple(bufs)


def _as_group_tuple(bufs: Union[jnp.ndarray, Sequence[jnp.ndarray]],
                    spec: GroupedPackSpec, what: str
                    ) -> Tuple[jnp.ndarray, ...]:
    """Normalize a per-group sequence (or a bare array for single-group
    specs) to a tuple matching ``spec.groups``."""
    if isinstance(bufs, (jnp.ndarray, np.ndarray)):
        bufs = (bufs,)
    bufs = tuple(bufs)
    if len(bufs) != spec.n_groups:
        raise ValueError(
            f"{what}: expected {spec.n_groups} per-group arrays "
            f"(one per dtype group), got {len(bufs)}")
    return bufs


def unpack(bufs: Union[jnp.ndarray, Sequence[jnp.ndarray]],
           spec: GroupedPackSpec) -> PyTree:
    """Inverse of ``pack``: per-group (n, P_pad_g) buffers -> delta tree
    (leading axis n).  Restores per-leaf dtypes exactly (a mixed buffer
    dtype -- e.g. the fused kernel's fp32 mixed output for a bf16 group
    -- is cast back per leaf)."""
    bufs = _as_group_tuple(bufs, spec, "unpack")
    n = bufs[0].shape[0]
    leaves = [None] * spec.n_leaves
    for g, buf in zip(spec.groups, bufs):
        for i, o, s, shp in zip(g.leaf_ids, g.offsets, g.sizes, g.shapes):
            leaves[i] = buf[:, o:o + s].reshape((n,) + shp).astype(g.dtype)
    return jax.tree.unflatten(spec.treedef, leaves)


def unpack_row(rows: Union[jnp.ndarray, Sequence[jnp.ndarray]],
               spec: GroupedPackSpec) -> PyTree:
    """Unpack per-group aggregate rows -- each (P_g,) or (P_pad_g,), e.g.
    the fused kernels' fp32 aggregates -- into a tree of per-leaf trailing
    shapes (no client axis).  Keeps the row dtype (fp32 accumulator)
    untouched."""
    rows = _as_group_tuple(rows, spec, "unpack_row")
    leaves = [None] * spec.n_leaves
    for g, row in zip(spec.groups, rows):
        for i, o, s, shp in zip(g.leaf_ids, g.offsets, g.sizes, g.shapes):
            leaves[i] = row[o:o + s].reshape(shp)
    return jax.tree.unflatten(spec.treedef, leaves)


def apply_aggregate_row(global_params: PyTree,
                        rows: Union[jnp.ndarray, Sequence[jnp.ndarray]],
                        spec: GroupedPackSpec) -> PyTree:
    """Eq.-4 epilogue shared by every one-pass backend: unpack the
    per-group fp32 aggregate rows and add them leaf-wise, casting back to
    each global-param leaf's dtype only after the add."""
    agg = unpack_row(rows, spec)
    return jax.tree.map(lambda g, a: (g + a).astype(g.dtype),
                        global_params, agg)


# ---------------------------------------------------------------------------
# Payload quantization (QuantSpec): pack-time compression + error feedback
# ---------------------------------------------------------------------------


def _pack_nibbles(v: jnp.ndarray, block: int) -> jnp.ndarray:
    """(n, p) int8 values in [-8, 7] -> (n, p//2) packed bytes, block by
    block: value j of a ``block``-value block sits in the low nibble of
    the block's byte j, value ``j + block // 2`` in its high nibble.  The
    kernels then unpack a tile by concatenating lane-aligned halves
    (``repro.kernels.mixing.fused.dequant_tile``)."""
    n, p = v.shape
    halves = v.reshape(n, p // block, 2, block // 2)
    packed = (halves[:, :, 0] & jnp.int8(0x0F)) | (halves[:, :, 1] << 4)
    return packed.reshape(n, p // 2)


def _unpack_nibbles(q: jnp.ndarray, block: int) -> jnp.ndarray:
    """Inverse of ``_pack_nibbles``: sign-extend both nibbles of every
    byte and put each block's halves back -- (n, p//2) int8 -> (n, p)
    int8."""
    n, pq = q.shape
    h = block // 2
    lo = (q << 4) >> 4            # shift out the high nibble, extend back
    hi = q >> 4                   # arithmetic shift sign-extends
    halves = jnp.stack([lo.reshape(n, pq // h, h),
                        hi.reshape(n, pq // h, h)], axis=2)
    return halves.reshape(n, 2 * pq)


def quantize_group(buf: jnp.ndarray, quant: QuantSpec,
                   key: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Quantize one group buffer ``(n, P)`` (``P % quant.block == 0``).

    Returns ``(stored, scales)``: the storage container
    ``(n, quant.stored_cols(P))`` and the fp32 per-block scales
    ``(n, P // quant.block)``.  An all-zero block gets scale 0 and
    dequantizes to exact zeros.  ``key`` is required for (and only for)
    stochastic rounding.
    """
    n, p = buf.shape
    if p % quant.block:
        raise ValueError(
            f"group width {p} is not a multiple of quant.block "
            f"{quant.block}; build the spec with pack_spec(..., quant=)")
    nb = p // quant.block
    x = buf.astype(jnp.float32).reshape(n, nb, quant.block)
    scales = jnp.max(jnp.abs(x), axis=2) / quant.qmax        # (n, nb)
    y = x / jnp.where(scales > 0, scales, 1.0)[:, :, None]
    if quant.storage == "fp8":
        stored = y.reshape(n, p).astype(quant.storage_dtype)
    else:
        if quant.rounding == "stochastic":
            if key is None:
                raise ValueError("stochastic rounding needs a PRNG key")
            v = jnp.floor(y + jax.random.uniform(key, y.shape))
        else:
            v = jnp.round(y)
        v = jnp.clip(v, -quant.qmax, quant.qmax)
        v = v.astype(jnp.int8).reshape(n, p)
        stored = (_pack_nibbles(v, quant.block) if quant.storage == "int4"
                  else v)
    return stored, scales


def dequantize_group(stored: jnp.ndarray, scales: jnp.ndarray,
                     quant: QuantSpec) -> jnp.ndarray:
    """Exact inverse mapping of ``quantize_group``'s grid: fp32
    ``(n, P)`` = stored values * per-block scales.  This is the same
    arithmetic the kernels' fused dequant epilogue applies in VMEM
    (``repro.kernels.mixing.fused.dequant_tile``), so host-side
    round-trips match the kernel path bitwise."""
    n = stored.shape[0]
    nb = scales.shape[1]
    if quant.storage == "int4":
        v = _unpack_nibbles(stored, quant.block).astype(jnp.float32)
    else:
        v = stored.astype(jnp.float32)
    x = v.reshape(n, nb, quant.block) * scales[:, :, None]
    return x.reshape(n, nb * quant.block)


def quantize_packed(bufs: Sequence[jnp.ndarray], spec: GroupedPackSpec,
                    residuals: Optional[Sequence[jnp.ndarray]] = None,
                    key: Optional[jnp.ndarray] = None):
    """Quantize every packed group buffer under ``spec.quant``.

    ``residuals`` (per-group fp32 ``(n, P_pad_g)``, or None) is the
    error-feedback state: when given, each group quantizes
    ``x + residual``.  Returns ``(stored, scales, new_residuals)`` with
    ``new_residuals[g] = (x_g + r_g) - dequant(stored_g)`` -- the exact
    fp32 round-trip error, always computed so the caller decides whether
    to carry it (error feedback on) or drop it (off).
    """
    quant = spec.quant
    if quant is None:
        raise ValueError("spec has no quant config; build one with "
                         "pack_spec(deltas, quant=QuantSpec(...))")
    bufs = _as_group_tuple(bufs, spec, "quantize_packed")
    keys = (jax.random.split(key, spec.n_groups)
            if key is not None else (None,) * spec.n_groups)
    stored, scales, new_res = [], [], []
    for i, buf in enumerate(bufs):
        x = buf.astype(jnp.float32)
        if residuals is not None:
            x = x + residuals[i]
        s, sc = quantize_group(x, quant, keys[i])
        stored.append(s)
        scales.append(sc)
        new_res.append(x - dequantize_group(s, sc, quant))
    return tuple(stored), tuple(scales), tuple(new_res)


def dequantize_packed(stored: Sequence[jnp.ndarray],
                      scales: Sequence[jnp.ndarray],
                      spec: GroupedPackSpec) -> Tuple[jnp.ndarray, ...]:
    """Per-group fp32 ``(n, P_pad_g)`` buffers reconstructed from the
    wire format -- the reference (einsum-oracle) inverse; the kernel
    backends never materialize these."""
    stored = _as_group_tuple(stored, spec, "dequantize_packed")
    return tuple(dequantize_group(s, sc, spec.quant)
                 for s, sc in zip(stored, scales))


def init_quant_state(spec: GroupedPackSpec, n: int):
    """Fresh client-side quantizer state ``(residuals, key)``: zero
    error-feedback residuals (one fp32 buffer per group, packed layout)
    plus the stochastic-rounding PRNG key (seeded from
    ``spec.quant.seed``).  Threaded through the round functions as a
    scan carry; round 0 with zero residuals is plain quantization."""
    if spec.quant is None:
        raise ValueError("spec has no quant config; build one with "
                         "pack_spec(deltas, quant=QuantSpec(...))")
    residuals = tuple(jnp.zeros((n, g.padded), jnp.float32)
                      for g in spec.groups)
    return residuals, jax.random.PRNGKey(spec.quant.seed)

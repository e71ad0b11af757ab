"""Fused page install/spill: one-kernel gather/scatter between byte
pages and the KV batch cache (DESIGN.md §11).

The last hop of a page fetch used to be naive: a device-resident byte
page was carved into cache leaves with one ``lax.slice`` + ``.view`` +
``.at[slot].set`` chain *per leaf per page*, and spill packed leaves
with one ``np.asarray`` D2H per leaf.  This module replaces both ends
with layout-driven fused paths:

* ``PageLayout`` — a precomputed, hashable descriptor of where every
  cache leaf lives inside the packed page (byte offset, single-request
  shape, dtype, slot axis in the batch tree).  Built once per
  ``(treedef, shapes, batch)`` and cached; shared by the kernels, the
  jit fallback, the host reference, and (via the unchanged byte format)
  the checksum plane.
* ``install_pages`` — scatter G staged pages into the batch cache tree.
  ``mode="pallas"`` runs one ``pallas_call`` whose copies are all
  HBM->HBM DMAs: each leaf's segment of the staged pages (an XLA
  slice + bitcast outside the kernel) lands in its slot row of the
  aliased batch leaf; ``mode="jit"`` is a single fused XLA program;
  ``mode="ref"`` is the per-leaf legacy chain, kept as the parity
  oracle.
* ``pack_page`` — the scatter's gather twin for spill: pack one slot's
  cache leaves into a contiguous uint8 page *on device*, so the caller
  does a single D2H instead of per-leaf readbacks + host concatenate.
* ``install_slot`` — the jitted replacement for the serving engine's
  per-leaf ``_slot_cache_set`` (donated batch cache, static slot-axis
  map), so non-paging installs stop paying per-admit dispatch overhead.

Byte format contract: a page is the concatenation of every leaf's C
-order bytes in tree-flatten order — identical to
``np.concatenate([np.asarray(l).reshape(-1).view(np.uint8) ...])``, so
fused and per-leaf paths (and the §9 checksums stamped over either) are
bit-exact interchangeable.

On a TPU the kernels never load or store an HBM ref: every byte moves
by ``make_async_copy``, and no VMEM is staged, so the kernels compile at
any ``max_len``.  Install hazard discipline: page g's copies run on lane
g % n_buffers, and a lane's previous page is waited before reuse.
``mode="auto"`` picks pallas (compiled) on TPU and the fused jit
program elsewhere; the CPU tests run the pallas kernels with
``interpret=True``.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# layout descriptor
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class LeafSpec:
    """One cache leaf's place in the packed page.

    ``shape`` is the single-request leaf shape (size 1 at the slot
    axis); ``batch_shape`` the batch-tree leaf; ``slot_axis`` the axis
    where the batch leaf has size ``batch`` and the single leaf size 1
    (None = no such axis: the leaf merges by elementwise maximum, the
    "len" counter rule)."""
    index: int
    offset: int
    shape: Tuple[int, ...]
    batch_shape: Tuple[int, ...]
    dtype: str
    slot_axis: Optional[int]

    @property
    def itemsize(self) -> int:
        return jnp.dtype(self.dtype).itemsize

    @property
    def nbytes(self) -> int:
        return int(np.prod(self.shape, dtype=np.int64)) * self.itemsize


@dataclasses.dataclass(frozen=True)
class PageLayout:
    """Static map from a packed byte page to a batch cache tree."""
    batch: int
    page_bytes: int
    leaves: Tuple[LeafSpec, ...]

    def kernel_leaves(self) -> Tuple[LeafSpec, ...]:
        """Leaves the fused kernels copy: a slot axis exists, ranks
        agree, and one slot's row folds into whole (sublane, 128) tiles
        (a TPU DMA may only slice an HBM ref along untiled dims)."""
        return tuple(sp for sp in self.leaves
                     if sp.slot_axis is not None and sp.nbytes
                     and len(sp.shape) == len(sp.batch_shape)
                     and _row_elems(sp) % (_sublanes(sp) * _LANES) == 0)

    def fallback_indices(self) -> Tuple[int, ...]:
        """Leaf indices the kernels skip (installed by the jit path)."""
        covered = {sp.index for sp in self.kernel_leaves()}
        return tuple(sp.index for sp in self.leaves
                     if sp.index not in covered)

    @property
    def word_bytes(self) -> int:
        """Widest unsigned word (4, 2 or 1 bytes) that every leaf starts
        on and the page ends on.  Device-side pages travel as words of
        this width: the TPU compiler takes minutes over programs that
        reshape large uint8 arrays, and seconds over 32-bit words."""
        return next(k for k in (4, 2, 1)
                    if self.page_bytes % k == 0
                    and all(sp.offset % k == 0 for sp in self.leaves))

    @property
    def word_dtype(self) -> np.dtype:
        return np.dtype(f"uint{8 * self.word_bytes}")


_LANES = 128


def _sublanes(sp: LeafSpec) -> int:
    # rows of one native TPU tile: 8 for 32-bit, 16 for 16-bit, 32 for
    # 8-bit dtypes
    return 8 * max(1, 4 // sp.itemsize)


def _row_elems(sp: LeafSpec) -> int:
    """Elements of one slot's row: the dims after the slot axis."""
    return int(np.prod(sp.shape[sp.slot_axis + 1:], dtype=np.int64))


def _tiled(sp: LeafSpec, shape) -> Tuple[int, ...]:
    """``shape`` through the slot axis, then the row folded into whole
    (sublane, 128) tiles, so the kernel's DMAs slice major dims only."""
    a = sp.slot_axis + 1
    sub = _sublanes(sp)
    return tuple(shape[:a]) + (_row_elems(sp) // (sub * _LANES), sub,
                               _LANES)


def _slot_axis(bshape, oshape, batch: int) -> Optional[int]:
    # the serving engine's structural rule, verbatim: first axis where
    # the batch leaf has size B and the single-request leaf size 1
    return next((i for i, (x, y) in enumerate(zip(bshape, oshape))
                 if x == batch and y == 1), None)


_LAYOUT_CACHE: Dict[tuple, PageLayout] = {}


def page_layout(single_tree, batch_tree, batch: int) -> PageLayout:
    """Build (or fetch the cached) ``PageLayout`` for a cache config.

    Both trees may hold arrays or ``jax.ShapeDtypeStruct`` (use
    ``jax.eval_shape`` to avoid materializing anything); they must share
    a treedef.  Cached by ``(treedef, shapes, dtypes, batch)``.
    """
    singles, sdef = jax.tree.flatten(single_tree)
    batches, bdef = jax.tree.flatten(batch_tree)
    if sdef != bdef:
        raise ValueError(f"tree mismatch: {sdef} vs {bdef}")
    if len(singles) != len(batches):
        raise ValueError("leaf count mismatch")
    key = (str(sdef), batch,
           tuple((tuple(l.shape), jnp.dtype(l.dtype).name) for l in singles),
           tuple((tuple(l.shape), jnp.dtype(l.dtype).name) for l in batches))
    hit = _LAYOUT_CACHE.get(key)
    if hit is not None:
        return hit
    specs, off = [], 0
    for i, (o, b) in enumerate(zip(singles, batches)):
        dt = jnp.dtype(o.dtype)
        if jnp.dtype(b.dtype) != dt:
            raise ValueError(
                f"leaf {i}: dtype mismatch {b.dtype} vs {o.dtype}")
        specs.append(LeafSpec(
            index=i, offset=off, shape=tuple(o.shape),
            batch_shape=tuple(b.shape), dtype=dt.name,
            slot_axis=_slot_axis(b.shape, o.shape, batch)))
        off += specs[-1].nbytes
    layout = PageLayout(batch=batch, page_bytes=off, leaves=tuple(specs))
    _LAYOUT_CACHE[key] = layout
    return layout


# ---------------------------------------------------------------------------
# byte <-> dtype plumbing (bit-exact with numpy .view on both ends)
# ---------------------------------------------------------------------------

def _retype(x, dt) -> jax.Array:
    """Flatten ``x`` and bitcast it to dtype ``dt``, whichever of the
    two is wider.  C order, so bit-exact with numpy's ``.view``."""
    dt = jnp.dtype(dt)
    k, it = x.dtype.itemsize, dt.itemsize
    x = x.reshape(-1, it // k) if it > k else x.reshape(-1)
    return jax.lax.bitcast_convert_type(x, dt).reshape(-1)


def _segment(page, sp: LeafSpec) -> jax.Array:
    """Leaf ``sp`` out of one 1-D page of any word width."""
    k = page.dtype.itemsize
    seg = jax.lax.slice(page, (sp.offset // k,),
                        ((sp.offset + sp.nbytes) // k,))
    return _retype(seg, sp.dtype).reshape(sp.shape)


def _resolve_mode(mode: str) -> str:
    if mode == "auto":
        return "pallas" if jax.default_backend() == "tpu" else "jit"
    if mode not in ("pallas", "jit", "ref"):
        raise ValueError(f"mode must be auto|pallas|jit|ref, got {mode!r}")
    return mode


def _check_width(layout: PageLayout, buf, width: int) -> None:
    k = buf.dtype.itemsize
    if buf.shape[-1] * k != width:
        raise ValueError(f"page width {buf.shape[-1] * k}B != {width}B")
    if k > layout.word_bytes:
        raise ValueError(f"{buf.dtype} pages cut leaves of a layout "
                         f"aligned to {layout.word_bytes}B")


def _normalize_pages(layout: PageLayout, pages,
                     width: Optional[int] = None):
    """Accept either a (G, words) array or a sequence of ``(buf, row)``
    entries — ``buf`` a (words,) page (row None) or a (Gk, words) staged
    group with ``row`` selecting one page — and return (bufs tuple, rows
    int32 array, G).  A page is uint8 bytes or unsigned words of at most
    ``layout.word_bytes``; ``width`` is its size in bytes, by default
    the logical page size (a codec install passes its encoded size)."""
    width = layout.page_bytes if width is None else width
    if hasattr(pages, "ndim"):
        if pages.ndim == 1:
            pages = pages[None]
        G = pages.shape[0]
        _check_width(layout, pages, width)
        bufs = tuple(pages[g] for g in range(G))
        rows = jnp.zeros((G,), jnp.int32)
        return bufs, rows, G
    bufs, rows = [], []
    for buf, row in pages:
        _check_width(layout, buf, width)
        bufs.append(buf)
        rows.append(0 if row is None else int(row))
    return tuple(bufs), jnp.asarray(rows, jnp.int32), len(bufs)


def _page_rows(bufs, rows) -> list:
    """The 1-D page of every entry (row picked on device)."""
    return [b if b.ndim == 1
            else jax.lax.dynamic_index_in_dim(b, rows[g], 0, keepdims=False)
            for g, b in enumerate(bufs)]


# ---------------------------------------------------------------------------
# reference (per-leaf legacy chain — the parity oracle)
# ---------------------------------------------------------------------------

def pack_page_ref(layout: PageLayout, leaves) -> np.ndarray:
    """Host-side per-leaf pack: one D2H readback per leaf (the legacy
    ``_page_store`` chain).  Defines the page byte format."""
    out = np.concatenate(
        [np.asarray(l).reshape(-1).view(np.uint8) for l in leaves])
    if out.nbytes != layout.page_bytes:
        raise ValueError(f"packed {out.nbytes} != {layout.page_bytes}")
    return out


def install_pages_ref(layout: PageLayout, batch_leaves, pages, slots):
    """Per-leaf reference install: the ``slice -> view -> reshape ->
    .at[slot].set`` chain of the legacy ``_page_fetch``/
    ``_slot_cache_set``, one dispatch per leaf per page."""
    bufs, rows, G = _normalize_pages(layout, pages)
    out = list(batch_leaves)
    for g in range(G):
        pg = bufs[g] if bufs[g].ndim == 1 else bufs[g][int(rows[g])]
        k = pg.dtype.itemsize
        sl = int(slots[g])
        for sp in layout.leaves:
            piece = jax.lax.slice(pg, (sp.offset // k,),
                                  ((sp.offset + sp.nbytes) // k,))
            val = piece.view(sp.dtype).reshape(sp.shape)
            b = out[sp.index]
            if sp.slot_axis is None:
                out[sp.index] = jnp.maximum(b, val)
                continue
            idx = [slice(None)] * b.ndim
            idx[sp.slot_axis] = sl
            src = [slice(None)] * val.ndim
            src[sp.slot_axis] = 0
            out[sp.index] = b.at[tuple(idx)].set(val[tuple(src)])
    return out


# ---------------------------------------------------------------------------
# fused jit paths (single XLA program; the CPU production path)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _pack_jit(layout: PageLayout):
    def fn(leaves):
        return jnp.concatenate([_retype(l, layout.word_dtype)
                                for l in leaves])
    return jax.jit(fn)


@functools.lru_cache(maxsize=None)
def _codec_segmap(codec) -> Dict[int, object]:
    return {s.offset: s for s in codec.segs}


def _codec_seg(codec, sp: LeafSpec):
    """The codec segment backing a layout leaf — offsets, widths and
    dtypes must agree or the encoded page was built for another tree."""
    seg = _codec_segmap(codec).get(sp.offset)
    if seg is None or seg.nbytes != sp.nbytes or seg.dtype != sp.dtype:
        raise ValueError(f"codec segment mismatch at byte {sp.offset}: "
                         f"layout leaf {sp.dtype}x{sp.nbytes}B, codec "
                         f"has {seg}")
    return seg


@functools.lru_cache(maxsize=None)
def _install_jit(layout: PageLayout, buf_shapes: tuple, donate: bool,
                 only: Optional[tuple], codec=None):
    """One fused scatter program per (layout, staging shape): every
    selected leaf of every page installs in a single dispatch.  ``only``
    restricts to a leaf-index subset (the pallas path's non-kernel
    leftovers); None = all leaves.  With ``codec``, the staged pages are
    codec-ENCODED bytes and each leaf's dequant runs as an epilogue
    inside the same program (no host hop, no intermediate byte image)."""
    keep = None if only is None else frozenset(only)

    def fn(batch_leaves, bufs, rows, slots):
        pages = _page_rows(bufs, rows)
        out = list(batch_leaves)
        for sp in layout.leaves:
            if keep is not None and sp.index not in keep:
                continue
            for g, pg in enumerate(pages):
                if codec is not None:
                    val = codec.decode_segment_jnp(
                        pg, _codec_seg(codec, sp)).reshape(sp.shape)
                else:
                    val = _segment(pg, sp)
                b = out[sp.index]
                if sp.slot_axis is None:
                    out[sp.index] = jnp.maximum(b, val)
                    continue
                starts = [jnp.int32(0)] * b.ndim
                starts[sp.slot_axis] = slots[g]
                out[sp.index] = jax.lax.dynamic_update_slice(
                    b, val, tuple(starts))
        return tuple(out)

    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _slot_set_jit(layout: PageLayout, donate: bool):
    """Jitted ``_slot_cache_set``: scatter one single-request cache tree
    into the batch tree at ``slot`` (traced — no recompile per slot),
    optionally donating the batch leaves for in-place update."""
    def fn(batch_leaves, single_leaves, slot):
        out = list(batch_leaves)
        for sp in layout.leaves:
            b, o = out[sp.index], single_leaves[sp.index]
            if sp.slot_axis is None:
                out[sp.index] = jnp.maximum(b, o)
                continue
            starts = [jnp.int32(0)] * b.ndim
            starts[sp.slot_axis] = slot
            out[sp.index] = jax.lax.dynamic_update_slice(
                b, o, tuple(starts))
        return tuple(out)

    return jax.jit(fn, donate_argnums=(0,) if donate else ())


def _can_donate(leaves) -> bool:
    # donating the same buffer twice is a hard XLA error; a cache tree
    # with structurally shared leaves must fall back to copy semantics
    return len({id(l) for l in leaves}) == len(leaves)


# ---------------------------------------------------------------------------
# pallas fused kernels
# ---------------------------------------------------------------------------

def _page_leaf_view(pages, sp: LeafSpec) -> jax.Array:
    """Leaf ``sp`` of every staged 1-D page, as a (G, *tiled leaf shape)
    array of the leaf's dtype: an XLA slice + bitcast outside the kernel,
    so the kernel moves typed rows and never touches bytes."""
    return jnp.stack([_segment(pg, sp).reshape(_tiled(sp, sp.shape))
                      for pg in pages])


def _slot_row(sp: LeafSpec, slot) -> tuple:
    return tuple(pl.ds(slot, 1) if i == sp.slot_axis else slice(None)
                 for i in range(len(sp.batch_shape)))


def _install_kernel(slots_ref, *refs, specs, n_buffers, n_pages):
    """Copy each kernel leaf of every staged page into its slot row of
    the aliased batch leaf.  Every copy is an HBM->HBM DMA, so the kernel
    holds no VMEM whatever ``max_len`` is.  Page g's copies run on lane
    g % n_buffers; a lane's previous page is waited before the lane is
    reused, so at most ``n_buffers`` pages are in flight."""
    n = len(specs)
    srcs, outs, sems = refs[:n], refs[2 * n:3 * n], refs[3 * n]

    def copies(g, lane):
        sl = slots_ref[g]
        return [pltpu.make_async_copy(srcs[j].at[g],
                                      outs[j].at[_slot_row(sp, sl)],
                                      sems.at[lane, j])
                for j, sp in enumerate(specs)]

    def body(g, _):
        lane = jax.lax.rem(g, n_buffers)

        @pl.when(g >= n_buffers)
        def _reuse():
            for c in copies(g - n_buffers, lane):
                c.wait()

        for c in copies(g, lane):
            c.start()
        return 0

    jax.lax.fori_loop(0, n_pages, body, 0)
    for g in range(max(0, n_pages - n_buffers), n_pages):
        for c in copies(g, g % n_buffers):
            c.wait()


@functools.lru_cache(maxsize=None)
def _install_pallas(layout: PageLayout, buf_shapes: tuple, n_buffers: int,
                    interpret: bool, donate: bool):
    """One program per (layout, staging shape): view each kernel leaf's
    segment of the staged rows, run the DMA kernel over the aliased
    batch leaves, then install the non-kernel leaves."""
    specs = layout.kernel_leaves()
    rest = layout.fallback_indices()
    G = len(buf_shapes)
    nb = max(1, min(n_buffers, G))
    n = len(specs)
    kernel = functools.partial(_install_kernel, specs=specs, n_buffers=nb,
                               n_pages=G)
    call = pl.pallas_call(
        kernel,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM)]
                 + [pl.BlockSpec(memory_space=pltpu.HBM)] * (2 * n),
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * n,
        out_shape=[jax.ShapeDtypeStruct(_tiled(sp, sp.batch_shape),
                                        jnp.dtype(sp.dtype))
                   for sp in specs],
        input_output_aliases={1 + n + j: j for j in range(n)},
        scratch_shapes=[pltpu.SemaphoreType.DMA((nb, n))],
        interpret=interpret,
        name="kv_install_pages",
    )
    fb = _install_jit(layout, buf_shapes, False, rest) if rest else None

    def fn(batch_leaves, bufs, rows, slots):
        pages = _page_rows(bufs, rows)
        out = list(batch_leaves)
        if specs:
            res = call(slots, *[_page_leaf_view(pages, sp) for sp in specs],
                       *[out[sp.index].reshape(_tiled(sp, sp.batch_shape))
                         for sp in specs])
            for sp, r in zip(specs, res):
                out[sp.index] = r.reshape(sp.batch_shape)
        if fb is not None:
            out = list(fb(tuple(out), bufs, rows, slots))
        return tuple(out)

    return jax.jit(fn, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _decode_stack(codec, buf_shapes: tuple):
    """Jitted batch decode: encoded staged groups -> a (G, page_bytes)
    logical byte image, feeding the pallas scatter kernels (the dequant
    stays device-side; only the scatter itself runs in pallas)."""
    def fn(bufs, rows):
        return jnp.stack([codec.decode_row_jnp(p)
                          for p in _page_rows(bufs, rows)])
    return jax.jit(fn)


def _pack_kernel(*refs, n):
    """Copy each kernel leaf HBM->HBM into its own output: all DMAs
    start up front (disjoint destinations, no reuse hazard), then all
    are waited."""
    ins, outs, sems = refs[:n], refs[n:2 * n], refs[2 * n]
    copies = [pltpu.make_async_copy(ins[j], outs[j], sems.at[j])
              for j in range(n)]
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


@functools.lru_cache(maxsize=None)
def _pack_pallas(layout: PageLayout, interpret: bool):
    """Fused device-side pack: the DMA kernel gathers the kernel leaves,
    and the same program lays every leaf's words end to end in layout
    order (non-kernel leaves straight from their inputs)."""
    specs = layout.kernel_leaves()
    n = len(specs)
    call = pl.pallas_call(
        functools.partial(_pack_kernel, n=n),
        in_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * n,
        out_specs=[pl.BlockSpec(memory_space=pltpu.HBM)] * n,
        out_shape=[jax.ShapeDtypeStruct(sp.shape, jnp.dtype(sp.dtype))
                   for sp in specs],
        scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        interpret=interpret,
        name="kv_pack_page",
    )

    def fn(leaves):
        merged = list(leaves)
        if specs:
            res = call(*[leaves[sp.index] for sp in specs])
            for sp, r in zip(specs, res):
                merged[sp.index] = r
        return jnp.concatenate([_retype(l, layout.word_dtype)
                                for l in merged])

    return jax.jit(fn)


# ---------------------------------------------------------------------------
# public entry points
# ---------------------------------------------------------------------------

def pack_page(layout: PageLayout, leaves, *, mode: str = "auto",
              interpret: Optional[bool] = None) -> np.ndarray:
    """Pack one slot's cache leaves into a (page_bytes,) uint8 host page:
    the jit and pallas modes pack on device, in ``layout.word_dtype``
    words, and cross D2H once.  Bit-identical to ``pack_page_ref`` in
    every mode."""
    mode = _resolve_mode(mode)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    leaves = tuple(leaves)
    if len(leaves) != len(layout.leaves):
        raise ValueError(f"{len(leaves)} leaves != layout "
                         f"{len(layout.leaves)}")
    if mode == "ref":
        return pack_page_ref(layout, leaves)
    fn = _pack_jit(layout) if mode == "jit" \
        else _pack_pallas(layout, interpret)
    return np.asarray(fn(leaves)).view(np.uint8)


def install_pages(layout: PageLayout, batch_leaves, pages, slots, *,
                  mode: str = "auto", n_buffers: int = 2,
                  interpret: Optional[bool] = None,
                  donate: bool = False, codec=None):
    """Scatter G staged pages into the batch cache leaves at ``slots``.

    ``pages``: a (G, words) array, or a sequence of ``(buf, row)``
    entries straight from ``TieredStore.ensure_packed`` (``buf`` a
    staged (Gk, words) group, ``row`` its page's row — no per-row split
    ever happens); words are uint8 or ``layout.word_dtype``.  Returns the new leaf list in
    tree-flatten order.  ``donate=True`` releases the old batch leaves
    to XLA for in-place update (callers must drop their own
    references).  ``n_buffers`` bounds the pages whose copies the
    pallas kernel keeps in flight; that path needs distinct slots.

    ``codec`` (a ``rmem.codec.PageCodec``) declares the staged pages
    codec-ENCODED (physical bytes, ``codec.encoded_bytes`` wide): the
    jit path fuses each leaf's dequant into the scatter program as an
    epilogue; the pallas path dequants in a jitted device pre-pass and
    scatters the logical image; the ref oracle decodes host-side."""
    mode = _resolve_mode(mode)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    batch_leaves = tuple(batch_leaves)
    width = None
    if codec is not None:
        if codec.page_bytes != layout.page_bytes:
            raise ValueError(f"codec pages {codec.page_bytes}B != "
                             f"layout {layout.page_bytes}B")
        for sp in layout.leaves:
            _codec_seg(codec, sp)
        width = codec.encoded_bytes
    bufs, rows, G = _normalize_pages(layout, pages, width)
    if len(slots) != G:
        raise ValueError(f"{len(slots)} slots != {G} pages")
    if mode == "ref":
        if codec is not None:
            host_rows = np.asarray(rows)
            host = np.stack([
                codec.decode(np.asarray(b if b.ndim == 1
                                        else b[int(host_rows[g])]))
                for g, b in enumerate(bufs)])
            return install_pages_ref(layout, batch_leaves,
                                     jnp.asarray(host), slots)
        return install_pages_ref(layout, batch_leaves, pages, slots)
    donate = donate and _can_donate(batch_leaves)
    if mode == "pallas":
        if len({int(s) for s in slots}) != G:
            raise ValueError(f"pallas install needs distinct slots, got "
                             f"{list(slots)}")
        if codec is not None:
            dec = _decode_stack(codec, tuple(b.shape for b in bufs))(
                bufs, rows)
            bufs = tuple(dec[g] for g in range(G))
            rows = jnp.zeros((G,), jnp.int32)
        fn = _install_pallas(layout, tuple(b.shape for b in bufs),
                             n_buffers, interpret, donate)
    else:
        fn = _install_jit(layout, tuple(b.shape for b in bufs), donate,
                          None, codec)
    return list(fn(batch_leaves, bufs, rows,
                   jnp.asarray(slots, jnp.int32)))


def install_slot(layout: PageLayout, batch_leaves, single_leaves, slot,
                 *, donate: bool = False):
    """Jitted single-slot cache install (the fused ``_slot_cache_set``):
    one dispatch, traced slot index, optional donation of the batch
    leaves.  Returns the new leaf list in tree-flatten order."""
    batch_leaves = tuple(batch_leaves)
    single_leaves = tuple(single_leaves)
    if len(batch_leaves) != len(layout.leaves) or \
            len(single_leaves) != len(layout.leaves):
        raise ValueError("leaf count != layout")
    donate = donate and _can_donate(batch_leaves)
    fn = _slot_set_jit(layout, donate)
    return list(fn(batch_leaves, single_leaves,
                   jnp.asarray(slot, jnp.int32)))

"""Kernel 1: dense phase-decomposed RLE/bit-unpack (Pallas).

The per-column Parquet decode path (io/device_parquet.py,
``expand_runs_matrix``) expands a hybrid RLE/bit-packed stream with
per-ELEMENT random work: a run-id lookup, four 4-byte window gathers
and ~5 run-metadata takes — ~9 gathers per element on a chip where
gathers run ~90M/s while dense vector ops stream at HBM bandwidth
(PERF.md round-4b cost model; "a dense phase-decomposed unpack is
future work").  This module is that future work:

  phase 0  ``unpack_bits`` — the whole packed byte buffer unpacks as
           ONE dense w-wide bitstring: bytes -> little-endian u32
           words -> per-value static (word, shift) slots.  A Pallas
           kernel over value blocks; ZERO gathers.
  phase 1  run metadata broadcasts to elements as two step functions
           (A = dense-index offset, C = RLE value*2+flag) via
           delta-scatter + cumsum — vector ops, zero gathers (the
           io/parquet_fused.py general-path formulation).
  phase 2  ``_expand`` — a Pallas kernel computes ``dense[A + i]`` per
           element with the step functions resident per block: ONE
           gather per element, into a dense value array.

Net: ~9 gathers/element -> 1 (``GATHERS_PER_ELEMENT`` below, asserted
by tests/test_kernels.py against the traced jaxpr of the XLA path).
The Pallas path also covers dictionary bit widths up to 32 — the XLA
window-gather path is capped at ``_MAX_W`` = 24 bits (4-byte window =
shift(<=7) + w), so widths 25-32 previously fell all the way back to
host Arrow decode; under ``kernel.backend=pallas`` they stay on
device (the per-kernel-fallback cliff the motivation cites).

Arbitrarily large dense-value buffers STREAM through the expand kernel
(kernels/tiling.py): the grid gains a second dimension over fixed-size
dense tiles (``kernel.pallas.tileBytes``), the output block stays
VMEM-resident across the tile sweep, and each tile's gather runs only
under ``pl.when`` when some element of the block actually indexes into
it — the dense index of a hybrid stream is monotone non-decreasing, so
almost every (block, tile) cell skips.  This replaced the PR 9 64 MiB
``dense_too_large`` residency fallback; tile volume is observable as
``kernel.pallas.tiles.decode.expand``.

Fallback matrix (reasons land in
``kernel.backend.pallas.fallbacks.decode.*``): mixed bit widths within
one stream, values too wide for the i32 step function, or shapes off
the 32-value alignment grid.  Everything unsupported takes the
existing XLA (or host) path for that stream only.
"""

from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from spark_rapids_tpu.kernels import backend as kb
from spark_rapids_tpu.kernels import tiling

# by-construction per-element gather counts of the two stream-expansion
# formulations (XLA's count is additionally measured from its traced
# jaxpr by tests/test_kernels.py and bench.py's kernels probe)
GATHERS_PER_ELEMENT = {"xla": 9, "pallas": 1}

_UNPACK_BLOCK = 8192      # base values per grid step (phase 0)
_EXPAND_BLOCK = 8192      # base elements per grid step (phase 2)


# ---------------------------------------------------------------------------
# phase 0: dense bit-unpack
# ---------------------------------------------------------------------------

def _unpack_xla(bytes_arr: jnp.ndarray, w: int, ncap: int) -> jnp.ndarray:
    """Reference XLA unpack — the exact ``io/parquet_fused``
    formulation (moved here so both backends share one definition and
    the fused decode routes through the backend switch)."""
    if w == 1:
        bits = ((bytes_arr[:, None] >>
                 jnp.arange(8, dtype=jnp.uint8)) & 1)      # [B, 8]
        return bits.reshape(-1).astype(jnp.uint32)
    if ncap % 32 == 0 and bytes_arr.shape[0] % 4 == 0:
        words = (bytes_arr.reshape(-1, 4).astype(jnp.uint32) <<
                 jnp.arange(0, 32, 8, dtype=jnp.uint32)[None, :]
                 ).sum(axis=1, dtype=jnp.uint32)           # LE u32 words
        W = words.reshape(ncap // 32, w)
        mask = jnp.uint32((1 << w) - 1)
        outs = []
        for j in range(32):
            a, s = (j * w) >> 5, (j * w) & 31
            v = W[:, a] >> jnp.uint32(s)
            if s + w > 32:
                v = v | (W[:, a + 1] << jnp.uint32(32 - s))
            outs.append(v & mask)
        return jnp.stack(outs, axis=1).reshape(-1)
    bits = ((bytes_arr[:, None] >>
             jnp.arange(8, dtype=jnp.uint8)) & 1)          # [B, 8]
    vals = bits.reshape(ncap, w).astype(jnp.uint32)
    return jnp.sum(vals << jnp.arange(w, dtype=jnp.uint32)[None, :],
                   axis=1)


def _unpack_body(w: int):
    """Pallas kernel body for one block of 32-value groups, laid out
    one group per LANE: ``w_ref`` is [w, L] (word a of every group in
    row a), ``o_ref`` is [32, L] (value j of every group in row j).
    Static (word, shift) slots per output row — bit-identical integer
    math to ``_unpack_xla``'s word path, zero gathers, and only
    row-sliced 2-D vector ops (the chip's compiler refuses the 1-D
    byte->word reshape and the strided column picks of a [groups, w]
    layout)."""
    def kernel(w_ref, o_ref):
        mask = jnp.uint32((1 << w) - 1)
        for j in range(32):
            a, s = (j * w) >> 5, (j * w) & 31
            v = w_ref[a, :] >> jnp.uint32(s)
            if s + w > 32:
                v = v | (w_ref[a + 1, :] << jnp.uint32(32 - s))
            o_ref[j, :] = v & mask
    return kernel


def _unpack_block(ncap: int) -> int:
    """Adaptive phase-0 block: pow2, grows with ncap (bounded grid —
    a 16M-value buffer is a 128-cell grid, not 2048) while staying on
    the 32-value alignment the (word, shift) slot table needs."""
    return tiling.plan("decode.unpack", ncap, 1, 1, _UNPACK_BLOCK).block


def _unpack_pallas(bytes_arr: jnp.ndarray, w: int,
                   ncap: int) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    B = min(ncap, _unpack_block(ncap))
    G, L = ncap // 32, B // 32        # 32-value groups: total / block
    # bytes -> LE u32 words and the group-per-lane transposes are plain
    # dense XLA ops around the kernel
    b4 = bytes_arr.reshape(-1, 4).astype(jnp.uint32)
    words = (b4[:, 0] | (b4[:, 1] << jnp.uint32(8)) |
             (b4[:, 2] << jnp.uint32(16)) | (b4[:, 3] << jnp.uint32(24)))
    out = pl.pallas_call(
        _unpack_body(w),
        grid=(G // L,),
        # i32 row index: under x64 a Python 0 traces as i64, which the
        # chip's compiler refuses in an index map
        in_specs=[pl.BlockSpec((w, L), lambda i: (jnp.int32(0), i))],
        out_specs=pl.BlockSpec((32, L), lambda i: (jnp.int32(0), i)),
        out_shape=jax.ShapeDtypeStruct((32, G), jnp.uint32),
        interpret=kb.interpret(),
    )(words.reshape(G, w).T)
    return out.T.reshape(-1)


def _unpack_supported(w: int, ncap: int, nbytes: int) -> bool:
    return (1 <= w <= 32 and ncap % 32 == 0 and
            ncap % min(ncap, _unpack_block(ncap)) == 0 and
            nbytes == ncap * w // 8 and nbytes % 4 == 0)


def unpack_bits(bytes_arr: jnp.ndarray, w: int, ncap: int,
                backend: Optional[str] = None) -> jnp.ndarray:
    """Dense phase-0 unpack of one width's packed byte buffer to
    [ncap] uint32 — the backend switch for every caller (the fused
    whole-batch decode's per-width phase 0 and this module's phase 0).
    Integer-exact on both backends, so results are bit-identical by
    construction."""
    bk = kb.choose("decode.unpack", kb.resolve(backend),
                   _unpack_supported(w, ncap, bytes_arr.shape[0]),
                   reason="shape")
    if bk == kb.PALLAS:
        return _unpack_pallas(bytes_arr, w, ncap)
    return _unpack_xla(bytes_arr, w, ncap)


# ---------------------------------------------------------------------------
# phase 2: run expansion (one gather/element, dense tiles streamed)
# ---------------------------------------------------------------------------

def _expand_body(B: int, T: int, dlen: int):
    """2D-grid kernel body: element block i against dense tile j.

    The output block is VMEM-resident across the whole tile sweep
    (its index map ignores j): j == 0 writes the RLE lanes and zeros,
    each tile then overwrites exactly the bit-packed lanes whose
    (clipped) dense index falls inside it — the index is unique per
    lane, so accumulation is a plain masked select, and the gather is
    ``pl.when``-elided for tiles no lane of this block references
    (monotone dense indices make that the overwhelming case)."""
    from jax.experimental import pallas as pl

    def kernel(d_ref, a_ref, c_ref, o_ref):
        base = pl.program_id(0) * B
        j = pl.program_id(1)
        i = jax.lax.broadcasted_iota(jnp.int32, (B, 1), 0)[:, 0] + base
        a = a_ref[:]
        c = c_ref[:]
        rle = (c & 1) != 0
        # the clip mirrors the untiled formulation exactly: padding
        # lanes ride the last run's step function past dlen and land
        # (clipped) in the final tile, same value as before tiling
        idx = jnp.clip(a + i, 0, dlen - 1)

        @pl.when(j == 0)
        def _():
            o_ref[:] = jnp.where(rle, (c >> 1).astype(jnp.uint32),
                                 jnp.uint32(0))

        lo = j * T
        in_tile = jnp.logical_not(rle) & (idx >= lo) & (idx < lo + T)

        @pl.when(jnp.any(in_tile))
        def _():
            local = jnp.clip(idx - lo, 0, T - 1).astype(jnp.int32)
            vals = jnp.take(d_ref[:], local)   # the ONE per-element
            #                                    gather, tile-resident
            o_ref[:] = jnp.where(in_tile, vals, o_ref[:])
    return kernel


def _expand_pallas(dense: jnp.ndarray, a: jnp.ndarray, c: jnp.ndarray,
                   cap: int,
                   p: "tiling.TilePlan | None" = None) -> jnp.ndarray:
    from jax.experimental import pallas as pl
    dlen = dense.shape[0]
    if p is None:
        p = tiling.plan("decode.expand", cap, dlen, 4, _EXPAND_BLOCK)
    B, T = p.block, p.tile
    if p.src_pad != dlen:
        # ragged final tile: pad the dense buffer to the tile grid (a
        # dense device-side pad); pad lanes are reachable only through
        # the clip, which in_tile already restricts to < dlen
        dense = jnp.pad(dense, (0, p.src_pad - dlen))
    return pl.pallas_call(
        _expand_body(B, T, dlen),
        grid=(cap // B, p.n_tiles),
        in_specs=[pl.BlockSpec((T,), lambda i, j: (j,)),
                  pl.BlockSpec((B,), lambda i, j: (i,)),
                  pl.BlockSpec((B,), lambda i, j: (i,))],
        out_specs=pl.BlockSpec((B,), lambda i, j: (i,)),
        out_shape=jax.ShapeDtypeStruct((cap,), jnp.uint32),
        interpret=kb.interpret(),
    )(dense, a, c)


# ---------------------------------------------------------------------------
# host prep + public stream expansion
# ---------------------------------------------------------------------------

def stream_width(runs) -> Tuple[bool, int, str]:
    """(supported, width, reason): a stream is Pallas-expandable when
    its bit-packed runs share one NONZERO width <= 32.

    Width-0 bit-packed runs (a page written against a 1-entry
    dictionary) occupy zero packed bytes and decode to constant 0, so
    they don't constrain the dense width — ``_dense_meta`` rewrites
    them as RLE-0 runs.  Treating the accumulated 0 as "no width yet"
    while ALSO letting a 0-width run read ``bit_bases[i]//w`` would
    alias the NEXT run's packed values (a confirmed wrong-results
    repro), hence the explicit rewrite."""
    w = 0
    for i in range(len(runs.counts)):
        if runs.is_rle[i]:
            continue
        wi = int(runs.widths[i])
        if wi == 0:
            continue        # zero packed bytes; rewritten to RLE-0
        if w and wi != w:
            return False, 0, "mixed_widths"
        w = wi
        if wi > 32:
            return False, 0, "width"
    return True, w, ""


def _dense_meta(runs, w: int, rcap: int) -> np.ndarray:
    """Per-run (start, dA, dC) deltas — the step-function coefficients
    phase 1 scatters (O(runs) host work, like ``_upload_runs``).  ``A``
    carries through RLE runs so deltas telescope (the
    ``io/parquet_fused._stream_quads`` trick).  The matrix widens to
    int64 when a wide RLE payload (w approaching 32) overflows the i32
    step function — the kernel handles either dtype."""
    n = len(runs.counts)
    rows = []
    pos = 0
    prev_a = prev_c = 0
    lo = hi = 0
    for i in range(n):
        start = pos
        pos += int(runs.counts[i])
        if runs.is_rle[i]:
            a = prev_a
            c = (int(runs.values[i]) << 1) | 1
        elif int(runs.widths[i]) == 0:
            # width-0 bit-pack: zero packed bytes, every value is 0 —
            # an RLE-0 run (its bit_base//w would alias the NEXT run's
            # values; see stream_width)
            a = prev_a
            c = 1
        else:
            valoff = int(runs.bit_bases[i]) // w if w else 0
            a = valoff - start
            c = 0
        rows.append((start, a - prev_a, c - prev_c))
        lo = min(lo, rows[-1][1], rows[-1][2])
        hi = max(hi, rows[-1][1], rows[-1][2])
        prev_a, prev_c = a, c
    np_t = np.int32 if -(1 << 31) <= lo and hi < (1 << 31) else np.int64
    mat = np.zeros((rcap, 3), dtype=np_t)
    mat[n:, 0] = np_t(1 << 30)          # padding rows: clipped + dropped
    for i, r in enumerate(rows):
        mat[i] = r
    return mat


def _expand_impl(w: int, ncap: int, cap: int, plan=None):
    """Device half of the Pallas stream expansion (jitted once per
    (w, ncap, cap, interpret, block, tile) via the kernel cache).
    ``plan`` is the tile plan the CALLER keyed the kernel on — trace
    time must use exactly that geometry, not a fresh read of the
    process tileBytes knob."""
    def run(mat: jnp.ndarray, packed: jnp.ndarray) -> jnp.ndarray:
        if w:
            dense = _unpack_pallas(packed, w, ncap)
        else:
            # 0-bit streams (single-entry dictionary): every bit-packed
            # value is 0 by definition; no dense phase at all
            dense = jnp.zeros((32,), jnp.uint32)
        # delta-scatter + cumsum step functions (zero gathers); the
        # meta dtype widens to i64 only for wide RLE payloads, and the
        # cumsum sits at jit TOP LEVEL — never inside control flow
        # (the scoped-VMEM pair-lowering landmine, exec/scans.py)
        starts = jnp.minimum(mat[:, 0], cap)
        a = jnp.cumsum(jnp.zeros((cap,), mat.dtype).at[starts].add(
            mat[:, 1], mode="drop"))
        c = jnp.cumsum(jnp.zeros((cap,), mat.dtype).at[starts].add(
            mat[:, 2], mode="drop"))
        return _expand_pallas(dense, a, c, cap, p=plan)
    return run


def expand_stream(runs, packed: bytes, cap: int,
                  backend: Optional[str] = None) -> jnp.ndarray:
    """Expand one hybrid RLE/bit-packed stream to [cap] uint32 on the
    selected backend (the per-column decode path's backend switch —
    io/device_parquet.decode_plan).

    Pallas: dense phase decomposition above, ONE gather/element, two
    uploads (run matrix + packed bytes — transfer parity with the XLA
    path).  XLA: the existing ``expand_runs_matrix`` window-gather
    formulation (~9 gathers/element), which additionally REQUIRES
    w <= ``_MAX_W`` (24) — wider streams raise ``UnsupportedChunk`` so
    the column takes the host-Arrow fallback, exactly as before this
    module existed."""
    from spark_rapids_tpu.columnar.batch import bucket_rows
    from spark_rapids_tpu.exec import kernel_cache as kc
    from spark_rapids_tpu.io import device_parquet as dp

    def xla_path():
        wmax = max((int(x) for x, r in zip(runs.widths, runs.is_rle)
                    if not r), default=0)
        if wmax > dp._MAX_W:
            # the XLA 4-byte-window formulation can't reach past 24
            # bits; raising keeps the pre-pallas per-column host
            # fallback behavior
            raise dp.UnsupportedChunk(f"dict bit width {wmax}")
        dev = dp._upload_runs(runs, packed)
        return dp._expand_runs_packed(dev["runs_mat"], dev["packed"],
                                      cap=cap)

    if kb.resolve(backend) != kb.PALLAS:
        # default path exits before any eligibility work: the support
        # walk below is O(runs) host time that only the pallas
        # decision consumes
        return xla_path()

    ok, w, reason = stream_width(runs)
    nvals = sum(int(c) for c, r in zip(runs.counts, runs.is_rle)
                if not r)
    ncap = bucket_rows(max(nvals, 1), 32)
    if ok and w:
        ok = _unpack_supported(w, ncap, ncap * w // 8)
        reason = reason or "shape"
    # tile plan for the dense gather source (the streaming replacement
    # for the retired 64 MiB dense_too_large residency gate); its
    # block/tile shapes join the kernel key — derived from tier-
    # bucketed caps + the process tileBytes, so keys stay coarse
    p = tiling.plan("decode.expand", cap, max(ncap, 32) if w else 32,
                    4, _EXPAND_BLOCK)
    if ok:
        ok = cap % p.block == 0
        reason = reason or "shape"
    bk = kb.choose("decode.expand", kb.PALLAS, ok,
                   reason=reason or "unsupported")
    if bk != kb.PALLAS:
        return xla_path()
    kb.record_tiles("decode.expand", p.n_tiles, p.tile_nbytes)

    rcap = bucket_rows(max(len(runs.counts), 1), 8)
    mat = _dense_meta(runs, w, rcap)
    pbytes = np.frombuffer(bytes(packed), dtype=np.uint8)
    packed_dev = jnp.asarray(dp._pad_np(pbytes, max(ncap * w // 8, 4)))
    kern = kc.get_kernel(
        ("decode_expand", kb.PALLAS, w, rcap, ncap, cap,
         str(mat.dtype), kb.interpret(), p.block, p.tile),
        lambda: _expand_impl(w, ncap, cap, plan=p),
        backend=kb.PALLAS)
    return kern(jnp.asarray(mat), packed_dev)

"""jit'd public wrappers around the Pallas kernels.

These handle the "any shape of matrices" property the paper advertises
(Fig. 3 deliberately uses non-sweet-spot dims).  GEMM tile plans fit each
extent exactly where they can (`pick_blocks`: every block divides its
extent at the hardware alignment or spans it whole), so the kernel runs on
the operands as they are.  An extent no such block fits is zero-padded up
to a block multiple and the result sliced back: zero padding is exact for
GEMM (0-rows/cols contribute 0), and the epilogue runs on padded columns
whose outputs the slice discards.  For attention, key padding is masked
exactly via the kernel's ``kv_len`` operand (zero keys would NOT be
softmax-neutral) and padded query rows are sliced off.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as flash_kernel
from repro.kernels import flash_decode as decode_kernel
from repro.kernels import gemm as gemm_kernel
from repro.kernels.common import im2col


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


# Double-buffered VMEM working set target (~half of a 16 MiB/core VMEM).
_VMEM_BUDGET = 8 * 1024 * 1024
# The GEMM kernels' budget for `_working_set`: Mosaic's 16 MiB scoped VMEM
# limit over the most it allocates per byte of that model.  Float32 at
# HIGHEST keeps split operands and partial products beside the tiles:
# compiles for a described v5e need 1.6-2.4x the model (the (676, 768, 256)
# plan of a 26x26 3x3 conv over three K steps 16.2 MiB for 6.8 modeled,
# and refused; Darknet-19's largest, (392, 1152, 256), 14.1 for 6.5).
_GEMM_VMEM_BUDGET = 6.5 * 1024 * 1024

# Alignment of each GEMM block dimension (bm, bk, bn): bm counts sublanes
# (8), bk and bn count lanes (128).  Mosaic takes a block dimension that is
# aligned or that spans its array's whole dimension.
_ALIGN = (8, 128, 128)

# Block caps (bm, bk, bn) per op.  bmm runs smaller blocks: the batch grid
# dimension multiplies the working set's live tiles.
_CAPS = {"matmul": (256, 2048, 256), "bmm": (128, 256, 128)}


def _working_set(bm: int, bk: int, bn: int, itemsize: int) -> int:
    """Bytes resident in VMEM for one grid step: double-buffered x/w tiles
    plus the fp32 accumulator and output tile, each laid out in whole
    (8, 128) tiles (a full-extent block of 27 lanes occupies 128)."""
    rm, rn = _round_up(bm, 8), _round_up(bn, 128)
    x_tile = rm * _round_up(bk, 128)
    w_tile = _round_up(bk, 8) * rn
    return 2 * (x_tile + w_tile) * itemsize + 2 * rm * rn * 4


def _exact_blocks(extent: int, align: int, cap: int) -> list[int]:
    """Blocks that tile ``extent`` with no padding, in order of preference:
    the aligned divisors up to ``cap``, largest first, headed by the whole
    extent when it fits under the cap or when the best divisor under the
    cap is less than half of it (a full extent is legal at any size)."""
    within = [b for b in range(min(extent, cap) // align * align, 0, -align)
              if extent % b == 0]
    if extent <= cap or not within or within[0] < cap // 2:
        return [extent] + [b for b in within if b != extent]
    return within


def pick_blocks(m: int, k: int, n: int, dtype,
                caps: tuple[int, int, int] = _CAPS["matmul"]
                ) -> tuple[int, int, int]:
    """Exact tile plan for an (m, k, n) GEMM (pure function).

    Each block divides its extent at the hardware alignment (bm a multiple
    of 8, bk/bn of 128) or equals the whole extent, so the wrapper pads
    and slices nothing.  Among such plans: the first, in each axis's
    `_exact_blocks` order (bm outermost, bk innermost), whose
    double-buffered `_working_set` fits `_GEMM_VMEM_BUDGET` — large row blocks
    first, since they read the weights fewest times.  Where no exact plan
    fits, the axes that cannot be tiled exactly fall back to the padded
    pick of `padded_blocks`, each on its own.

    Callers go through the process-wide tile-plan cache in core/backends.py
    (keyed on (op, shapes, dtype, backend)) rather than invoking this
    per call; `_cached_blocks` below routes the default path there too.
    """
    itemsize = jnp.dtype(dtype).itemsize
    exact = [_exact_blocks(d, a, c) for d, a, c in zip((m, k, n), _ALIGN,
                                                        caps)]
    padded = padded_blocks(m, k, n, dtype, caps)
    # First every axis exact; then each axis may take its padded pick.
    for prefs in (exact, [e + [p] for e, p in zip(exact, padded)]):
        for bm in prefs[0]:
            for bn in prefs[2]:
                for bk in prefs[1]:
                    if (_working_set(bm, bk, bn, itemsize)
                            <= _GEMM_VMEM_BUDGET):
                        return bm, bk, bn
    return padded


def padded_blocks(m: int, k: int, n: int, dtype,
                  caps: tuple[int, int, int] = _CAPS["matmul"]
                  ) -> tuple[int, int, int]:
    """Aligned block heuristic that pads every extent to a block multiple:
    bm/bn the aligned extent up to its cap, bk grown by doubling while the
    working set stays under budget.  The GEMM backward's picker
    (`default_gemm_bwd_blocks`) and the fallback of `pick_blocks`."""
    itemsize = jnp.dtype(dtype).itemsize
    cap_m, cap_k, cap_n = caps
    bm = min(_round_up(m, 8), cap_m)
    bn = min(_round_up(n, 128), cap_n)
    bk = 128
    while bk < cap_k:
        nxt = bk * 2
        if (_working_set(bm, nxt, bn, itemsize) > _GEMM_VMEM_BUDGET
                or nxt > _round_up(k, 128)):
            break
        bk = nxt
    return bm, bk, bn


def _op_caps(op: str) -> tuple[int, int, int]:
    return _CAPS["bmm" if op == "bmm" else "matmul"]


def default_blocks(op: str, m: int, k: int, n: int, dtype
                   ) -> tuple[int, int, int]:
    """Per-op heuristic pick: `pick_blocks` under the op's block caps."""
    return pick_blocks(m, k, n, dtype, _op_caps(op))


def gemm_padding(m: int, k: int, n: int, tiles: tuple[int, int, int]
                 ) -> tuple[int, int, int]:
    """The extents the GEMM kernel runs on under a (bm, bk, bn) plan: each
    extent rounded up to its block (equal to (m, k, n) for an exact
    plan)."""
    return tuple(_round_up(d, t) for d, t in zip((m, k, n), tiles))


def validate_gemm_tiles(m: int, k: int, n: int, dtype,
                        tiles: tuple) -> list[str]:
    """Static legality of a (bm, bk, bn) plan for an (m, k, n) GEMM.

    The conditions the tiled kernels assume (the trace linter's R004 and
    pinned-tile engines check plans against this): three positive
    ints; each block aligned (bm a multiple of 8 sublanes, bk/bn multiples
    of the 128-lane width) or equal to its whole extent, the only other
    block shape Mosaic takes; the double-buffered `_working_set` under the
    VMEM budget; and no tile longer than its padded problem extent (the
    grid would schedule pure-padding steps).  Returns problem strings;
    empty means legal.
    """
    if len(tiles) != 3 or not all(
            isinstance(t, int) and not isinstance(t, bool) and t > 0
            for t in tiles):
        return [f"plan {tiles!r} is not three positive ints (bm, bk, bn)"]
    problems = []
    for name, tile, dim, align, unit in zip(
            ("bm", "bk", "bn"), tiles, (m, k, n), _ALIGN,
            ("8 sublanes", "the 128-lane width", "the 128-lane width")):
        if tile % align and tile != dim:
            problems.append(f"{name}={tile} is not a multiple of {unit} "
                            f"nor the full extent {dim}")
        if tile > _round_up(dim, align):
            problems.append(f"{name}={tile} exceeds the padded problem "
                            f"extent {_round_up(dim, align)} (dead grid "
                            f"steps)")
    ws = _working_set(*tiles, jnp.dtype(dtype).itemsize)
    if ws > _GEMM_VMEM_BUDGET:
        problems.append(f"working set {ws} B exceeds the VMEM budget "
                        f"{_GEMM_VMEM_BUDGET:.0f} B")
    return problems


def validate_attention_tiles(sq: int, skv: int, d: int, dtype,
                             tiles: tuple, *, bwd: bool = False) -> list[str]:
    """Static legality of a (bq, bk) sequence-tile plan for a flash
    attention problem (q length sq, key length skv, head_dim d).

    Same contract as `validate_gemm_tiles`: alignment (bq multiple of 8,
    bk multiple of 128), the grouped-KV working set under the VMEM budget
    (`_attention_bwd_working_set` when ``bwd`` — the backward keeps three
    fp32 score tiles and the dK/dV accumulators live), and tiles no
    longer than the padded sequence extents.  Returns problem strings.
    """
    if len(tiles) != 2 or not all(
            isinstance(t, int) and not isinstance(t, bool) and t > 0
            for t in tiles):
        return [f"plan {tiles!r} is not two positive ints (bq, bk)"]
    bq, bk = tiles
    problems = []
    if bq % 8:
        problems.append(f"bq={bq} is not a multiple of 8 sublanes")
    if bk % 128:
        problems.append(f"bk={bk} is not a multiple of the 128-lane width")
    working_set = (_attention_bwd_working_set if bwd
                   else _attention_working_set)
    ws = working_set(bq, bk, d, jnp.dtype(dtype).itemsize)
    if ws > _VMEM_BUDGET:
        which = "backward " if bwd else ""
        problems.append(f"{which}working set {ws} B exceeds the VMEM "
                        f"budget {_VMEM_BUDGET} B")
    if bq > _round_up(sq, 8):
        problems.append(f"bq={bq} exceeds the padded query extent "
                        f"{_round_up(sq, 8)} (dead grid steps)")
    if bk > _round_up(skv, 128):
        problems.append(f"bk={bk} exceeds the padded key extent "
                        f"{_round_up(skv, 128)} (dead grid steps)")
    return problems


# ---------------------------------------- implicit-patch conv tiles ---
# A stride-1 KxK convolution (K > 1) runs the fused GEMM on patches formed
# in VMEM from a halo window of its input (`gemm_kernel.conv_gemm`) rather
# than on im2col's KH*KW-fold matrix in HBM.  Its plan is (bm, bn): bm
# output rows of one image's flat layout (`gemm_kernel.conv_layout`),
# bn output columns.  The rule gives () where the convolution keeps
# im2col: a 1x1 (its im2col is a reshape), a stride above 1, Cin below
# IMPLICIT_MIN_CIN (the RGB stems), a dtype other than 32-bit (the taps
# sit at unaligned offsets of 32-bit sublanes), a batch above 1 of
# images with fewer flat output rows than the MXU has rows, or no plan
# under the GEMM's VMEM budget.  A row block never spans two images, so
# there each image streams the weights through the MXU for one short
# block where the im2col GEMM runs the batch's rows as one, and the input
# saved is small: Darknet-19's 7x7 512->1024 layers at batch 8 took
# 179 us implicit against 161 us im2col on a TPU v5e, and 32.5 against
# 43.4 us at batch 1.

# Cin from which the implicit path runs: the narrowest Cin of the
# published networks' stride-1 3x3 convolutions past their stems.  Below
# 128 it forms one (bm, K*K*Cin) patch tile per block, so the MXU sees the
# im2col GEMM's dot.  On a TPU v5e this beat im2col at every Cin 32/64
# shape of the networks, by 1.5x to 2.6x (per-tap dots at K = Cin ran
# 1.6x to 2.2x slower than the patch tile).
IMPLICIT_MIN_CIN = 32
_MXU_ROWS = 128
# Row-block cap: a window reads (K - 1) * (Wp + 1) halo rows beside its
# bm, 42 % more at bm 1024 for a 208-wide 3x3.
_CONV_BM_CAP = 1024


def _conv_working_set(bm: int, bn: int, c: int, kk: int, halo: int,
                      itemsize: int) -> int:
    """Bytes resident in VMEM for one implicit-conv grid step: the
    double-buffered input window (bm + halo rows), weight block (kk, Cin,
    bn) and output block, plus the fp32 dot result and the patch tile
    (one tap where Cin fills the MXU depth) the dot reads; lanes padded
    to 128 and rows to 8, as in `_working_set`."""
    rm, rn = _round_up(bm, 8), _round_up(bn, 128)
    window = _round_up(bm + halo, 8) * _round_up(c, 128)
    weights = kk * _round_up(c, 8) * rn
    patch = rm * _round_up(kk * c if c % 128 else c, 128)
    return 2 * (window + weights + rm * rn) * itemsize + (rm * rn
                                                          + patch) * 4


def default_conv_implicit_blocks(x_shape: tuple, n: int, size: int,
                                 stride: int, pad: int, dtype) -> tuple:
    """Implicit-patch plan (bm, bn) of a conv2d, or () where it keeps
    im2col (see above).  bm splits one image's flat output rows
    (`gemm_kernel.conv_layout`): first the largest multiple of 8 that
    divides them within `_CONV_BM_CAP` and at least half of it (or all
    rows, within the cap), which leaves no rows past them to slice off;
    then the fewest even blocks of at most the cap, more while the working
    set does not fit.  bn: the `_exact_blocks` column blocks, largest
    first."""
    b, h, w, c = x_shape
    itemsize = jnp.dtype(dtype).itemsize
    if size == 1 or stride != 1 or c < IMPLICIT_MIN_CIN or itemsize != 4:
        return ()
    _, rows, halo = gemm_kernel.conv_layout(h, w, size, pad)
    if rows < _MXU_ROWS and b > 1:
        return ()
    bns = _exact_blocks(n, _ALIGN[2], _CAPS["matmul"][2])
    exact = [bm for bm in _exact_blocks(rows, _ALIGN[0], _CONV_BM_CAP)
             if bm <= _CONV_BM_CAP and (bm == rows
                                        or 2 * bm >= _CONV_BM_CAP)]
    nm0 = -(-rows // _CONV_BM_CAP)
    even = [rows if nm == 1 else _round_up(-(-rows // nm), 8)
            for nm in range(nm0, max(nm0, rows // 128) + 1)]
    for bm in exact[:1] + even:
        for bn in bns:
            if (_conv_working_set(bm, bn, c, size * size, halo, itemsize)
                    <= _GEMM_VMEM_BUDGET):
                return bm, bn
    return ()


def validate_conv_implicit_tiles(x_shape: tuple, n: int, size: int,
                                 stride: int, pad: int, dtype,
                                 tiles: tuple) -> list[str]:
    """Static legality of an implicit-patch (bm, bn) plan for a conv2d
    with the engine's key shapes, as `validate_gemm_tiles` is for a GEMM:
    a stride-1 convolution of a 32-bit dtype; bm a multiple of 8 or all
    of one image's flat output rows, and no longer than them (padded to
    8); bn a multiple of 128 or N, dividing N; `_conv_working_set` under
    the VMEM budget.  Returns problem strings; empty means legal."""
    if len(tiles) != 2 or not all(
            isinstance(t, int) and not isinstance(t, bool) and t > 0
            for t in tiles):
        return [f"plan {tiles!r} is not two positive ints (bm, bn)"]
    bm, bn = tiles
    _, h, w, c = x_shape
    itemsize = jnp.dtype(dtype).itemsize
    problems = []
    if stride != 1 or itemsize != 4:
        return [f"an implicit-patch plan needs stride 1 and a 32-bit "
                f"dtype, not stride {stride} {jnp.dtype(dtype)}"]
    _, rows, halo = gemm_kernel.conv_layout(h, w, size, pad)
    if bm % 8 and bm != rows:
        problems.append(f"bm={bm} is not a multiple of 8 sublanes nor the "
                        f"image's {rows} flat output rows")
    if bm > _round_up(rows, 8):
        problems.append(f"bm={bm} exceeds the image's padded flat output "
                        f"rows {_round_up(rows, 8)} (dead grid steps)")
    if (bn % 128 and bn != n) or n % bn:
        problems.append(f"bn={bn} is not a multiple of the 128-lane width "
                        f"dividing N={n}, nor N")
    ws = _conv_working_set(bm, bn, c, size * size, halo, itemsize)
    if ws > _GEMM_VMEM_BUDGET:
        problems.append(f"working set {ws} B exceeds the VMEM budget "
                        f"{_GEMM_VMEM_BUDGET:.0f} B")
    return problems


# ------------------------------------------------ GEMM backward tiles ---
# The custom-VJP backward kernels (kernels/gemm.py) re-tile the two
# backward GEMMs — dX = dY . W^T and dW = X^T . dY — as problems in their
# own right, keyed ("gemm_bwd", (variant, rows, contraction, cols), dtype,
# backend) where the dims are the BACKWARD problem's own (m, k, n) (so the
# generic (bm, bk, bn) machinery applies verbatim).  Variants: "dx"/"dw"
# for matmul-shaped calls, "bdx"/"bdw" for bmm.  Keys resolve lazily at
# backward-trace time — inference never touches them.

GEMM_BWD_VARIANTS = ("dx", "dw", "bdx", "bdw")

# Re-exported: maps an engine-layout (m, k, n) to a variant's own
# (rows, contraction, cols) — callers building "gemm_bwd" keys use it.
gemm_bwd_problem = gemm_kernel.gemm_bwd_problem


def _gemm_bwd_base_op(variant: str) -> str:
    if variant not in GEMM_BWD_VARIANTS:
        raise ValueError(f"unknown gemm_bwd variant {variant!r}; expected "
                         f"one of {GEMM_BWD_VARIANTS}")
    return "bmm" if variant.startswith("b") else "matmul"


def default_gemm_bwd_blocks(variant: str, rows: int, kdim: int, cols: int,
                            dtype) -> tuple[int, int, int]:
    """Heuristic (bm, bk, bn) for a backward GEMM: the aligned padding
    heuristic (`padded_blocks`) on the backward problem's own (rows,
    contraction, cols), under the op's block caps (the bmm caps for the
    batched "bdx"/"bdw" variants: the batch grid dim multiplies live
    tiles).  The backward pads its operands to the plan's multiples."""
    return padded_blocks(rows, kdim, cols, dtype,
                         _op_caps(_gemm_bwd_base_op(variant)))


# ------------------------------------------------- attention (bq, bk) ---
# The attention op tiles by SEQUENCE, not (bm, bk, bn): (bq, bk) are the
# query/key tile lengths the flash kernel streams through VMEM.  The same
# tile-plan cache covers them — only the dims and the working-set formula
# differ.

def attention_dims(shapes: tuple) -> tuple[int, int, int, int, int, int]:
    """Normalize the attention cache-key shapes ``(q_shape, k_shape)`` —
    q: (B, Sq, H, D), k: (B, Skv, KV, D) — to (b, sq, skv, h, kv, d)."""
    (b, sq, h, d), (_, skv, kv, _) = shapes
    return b, sq, skv, h, kv, d


def _attention_working_set(bq: int, bk: int, d: int, itemsize: int) -> int:
    """Bytes resident in VMEM for one attention grid step, with the
    GROUPED KV footprint: all G query heads of a group read the same
    (bk, d) K/V tile, so exactly one double-buffered K and V tile is live
    regardless of the group size.  Adds the fp32 (bq, bk) score tile, the
    lane-replicated (m, l) statistics, and the fp32 accumulator."""
    q_out = 2 * 2 * bq * d * itemsize          # double-buffered q + out tile
    kv = 2 * 2 * bk * d * itemsize             # double-buffered k and v
    scores = bq * bk * 4
    stats = 2 * bq * 128 * 4 + bq * d * 4      # m, l (lane-replicated) + acc
    return q_out + kv + scores + stats


def _default_seq_blocks(sq: int, skv: int, d: int, dtype, working_set,
                        bq_start: int, bk_start: int) -> tuple[int, int]:
    """Shared (bq, bk) heuristic walk for the forward and backward
    attention tilings: MXU-aligned (bq multiple of 8 sublanes, bk multiple
    of 128 lanes), clamped to the padded problem so short sequences never
    pad past one tile, shrunk while `working_set` exceeds the VMEM
    budget."""
    itemsize = jnp.dtype(dtype).itemsize
    bq = min(_round_up(sq, 8), bq_start)
    bk = min(_round_up(skv, 128), bk_start)
    while bk > 128 and working_set(bq, bk, d, itemsize) > _VMEM_BUDGET:
        bk //= 2
    while bq > 8 and working_set(bq, bk, d, itemsize) > _VMEM_BUDGET:
        bq = _round_up(bq // 2, 8)
    return bq, bk


def default_attention_blocks(b: int, sq: int, skv: int, h: int, kv: int,
                             d: int, dtype) -> tuple[int, int]:
    """Heuristic forward (bq, bk) pick under the grouped-KV working set
    (`_attention_working_set`)."""
    return _default_seq_blocks(sq, skv, d, dtype, _attention_working_set,
                               256, 512)


# -------------------------------------------- attention backward tiles ---
# The custom-VJP backward kernels (flash_attention.py) re-tile the same
# padded problem with their own (bq, bk): the backward working set is
# larger (q, dO, k, v, dK, dV tiles plus THREE fp32 score-sized tiles are
# live per grid step), so the forward winner is usually too big.  Backward
# tiles get their own key — ("attention_bwd", (q_shape, k_shape), dtype,
# backend) — resolved lazily at backward-trace time, so inference never
# touches them.

def _attention_bwd_working_set(bq: int, bk: int, d: int,
                               itemsize: int) -> int:
    """VMEM bytes for one backward grid step, grouped-KV footprint: the
    double-buffered q/dO (query side) and k/v/dK/dV (kv side) tiles, the
    per-row lse/delta operands, the fp32 p/dp/ds score tiles, and the
    fp32 gradient accumulators (dQ on the dQ grid, dK+dV on the kv grid —
    budgeted together since both kernels must fit)."""
    q_like = 2 * 2 * bq * d * itemsize          # double-buffered q + dO
    kv_like = 2 * 4 * bk * d * itemsize         # k, v and the dK/dV outs
    rows = 2 * 2 * bq * 4                       # lse + delta (fp32)
    scores = 3 * bq * bk * 4                    # p, dp, ds (fp32)
    acc = (bq * d + 2 * bk * d) * 4             # dQ | dK/dV accumulators
    return q_like + kv_like + rows + scores + acc


def default_attention_bwd_blocks(b: int, sq: int, skv: int, h: int, kv: int,
                                 d: int, dtype) -> tuple[int, int]:
    """Heuristic backward (bq, bk): the shared walk, started smaller
    (128, 256) and shrunk under the backward working-set formula
    (`_attention_bwd_working_set`)."""
    return _default_seq_blocks(sq, skv, d, dtype,
                               _attention_bwd_working_set, 128, 256)


# ----------------------------------------- attention decode formulation ---
# Short-query/long-KV problems (a decode step against a deep cache) leave
# the forward kernel's (B*H, Sq/bq, Skv/bk) grid with B*H programs — most
# of the chip idles while each crawls the whole KV extent.  The split-KV
# decode kernel (kernels/flash_decode.py) instead grids over
# (B*H, n_splits) independent KV spans, each emitting a partial (o, lse)
# combined by the logsumexp merge.  Its (bk_split, n_splits) tiles ride
# the same tile-plan cache under their own lazy key —
# ("attention_decode", (q_shape, k_shape), dtype, backend) — resolved
# only when a dispatch actually selects the decode formulation, so
# prefill/training never touches decode keys.

# The decode formulation engages when the query is no longer than a
# single sublane tile AND the key extent is deep enough that splitting
# the reduction beats one pass (below this, the forward kernel's grid is
# already fine and the merge would be pure overhead).
DECODE_MAX_SQ = 8
DECODE_MIN_SKV = 256


def use_decode_formulation(sq: int, skv: int) -> bool:
    """Whether an (Sq, Skv) attention dispatch is decode-shaped: Sq within
    one 8-row sublane tile and the KV extent at/above DECODE_MIN_SKV."""
    return sq <= DECODE_MAX_SQ and skv >= DECODE_MIN_SKV


def _attention_decode_working_set(bk: int, d: int, itemsize: int) -> int:
    """VMEM bytes for one decode grid step: the grouped-KV forward
    working set at the fixed 8-row query tile (the padded decode query)
    plus the fp32 partial (o, lse) block."""
    return (_attention_working_set(DECODE_MAX_SQ, bk, d, itemsize)
            + DECODE_MAX_SQ * (d + 1) * 4)


def default_attention_decode_blocks(b: int, sq: int, skv: int, h: int,
                                    kv: int, d: int, dtype
                                    ) -> tuple[int, int]:
    """Heuristic (bk_split, n_splits): a 256-key block (clamped to the
    padded extent), then enough splits that each span still covers at
    least two blocks — more splits than that trades streaming efficiency
    for parallelism the (b*h) grid axis may already provide."""
    itemsize = jnp.dtype(dtype).itemsize
    bk = min(_round_up(skv, 128), 256)
    while bk > 128 and _attention_decode_working_set(
            bk, d, itemsize) > _VMEM_BUDGET:
        bk //= 2
    skvp = _round_up(skv, 128)
    n_splits = max(1, min(8, skvp // (2 * bk)))
    return bk, n_splits


def validate_attention_decode_tiles(sq: int, skv: int, d: int, dtype,
                                    tiles: tuple) -> list[str]:
    """Static legality of a (bk_split, n_splits) decode plan: two positive
    ints, bk_split 128-lane aligned and no longer than the padded key
    extent, n_splits small enough that every span holds at least one live
    block, the working set under the VMEM budget.  Same contract as
    `validate_gemm_tiles`: problem strings, empty means legal."""
    if len(tiles) != 2 or not all(
            isinstance(t, int) and not isinstance(t, bool) and t > 0
            for t in tiles):
        return [f"plan {tiles!r} is not two positive ints "
                f"(bk_split, n_splits)"]
    bk, ns = tiles
    problems = []
    if bk % 128:
        problems.append(f"bk_split={bk} is not a multiple of the 128-lane "
                        f"width")
    if bk > _round_up(skv, 128):
        problems.append(f"bk_split={bk} exceeds the padded key extent "
                        f"{_round_up(skv, 128)} (dead grid steps)")
    if ns > max(1, -(-skv // bk)):
        problems.append(f"n_splits={ns} leaves empty spans for Skv={skv} "
                        f"at bk_split={bk} (dead programs)")
    ws = _attention_decode_working_set(bk, d, jnp.dtype(dtype).itemsize)
    if ws > _VMEM_BUDGET:
        problems.append(f"decode working set {ws} B exceeds the VMEM "
                        f"budget {_VMEM_BUDGET} B")
    return problems


@functools.partial(
    jax.jit, static_argnames=("causal", "bk_split", "n_splits", "interpret"))
def attention_decode(q, k, v, kv_len=None, sm_scale=None, *,
                     causal: bool = True, bk_split: int = 0,
                     n_splits: int = 0, interpret: bool | None = None):
    """Split-KV flash-decoding attention, arbitrary sequence lengths.

    Same operand contract as `attention` — q (B, Sq, H, D), k/v compact
    grouped (B, Skv, KV, D), optional scalar/(B,) ``kv_len``, traced
    ``sm_scale`` folded into q — but computed by the split-KV kernel:
    ``n_splits`` programs per (batch, head) each reduce one KV span to a
    partial (o, lse), merged by the logsumexp combine.  The key extent is
    zero-padded up to an (n_splits * bk_split) multiple and masked via
    ``kv_len`` exactly like the forward wrapper pads to ``bk``.

    Inference-only (no VJP): the registry selects this formulation for
    decode-shaped dispatches (`use_decode_formulation`), which are never
    differentiated — training geometries take the custom-VJP forward
    kernel.  Fully-masked rows (kv_len == 0) return exact 0, never NaN;
    partials and the merge stay fp32 for every operand dtype.
    """
    validate_attention_shapes(q, k, v)
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    if not (bk_split and n_splits):
        bk_split, n_splits = _cached_attention_decode_blocks(
            (q.shape, k.shape), q.dtype)
    sqp = _round_up(sq, 8)
    skvp = _round_up(skv, bk_split * n_splits)
    kvl = normalize_kv_len(kv_len, b, skv)
    if kvl is None:
        kvl = jnp.full((b, 1), skv, jnp.int32)   # mask the key padding
    qt = q.transpose(0, 2, 1, 3)                 # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                 # (B, KV, Skv, D)
    vt = v.transpose(0, 2, 1, 3)
    scale = (jnp.float32(1.0 / (d ** 0.5)) if sm_scale is None
             else jnp.asarray(sm_scale, jnp.float32))
    qt = (qt.astype(jnp.float32) * scale).astype(q.dtype)
    if sqp != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skvp != skv:
        pad = ((0, 0), (0, 0), (0, skvp - skv), (0, 0))
        kt, vt = jnp.pad(kt, pad), jnp.pad(vt, pad)
    o = decode_kernel.flash_decode(
        qt, kt, vt, kvl, causal=causal, sm_scale=1.0, bk=bk_split,
        n_splits=n_splits, q_len=sq, interpret=interpret)
    return o[:, :, :sq].transpose(0, 2, 1, 3).astype(q.dtype)


def _cached_attention_decode_blocks(shapes: tuple, dtype
                                    ) -> tuple[int, int]:
    """Default (bk_split, n_splits) pick, resolved through the registry's
    tile-plan cache under the lazy ("attention_decode",
    (q_shape, k_shape), dtype, "pallas") key."""
    from repro.core import backends
    return backends.get_backend("pallas").tiles("attention_decode", shapes,
                                                dtype)


def validate_attention_shapes(q, k, v) -> None:
    """Grouped-layout contract checks shared by `ComputeEngine.attention`
    and the direct `attention` wrapper: q (B, Sq, H, D), k/v (B, Skv, KV, D)
    with KV <= H, H % KV == 0, matching dtypes.  Raises ValueError with the
    offending shapes/dtypes instead of failing deep inside a kernel."""
    if q.ndim != 4 or k.ndim != 4:
        raise ValueError(f"attention expects 4-D (B, S, heads, head_dim) "
                         f"operands; got q {q.shape}, k {k.shape}")
    if k.shape != v.shape:
        raise ValueError(f"k/v shapes differ: {k.shape} vs {v.shape}")
    b, _, h, d = q.shape
    kb, _, kvh, kd = k.shape
    if kb != b or kd != d:
        raise ValueError(f"q {q.shape} and k {k.shape} disagree on "
                         "batch or head_dim")
    if kvh == 0 or kvh > h or h % kvh != 0:
        raise ValueError(
            f"grouped attention requires KV heads to evenly divide query "
            f"heads (KV <= H, H % KV == 0); got H={h}, KV={kvh}")
    if q.dtype != k.dtype or q.dtype != v.dtype:
        raise ValueError(f"q/k/v dtype mismatch: q={q.dtype}, k={k.dtype}, "
                         f"v={v.dtype}")


def validate_kv_len(kv_len, b: int) -> None:
    """Shape check for a kv_len argument: None, a python int, a scalar
    array, or a (B,) array (per-slot decode positions).  Raises ValueError
    on any other shape — shared by `ComputeEngine.attention` and the
    direct `attention` wrapper so the two entry points cannot drift."""
    if kv_len is None:
        return
    kvl = jnp.asarray(kv_len)
    if kvl.ndim > 1 or (kvl.ndim == 1 and kvl.shape[0] != b):
        raise ValueError(f"kv_len must be a scalar or ({b},) vector; got "
                         f"shape {kvl.shape}")


def normalize_kv_len(kv_len, b: int, skv: int):
    """Canonicalize a kv_len argument to (B, 1) int32 clamped to Skv, or
    None (see `validate_kv_len` for the accepted forms)."""
    if kv_len is None:
        return None
    validate_kv_len(kv_len, b)
    kvl = jnp.asarray(kv_len, jnp.int32)
    return jnp.minimum(jnp.broadcast_to(kvl.reshape(-1), (b,)),
                       skv).reshape(b, 1)


@functools.partial(
    jax.jit, static_argnames=("causal", "bq", "bk", "bq_bwd", "bk_bwd",
                              "interpret"))
def attention(q, k, v, kv_len=None, sm_scale=None, *, causal: bool = True,
              bq: int = 0, bk: int = 0, bq_bwd: int = 0, bk_bwd: int = 0,
              interpret: bool | None = None):
    """Grouped flash attention on the engine, arbitrary sequence lengths.

    q: (B, Sq, H, D); k, v: (B, Skv, KV, D) with KV <= H, H % KV == 0 —
    query head h attends kv-head h // (H // KV), with NO caller-side
    broadcast.  Sequences are zero-padded up to (bq, bk) multiples, the
    kernel masks padded keys via ``kv_len``, and padded query rows are
    sliced off.  ``kv_len`` (scalar or (B,)) masks keys at/beyond the given
    per-batch length, clamped to Skv — decode passes its cache extent
    pos+1.  ``sm_scale`` may be traced (a learned temperature).  Causal
    queries right-align against the LIVE key extent: the real (unpadded)
    Skv, or ``kv_len`` when given (chunked prefill into a larger cache
    buffer).  Fully-masked query rows return exact 0.

    DIFFERENTIABLE end-to-end: the kernel carries a custom VJP, and this
    wrapper's pad/slice are gradient-transparent (the slice VJP zero-fills
    padded-row cotangents; the pad VJP slices padded-key gradients off, and
    the synthesized ``kv_len`` masks padded keys inside the backward
    kernels too).  ``bq_bwd``/``bk_bwd`` pin the backward tiles; 0 resolves
    them at backward-trace time from the lazy ``"attention_bwd"``
    tile-plan key — forward-only callers never touch that key.
    """
    validate_attention_shapes(q, k, v)
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    if not (bq and bk):
        bq, bk = _cached_attention_blocks((q.shape, k.shape), q.dtype)
    sqp, skvp = _round_up(sq, bq), _round_up(skv, bk)
    kvl = normalize_kv_len(kv_len, b, skv)
    if kvl is None and skvp != skv:
        kvl = jnp.full((b, 1), skv, jnp.int32)   # mask the key padding
    qt = q.transpose(0, 2, 1, 3)                 # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                 # (B, KV, Skv, D)
    vt = v.transpose(0, 2, 1, 3)
    # sm_scale is a traced value (a learned temperature works on every
    # backend): fold it into q in fp32 and run the kernel unscaled.
    scale = (jnp.float32(1.0 / (d ** 0.5)) if sm_scale is None
             else jnp.asarray(sm_scale, jnp.float32))
    qt = (qt.astype(jnp.float32) * scale).astype(q.dtype)
    if sqp != sq:
        qt = jnp.pad(qt, ((0, 0), (0, 0), (0, sqp - sq), (0, 0)))
    if skvp != skv:
        pad = ((0, 0), (0, 0), (0, skvp - skv), (0, 0))
        kt, vt = jnp.pad(kt, pad), jnp.pad(vt, pad)
    o = flash_kernel.flash_attention(
        qt, kt, vt, causal=causal, sm_scale=1.0, bq=bq, bk=bk,
        bq_bwd=bq_bwd, bk_bwd=bk_bwd, bwd_key=(q.shape, k.shape),
        kv_len=kvl, q_offset=skv - sq, q_len=sq, interpret=interpret)
    return o[:, :, :sq].transpose(0, 2, 1, 3)


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def attention_partial(q, k, v, kv_len, sm_scale=None, *, causal: bool = True,
                      interpret: bool | None = None):
    """Forward-only partial attention over ONE KV span: returns (o, lse).

    The sequence-split building block (kernels/sharded.py): q (B, Sq, H, D)
    against a LOCAL key span k/v (B, Skv, KV, D), where ``kv_len`` is the
    GLOBAL live extent minus this span's start offset — it may exceed Skv
    (the extent ends beyond this span: every local key is live) or be <= 0
    (the span is entirely beyond the extent: all rows fully masked).
    Causal queries right-align against that same relative extent — the
    kernel's dynamic ``q_offset = kv_len - Sq`` reproduces the global
    diagonal span-locally — so kv_len is deliberately NOT clamped to Skv;
    the (bq, bk) tiles are clamped to divisors of (Sq, Skv) instead, so no
    key padding exists for an oversized kv_len to unmask.

    Returns ``o`` (B, H, Sq, D) span-normalized in q.dtype and ``lse``
    (B, H, Sq) fp32 with the -1e30 empty-span sentinel on fully-masked
    rows — exactly the per-span contract of `flash_decode.combine`, which
    merges partials across spans (or devices, after an all-gather).
    Inference-only, like the split-KV decode kernel."""
    validate_attention_shapes(q, k, v)
    b, sq, h, d = q.shape
    _, skv, kvh, _ = k.shape
    bq, bk = _cached_attention_blocks((q.shape, k.shape), q.dtype)
    if sq % bq:
        bq = math.gcd(sq, bq)
    if skv % bk:
        bk = math.gcd(skv, bk)
    validate_kv_len(kv_len, b)
    kvl = jnp.broadcast_to(jnp.asarray(kv_len, jnp.int32).reshape(-1), (b,))
    qt = q.transpose(0, 2, 1, 3)                 # (B, H, Sq, D)
    kt = k.transpose(0, 2, 1, 3)                 # (B, KV, Skv, D)
    vt = v.transpose(0, 2, 1, 3)
    scale = (jnp.float32(1.0 / (d ** 0.5)) if sm_scale is None
             else jnp.asarray(sm_scale, jnp.float32))
    qt = (qt.astype(jnp.float32) * scale).astype(q.dtype)
    o, lse = flash_kernel.flash_attention_with_lse(
        qt, kt, vt, causal=causal, sm_scale=1.0, bq=bq, bk=bk,
        kv_len=kvl.reshape(b, 1), q_len=sq, interpret=interpret)
    # The kernel stores lse == 0 for fully-masked rows; the combine needs
    # the empty-span sentinel there.  Row liveness is analytic: some key is
    # live iff kv_len > 0 and (non-causal, or the row's causal extent
    # kv_len - Sq + i reaches key 0).
    rows = jnp.arange(sq)[None, :]               # (1, Sq)
    live = kvl[:, None] > 0                      # (B, Sq)
    if causal:
        live = live & (rows >= sq - kvl[:, None])
    lse = jnp.where(live[:, None, :], lse, decode_kernel.EMPTY_SPAN_LSE)
    return o, lse


def _cached_attention_blocks(shapes: tuple, dtype) -> tuple[int, int]:
    """Default (bq, bk) pick for direct `attention` calls, resolved through
    the registry's tile-plan cache under the same ("attention",
    (q_shape, k_shape), dtype, "pallas") key engine dispatch uses."""
    from repro.core import backends
    return backends.get_backend("pallas").tiles("attention", shapes, dtype)


def _cached_blocks(op: str, m: int, k: int, n: int, dtype
                   ) -> tuple[int, int, int]:
    """Default block pick, resolved through the registry's tile-plan
    cache (same picker and cache key as engine dispatch, so both paths
    agree).

    Imported lazily: core/backends.py imports this module at load time, and
    by the time a kernel wrapper actually executes the registry is loaded.
    """
    from repro.core import backends
    return backends.get_backend("pallas").tiles(op, (m, k, n), dtype)


@functools.partial(
    jax.jit,
    static_argnames=("act", "out_dtype", "bm", "bk", "bn", "interpret",
                     "bwd_dx", "bwd_dw"))
def matmul(x, w, scale=None, shift=None, *, act: str = "linear",
           out_dtype=None, bm: int = 0, bk: int = 0, bn: int = 0,
           interpret: bool | None = None, bwd_dx: tuple = (), bwd_dw: tuple = ()):
    """Fused GEMM on the compute engine, arbitrary (M, K) x (K, N).

    DIFFERENTIABLE end-to-end: the kernel carries a custom VJP (backward
    GEMM kernels under lazily-resolved ``"gemm_bwd"`` tile-plan keys — the
    unpadded (m, k, n) threads through as the key), and this wrapper's
    pad/slice (only where the plan does not tile an extent exactly) are
    gradient-transparent.  ``bwd_dx``/``bwd_dw`` pin the
    backward (bm, bk, bn) plans; () resolves them at backward-trace time.
    """
    m, k = x.shape
    _, n = w.shape
    out_dtype = out_dtype or x.dtype
    if not (bm and bk and bn):
        bm, bk, bn = _cached_blocks("matmul", m, k, n, x.dtype)
    mp, kp, np_ = gemm_padding(m, k, n, (bm, bk, bn))
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, kp - k), (0, np_ - n)))
    if np_ != n:
        scale = None if scale is None else jnp.pad(scale, (0, np_ - n))
        shift = None if shift is None else jnp.pad(shift, (0, np_ - n))
    out = gemm_kernel.gemm(x, w, scale=scale, shift=shift, act=act,
                           out_dtype=out_dtype, bm=bm, bk=bk, bn=bn,
                           interpret=interpret, bwd_key=(m, k, n),
                           bwd_dx=bwd_dx, bwd_dw=bwd_dw)
    return out if (mp, np_) == (m, n) else out[:m, :n]


@functools.partial(
    jax.jit, static_argnames=("out_dtype", "bm", "bk", "bn", "interpret",
                              "bwd_dx", "bwd_dw"))
def bmm(x, w, *, out_dtype=None, bm: int = 0, bk: int = 0, bn: int = 0,
        interpret: bool | None = None, bwd_dx: tuple = (), bwd_dw: tuple = ()):
    """Batched GEMM (B, M, K) @ (B, K, N) on the engine.

    DIFFERENTIABLE via the same custom-VJP machinery as `matmul` —
    backward keys are variant-tagged "bdx"/"bdw" (batch stays out of the
    key, like the forward "bmm" key).
    """
    b, m, k = x.shape
    _, _, n = w.shape
    out_dtype = out_dtype or x.dtype
    if not (bm and bk and bn):
        bm, bk, bn = _cached_blocks("bmm", m, k, n, x.dtype)
    mp, kp, np_ = gemm_padding(m, k, n, (bm, bk, bn))
    if (mp, kp) != (m, k):
        x = jnp.pad(x, ((0, 0), (0, mp - m), (0, kp - k)))
    if (kp, np_) != (k, n):
        w = jnp.pad(w, ((0, 0), (0, kp - k), (0, np_ - n)))
    out = gemm_kernel.bmm(x, w, out_dtype=out_dtype, bm=bm, bk=bk, bn=bn,
                          interpret=interpret, bwd_key=(m, k, n),
                          bwd_dx=bwd_dx, bwd_dw=bwd_dw)
    return out if (mp, np_) == (m, n) else out[:, :m, :n]


# ------------------------------------------- implicit-patch convolution ---

@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9, 10))
def _conv_implicit(x, w, scale, shift, size, pad, act, out_dtype, bm, bn,
                   interpret):
    return gemm_kernel.conv_gemm(x, w, scale, shift, size=size, pad=pad,
                                 act=act, out_dtype=out_dtype, bm=bm, bn=bn,
                                 interpret=interpret)


def _conv_implicit_fwd(x, w, scale, shift, *statics):
    return _conv_implicit(x, w, scale, shift, *statics), (x, w, scale, shift)


def _conv_implicit_bwd(size, pad, act, out_dtype, bm, bn, interpret, res,
                       dy):
    """The im2col formulation's VJP: col2im over the fused GEMM's
    backward kernels, as every other convolution trains (traced under the
    GEMM backward's dispatch scope, as those kernels are)."""
    def im2col_conv(x, w, scale, shift):
        cols = im2col(x, size, size, 1, pad)
        b, oh, ow, kk = cols.shape
        y = matmul(cols.reshape(-1, kk), w, scale, shift, act=act,
                   out_dtype=out_dtype, interpret=interpret)
        return y.reshape(b, oh, ow, -1)

    with jax.named_scope(gemm_kernel.GEMM_BWD_SCOPE):
        return jax.vjp(im2col_conv, *res)[1](dy)


_conv_implicit.defvjp(_conv_implicit_fwd, _conv_implicit_bwd)


@functools.partial(
    jax.jit, static_argnames=("size", "pad", "act", "out_dtype", "bm", "bn",
                              "interpret"))
def conv2d_implicit(x, w, scale=None, shift=None, *, size: int, pad: int,
                    act: str = "linear", out_dtype=None, bm: int, bn: int,
                    interpret: bool | None = None):
    """Fused stride-1 convolution act(conv(x, w) * scale + shift) with its
    patches formed in VMEM (`gemm_kernel.conv_gemm`): x (B, H, W, Cin), w
    (size*size*Cin, N) in (kh, kw, Cin) row order, scale/shift (N,) or
    None; returns (B, OH, OW, N).  (bm, bn) is the plan
    `default_conv_implicit_blocks` gives (the pallas backend's conv2d
    plan where the convolution takes this path).
    DIFFERENTIABLE: the custom VJP is the im2col formulation's.
    """
    return _conv_implicit(x, w, scale, shift, size, pad, act,
                          jnp.dtype(out_dtype or x.dtype), bm, bn, interpret)

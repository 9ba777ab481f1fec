"""Tile sizes the TPU's Pallas lowering accepts, sized to a VMEM budget.

Mosaic accepts a block only when each of its last two dimensions is a
multiple of the hardware tile (8 sublanes x 128 lanes for f32) or equals
the array's own dimension. ``legal_block`` turns any requested tile —
a caller's, a default, or whatever the tuning cache returns — into one
that satisfies this, so no tile size can reach the lowering unaligned.

``VMEM_BUDGET_BYTES`` is what a kernel's per-grid-step working set may
take: the double-buffered streamed blocks, the resident blocks, the f32
accumulators and the in-register temporaries. The kernels shrink their
tiles until their own byte model fits it (``fit_tiles``), and raise
(naming the shape and the budget) before lowering when even the smallest
legal tiles do not. ``VMEM_LIMIT_BYTES`` is the scoped-VMEM limit they
pass to the compiler: headroom over the budget for what the model does
not price.
"""
from __future__ import annotations

VMEM_BUDGET_BYTES = 16 * 2 ** 20
VMEM_LIMIT_BYTES = 32 * 2 ** 20

SUBLANE = 8
LANE = 128
MIN_WIDE_TILE = 512           # fit_tiles' first-pass floor


def legal_block(n: int, want: int, align: int) -> int:
    """Largest tile <= ``want`` that is a multiple of ``align``, or ``n``.

    A request at or past the dimension takes the whole dimension; a
    request below ``align`` rounds up to ``align`` (or to ``n`` when the
    dimension itself is smaller).
    """
    if want >= n:
        return n
    return min(n, max(align, want // align * align))


def round_up(n: int, align: int) -> int:
    return -(-n // align) * align


def fit_tiles(what: str, dims, tiles, aligns, nbytes) -> tuple:
    """Legal tiles no larger than ``tiles`` whose ``nbytes(*tiles)`` fits
    ``VMEM_BUDGET_BYTES``.

    Tiles are halved in the given order: first none below ``MIN_WIDE_TILE``
    (so the MXU keeps wide operands while anything else can give), then
    down to their alignment. Raises, naming ``what`` and the budget, when
    the smallest legal tiles still do not fit.
    """
    tiles = [legal_block(n, t, a) for n, t, a in zip(dims, tiles, aligns)]
    for lo in (MIN_WIDE_TILE, 0):
        for i, (n, a) in enumerate(zip(dims, aligns)):
            while nbytes(*tiles) > VMEM_BUDGET_BYTES:
                smaller = legal_block(n, tiles[i] // 2, a)
                if smaller >= tiles[i] or smaller < lo:
                    break
                tiles[i] = smaller
    if nbytes(*tiles) > VMEM_BUDGET_BYTES:
        raise ValueError(
            f"{what}: even the smallest legal tiles {tuple(tiles)} need "
            f"{nbytes(*tiles)} B of VMEM, over the {VMEM_BUDGET_BYTES} B "
            "budget")
    return tuple(tiles)

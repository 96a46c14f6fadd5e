"""Single source of truth for on-chip kernel budgets and tile clamps.

Every constant that prices a Pallas kernel's on-chip residency — and
every helper that derives a block shape from one — lives here and
nowhere else.  PR 5 shipped with the fused kernel's ``m_blk`` clamp
duplicated between ``rotseq_batched/ops.py`` and the registry cost
guard, coupled only by a comment ("mirror the kernel wrapper's clamp");
the analyzer rule RA403/RA404 (``repro.analysis``) now *enforces* that
budget constants and clamp helpers are defined in this module and
imported everywhere else, so the cost model can never silently price a
kernel off a stale copy of its own limits.

No jax imports: this module is pure host arithmetic, importable from
the registry (which must stay cheap to import) and from every kernel
wrapper without ordering constraints.
"""
from __future__ import annotations

__all__ = [
    "SUBLANES", "LANES", "SMEM_BYTES", "SMEM_PANEL_BUDGET",
    "FACTOR_VMEM_BUDGET", "FACTOR_TILES_PER_STEP", "FACTOR_PAD_SHARE",
    "round_up", "clamp_m_blk", "smem_panel_bytes", "factor_tile_dims",
    "factor_tile_bytes", "factor_tiles_per_step",
]

# TPU sublane count: block shapes keep the second-minor dimension a
# multiple of this so Mosaic never pads a tile internally.
SUBLANES = 8

# TPU lane count: Mosaic refuses a block whose minor dimension is neither
# a multiple of this nor the whole array dimension.
LANES = 128

# Scalar memory of one TPU v5e core.  Compiling for a v5e chip refuses
# SMEM windows past it ("would exceed memory (size=1048576)").
SMEM_BYTES = 2**20

# SMEM bytes the fused rotseq_batched kernel's C/S/G panel windows may
# take: all of SMEM but a reserve for its (1, 1, K) wave windows and
# plane counter.  Compiling for v5e admits panels of n=336, K<=128 at
# exactly this bound and refuses n=344 by 12 KB.  It also bounds the
# kernel's VMEM: n <= 337 keeps an (n, 256) float32 slab under 345 KB.
SMEM_PANEL_BUDGET = SMEM_BYTES - 16 * 2**10

# VMEM the rotseq_factors kernel's double-buffered blocks may take: 12
# MiB of the 16 MiB that Mosaic scopes for a kernel on a v5e by default,
# the rest left to the loop's temporaries.
FACTOR_VMEM_BUDGET = 12 * 2**20

# Tiles the rotseq_factors kernel may build per grid step, largest first.
FACTOR_TILES_PER_STEP = (8, 4, 2, 1)

# The no-op tiles that pad a band of T tiles to whole grid steps stay
# within T / FACTOR_PAD_SHARE: past two tiles a step, doubling the tiles
# saved at most ~11% of a tile's issue slots in the loop body as Mosaic
# schedules it for a v5e (64/32, 4 -> 8 tiles; PERF.md, section 6).
FACTOR_PAD_SHARE = 8


def round_up(x: int, mult: int) -> int:
    """``x`` rounded up to the next multiple of ``mult``."""
    return ((x + mult - 1) // mult) * mult


def clamp_m_blk(m: int, m_blk: int) -> int:
    """Clamp a lane-tile request to the target's (sublane-padded) rows.

    Never tile (and pad) wider than the target: small serve-bucket rows
    would otherwise pay ``m_blk`` lanes of identity work per plane.
    Multiples of :data:`SUBLANES` keep sublane alignment; use 128+ on
    hardware.  Both the ``rotseq_batched`` wrapper and the registry cost
    guard call this one definition, so the kernel the cost model prices
    is the kernel that actually launches.
    """
    return min(m_blk, round_up(max(1, m), SUBLANES))


def smem_panel_bytes(J: int, K: int) -> int:
    """SMEM the fused kernel's three ``(1, J, K)`` panel windows take.

    SMEM holds 32-bit words and pads a window's last two dimensions to
    ``(SUBLANES, LANES)``; each window is double-buffered.
    """
    return (3 * 2 * round_up(max(1, J), SUBLANES)
            * round_up(max(1, K), LANES) * 4)


def factor_tile_dims(n_b: int, k_b: int):
    """``(steps, lanes, w, P)`` of one tile in the factor kernel: its
    waves are ``(steps, lanes)`` (anti-diagonals rounded up to a sublane
    tile, waves to a lane tile), each parity of its factor ``(w, P)``."""
    half = -(-(n_b + k_b - 1) // 2)
    return (round_up(n_b + k_b - 1, SUBLANES), round_up(k_b, LANES),
            n_b + k_b, round_up(half + k_b, LANES))


def factor_tile_bytes(n_b: int, k_b: int) -> int:
    """VMEM one tile's blocks take in the factor kernel: three float32
    wave blocks in, two factor blocks out, each double-buffered."""
    steps, lanes, w, P = factor_tile_dims(n_b, k_b)
    return 2 * 4 * (3 * steps * lanes + 2 * w * P)


def factor_tiles_per_step(T: int, n_b: int, k_b: int) -> int:
    """Tiles per grid step of the factor kernel, for ``T`` tiles.

    One tile's anti-diagonals form a chain of dependent steps; the tiles
    of one grid step run their chains side by side.  The most tiles of
    :data:`FACTOR_TILES_PER_STEP` win whose blocks fit
    :data:`FACTOR_VMEM_BUDGET` and whose last step pads at most ``T /
    FACTOR_PAD_SHARE`` tiles, never more than ``T``: 8 for 16 or 1025
    tiles, 4 for 41, where 8 would pad 7.
    """
    fit = [b for b in FACTOR_TILES_PER_STEP
           if b <= max(1, T) and -T % b * FACTOR_PAD_SHARE <= T
           and b * factor_tile_bytes(n_b, k_b) <= FACTOR_VMEM_BUDGET]
    return fit[0] if fit else 1

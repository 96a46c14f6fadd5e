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
    "round_up", "clamp_m_blk", "smem_panel_bytes",
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

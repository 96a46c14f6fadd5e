"""sweep_roofline.apply: the GEMM sweep's share of its roofline.

The least time the chip could take for the work of the flushes launched
in the traced window (the driver's ``least_s``: flops and bytes at the
published peaks) over the device seconds of the ``rotseq.sweep`` phase,
which the driver read from the trace (``bench/xspace.py``) and passed
in its ``layer`` as ``phases``.  The other phases and the probe are
left out, so this is the sweep's own share.  Nothing where the layer
carries no such phase.  Moves ``rot_gflops``.
"""

SWEEP = "rotseq.sweep"


def read(view):
    layer = view.layer or {}
    sweep_s = (layer.get("phases") or {}).get(SWEEP)
    if not sweep_s or not layer.get("least_s"):
        return None
    return 100.0 * layer["least_s"] / sweep_s

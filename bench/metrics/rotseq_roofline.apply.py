"""rotseq_roofline.apply: the rotation application's share of its roofline.

The least time the chip could take for the algorithm's work in the
traced window (``bench/work.py``: flops and bytes of every application
launched there, whichever backend ran it, at the published peaks) over
the device's busy time for them: the benchmark's own probe programs are
not counted as busy.  Moves ``rot_gflops``.
"""


def read(view):
    r, layer = view.reduction, view.layer
    if r is None or not layer.get("least_s"):
        return None
    busy = r.busy_s
    if layer.get("exclude_programs"):
        busy -= r.program_seconds(layer["exclude_programs"])
    if busy <= 0:
        return None
    return 100.0 * layer["least_s"] / busy

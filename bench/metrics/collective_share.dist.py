"""collective_share.dist: the share of the traced window that the chips
spend in collective operations (``all-reduce``, ``all-gather``,
``reduce-scatter``, ``collective-permute``, ``all-to-all`` and their
start/done halves), averaged over the chips.  A row-sharded plan
exchanges nothing and reads near 0; a replicated or resharded plan
shows here.  Moves ``rot_gflops``."""

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def read(view):
    r = view.reduction
    if r is None or r.window_s <= 0:
        return None
    seconds = sum(s for name, s in r.ops.items()
                  if name.startswith(COLLECTIVES))
    return 100.0 * seconds / max(1, r.devices) / r.window_s

"""Backend registry + cost-model dispatch for rotation-sequence application.

Every backend registers a :class:`BackendSpec` (capability record, §6
memory-operation cost model split into per-sequence *setup* and per-row
*stream* terms, tile-candidate generator); ``select_plan`` ranks the
eligible (backend, tile) candidates by modeled cost and memoizes the
winning :class:`Plan` per problem key in this process.  The choice is a
pure function of the :class:`Problem`: nothing is timed, read from disk
or borrowed from another shape.  :class:`Problem.shared_sequence`
distinguishes one sequence amortized over a batch from the serving
path's one-sequence-per-request batches, which pay setup × b.

The full pricing derivation (every backend's flop/memop/setup formula,
the per-request correction, and a worked batch-64 example) lives in
``docs/cost-model.md``; ``docs/architecture.md`` places this module in
the registry → sequence → serve → stream layer diagram.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Callable, Dict, List, Optional, Tuple

from repro import compat, obs

__all__ = [
    "REAL_DTYPES", "COMPLEX_DTYPES",
    "Hardware", "Problem", "Plan", "Capability", "BackendSpec",
    "register", "get_backend", "registered_methods", "eligible_backends",
    "no_tiles", "blocked_tiles", "accumulated_tiles",
    "pallas_wave_tiles", "pallas_mxu_tiles", "rotseq_batched_tiles",
    "pinned_tiles", "select_plan", "plan_cache_stats", "clear_plan_cache",
    "cost_components",
]


# hardware table lives in the jax-free repro.hw (shared with the
# roofline report); re-exported here for registry users
from repro.hw import Hardware, peaks, planning_peaks  # noqa: E402
from repro.kernels.limits import (LANES, SMEM_PANEL_BUDGET,  # noqa: E402
                                  smem_panel_bytes)

# Pallas interpret mode executes the kernel body op-by-op on the host —
# orders of magnitude off compiled speed.  Off-TPU the pallas backends
# remain *eligible* (interpret_ok) but carry this penalty, so "auto"
# never picks them while explicit method="pallas_*" still works.
_INTERPRET_PENALTY = 1e3


# --------------------------------------------------------------------------
# problem / plan records
# --------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class Problem:
    """Shape/dtype/platform key of one application ``A (m,n) <- k waves``.

    ``batch`` counts independent ``(m, n)`` targets served by one call
    (the serving path's shape buckets, or a batched accumulator).
    Rotations act row-wise, so a shared-sequence batch flattens to a
    ``(batch*m, n)`` problem: streaming traffic and sweep flops scale
    with the batch while per-sequence setup work (accumulating tile
    factors ``Q_t``, packing sheared tiles) is paid once — which is why
    ``method="auto"`` can pick a different backend at ``batch=64`` than
    at ``batch=1``.

    ``shared_sequence`` says whether those ``batch`` targets share one
    rotation sequence (the default — a batched accumulator flush) or
    each carry their own (the serving path's per-request buckets, via
    ``apply_batched(A, sequences=...)``).  Per-request batches rebuild
    the per-sequence setup ``batch`` times, so the same shape can price
    — and plan — onto a different backend (see ``docs/cost-model.md``,
    "the per-request correction").
    """
    m: int
    n: int
    k: int
    dtype: str = "float32"
    platform: str = "cpu"
    signs: bool = False    # needs per-entry G support
    sharded: bool = False  # must be traceable inside shard_map
    batch: int = 1         # independent (m, n) targets per application
    # one sequence amortized over the batch (True) vs one sequence per
    # batch element (False, the serving path).  Irrelevant at batch=1.
    shared_sequence: bool = True
    # live (non-identity) planes in the (n-1, k) grid, when statically
    # known (RotationSequence.k_live): pad_to tails and seq.T staircase
    # padding make the live fraction tiny, which only plane-skipping
    # backends (rotseq_batched) can exploit — their cost scales with
    # live_planes while every other backend pays the full grid.
    live_planes: Optional[int] = None
    # mesh size of a sharded execution (repro.dist): shape fields above
    # stay *global* — per-shard row counts and the inter-device
    # communication term are derived from ``devices`` in the cost
    # models, never baked into ``m``.  Meaningful only with
    # ``sharded=True``; ``devices=1`` keeps every existing cost exact.
    devices: int = 1

    @property
    def itemsize(self) -> int:
        """Bytes of one real item: a complex target is priced as its
        real view, so ``complex64`` counts 4."""
        return {"float64": 8, "float32": 4, "bfloat16": 2,
                "float16": 2, "complex64": 4}.get(self.dtype, 4)

    @property
    def m_total(self) -> int:
        """Total real rows streamed per application (``batch * m``).

        A complex target counts twice: real rotations update its real
        and imaginary parts alike, so it is priced as its real view of
        ``2m`` rows."""
        views = 2 if self.dtype in COMPLEX_DTYPES else 1
        return self.m * max(1, self.batch) * views

    @property
    def sequences(self) -> int:
        """Distinct rotation sequences the application pays setup for."""
        if self.batch <= 1 or self.shared_sequence:
            return 1
        return self.batch

    @property
    def planes_total(self) -> int:
        """Planes in the full (n-1, k) grid (identity padding included)."""
        return max(0, self.n - 1) * self.k

    @property
    def planes_live(self) -> int:
        """Statically-known live planes (falls back to the full grid)."""
        if self.live_planes is None:
            return self.planes_total
        return min(self.live_planes, self.planes_total)

    @property
    def hardware(self) -> Hardware:
        """Peaks of the attached device when it is this platform's, else
        of the device a plan for the platform assumes (``repro.hw``);
        an unknown platform or device kind raises."""
        if self.platform == compat.default_platform():
            return peaks(compat.device_kind())
        return planning_peaks(self.platform)


@dataclasses.dataclass(frozen=True)
class Plan:
    """A dispatch decision: backend + tile parameters (+ model cost)."""
    method: str
    n_b: Optional[int] = None
    k_b: Optional[int] = None
    m_blk: Optional[int] = None
    est_seconds: float = float("inf")
    source: str = "model"  # "model": ranked by select_plan's cost model

    def kwargs(self) -> dict:
        kw = {}
        if self.n_b is not None:
            kw["n_b"] = self.n_b
        if self.k_b is not None:
            kw["k_b"] = self.k_b
        if self.m_blk is not None:
            kw["m_blk"] = self.m_blk
        return kw


# Target dtypes.  Real rotations apply to a complex target part by part,
# so the jnp backends take complex64 too; Mosaic has no complex type,
# so the Pallas backends list the real dtypes alone.
REAL_DTYPES = ("float32", "bfloat16", "float64", "float16")
COMPLEX_DTYPES = ("complex64",)


@dataclasses.dataclass(frozen=True)
class Capability:
    """What a backend can run; consulted before costing it."""
    dtypes: Tuple[str, ...] = REAL_DTYPES + COMPLEX_DTYPES
    platforms: Tuple[str, ...] = ("cpu", "gpu", "tpu")
    supports_signs: bool = True       # per-entry G (mixed rot/reflector)
    supports_sharding: bool = False   # callable inside shard_map
    tile_min: Tuple[int, int] = (1, 1)
    tile_max: Tuple[int, int] = (4096, 4096)
    needs_pallas: bool = False
    interpret_ok: bool = True
    # batched execution (SequencePlan.apply_batched): rotations act
    # row-wise, so a shared-sequence batch (b, m, n) flattens exactly to
    # (b*m, n); "vmap" instead maps the backend over the leading axis
    # (for kernels whose tiling assumptions are per-instance); "fused"
    # means the backend fn natively accepts a (b, m, n) target with
    # shared (n-1, K) or stacked (b, n-1, K) waves — one launch per
    # bucket (the rotseq_batched kernel).
    batch_via: str = "flatten"        # "flatten" | "vmap" | "fused"
    supports_vmap: bool = True        # jax.vmap-able over (A, C, S, G)


@dataclasses.dataclass(frozen=True)
class BackendSpec:
    name: str
    fn: Callable                       # (A, C, S, *, reflect, G, **plan_kw)
    capability: Capability
    cost: Callable[[Problem, Plan], float]
    candidates: Callable[[Problem], List[Plan]]
    doc: str = ""


_REGISTRY: Dict[str, BackendSpec] = {}


def register(spec: BackendSpec) -> BackendSpec:
    """Register (or replace) a backend spec under ``spec.name``."""
    _REGISTRY[spec.name] = spec
    return spec


def get_backend(name: str) -> BackendSpec:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise ValueError(
            f"unknown method {name!r}; one of {registered_methods()} "
            f"(or 'auto' via apply_rotation_sequence)"
        ) from None


def registered_methods() -> Tuple[str, ...]:
    return tuple(_REGISTRY)


def eligible_backends(problem: Problem) -> List[BackendSpec]:
    """Backends whose capability record admits ``problem``."""
    out = []
    for spec in _REGISTRY.values():
        cap = spec.capability
        if problem.dtype not in cap.dtypes:
            continue
        if problem.platform not in cap.platforms:
            # Pallas backends stay *eligible* off-platform when they can
            # run under the interpreter, but their cost carries the
            # interpret penalty so "auto" never actually picks them.
            if not (cap.needs_pallas and cap.interpret_ok):
                continue
        if problem.signs and not cap.supports_signs:
            continue
        if problem.sharded and not cap.supports_sharding:
            continue
        out.append(spec)
    return out


# --------------------------------------------------------------------------
# cost models (paper SS6 memory-operation analysis)
# --------------------------------------------------------------------------

def _bands(k: int, k_b: int) -> int:
    return max(1, math.ceil(k / max(1, k_b)))


# latency floor keeps tiny problems from reading as free
_LATENCY_FLOOR = 2e-6


def _roofline_seconds(flop_term: float, byte_term: float) -> float:
    return max(flop_term, byte_term, _LATENCY_FLOOR)


# Each ``_components_*`` function returns the §6 traffic split into an
# explicit per-sequence **setup** term (building the accumulated path's
# Q_t factors, packing sheared tiles, streaming per-request wave panels
# — work proportional to the sequence, paid once per *distinct*
# sequence) and a per-row **stream** term (work proportional to the
# rows of A).  The returned totals are already scaled: setup terms are
# multiplied by ``Problem.sequences`` (1 for a shared-sequence batch,
# b for the serving path's per-request batches), which is the whole
# per-request pricing correction — see docs/cost-model.md.
_ZERO_SPLIT = {"setup_flops": 0.0, "setup_bytes": 0.0,
               "stream_flops": 0.0, "stream_bytes": 0.0}


def _split(setup_flops=0.0, setup_bytes=0.0,
           stream_flops=0.0, stream_bytes=0.0) -> Dict[str, float]:
    return {"setup_flops": float(setup_flops),
            "setup_bytes": float(setup_bytes),
            "stream_flops": float(stream_flops),
            "stream_bytes": float(stream_bytes)}


# ---------------------------------------------------------------------------
# inter-device communication term (repro.dist sharded executions)
# ---------------------------------------------------------------------------
#
# Row-sharded application (the ShardedSequencePlan fused path) keeps the
# rows of every shard independent — rotations act on column *pairs* — so
# the only wire traffic is replicating the C/S/G wave panels to every
# shard once per plan (a setup-side cost, per the PR 9 split).  The
# stream side of the wire is zero for row sharding; the CAQR-style
# column-panel path prices its per-panel boundary exchange separately in
# ``repro.dist.column_sharded_comm_bytes``.  A per-hop latency constant
# keeps tiny sharded problems from reading as free: broadcasting to D
# devices costs ~log2(D) link round-trips regardless of payload, which
# is exactly what makes ``method="auto"`` keep small-n problems
# replicated while large-n problems amortize the wire and go sharded.

_LINK_HOP_LATENCY = 5e-6


def _comm_components(p: Problem) -> Dict[str, float]:
    """Wire traffic + seconds of one sharded application (zero at D=1).

    ``setup_bytes`` is the wave-panel broadcast — 3 planes arrays
    (C/S/G) per *distinct* sequence, ``devices - 1`` copies leaving the
    source shard; ``stream_bytes`` is zero for the row-sharded fused
    path.  ``seconds`` prices the bytes at ``Hardware.link_bw`` plus
    ``ceil(log2(D))`` per-hop latencies.
    """
    D = max(1, p.devices)
    if not p.sharded or D <= 1:
        return {"setup_bytes": 0.0, "stream_bytes": 0.0, "bytes": 0.0,
                "hops": 0.0, "seconds": 0.0}
    panel = 3.0 * p.sequences * p.planes_total * p.itemsize
    setup_bytes = panel * (D - 1)
    hops = float(math.ceil(math.log2(D)))
    secs = setup_bytes / p.hardware.link_bw + hops * _LINK_HOP_LATENCY
    return {"setup_bytes": setup_bytes, "stream_bytes": 0.0,
            "bytes": setup_bytes, "hops": hops, "seconds": secs}


def _dist_terms(p: Problem) -> Tuple[float, float]:
    """``(stream_divisor, comm_seconds)`` of the problem's mesh.

    Per-row stream work divides across ``devices`` shards (each shard
    owns ``m_total / D`` rows); per-sequence setup work is replicated —
    every shard packs/accumulates the full sequence locally — so setup
    terms never divide.  The returned comm seconds are *additive* on
    top of the per-shard roofline.
    """
    D = max(1, p.devices)
    if not p.sharded or D <= 1:
        return 1.0, 0.0
    return float(D), _comm_components(p)["seconds"]


def _components_unoptimized(p: Problem, plan: Plan) -> Dict[str, float]:
    # Alg 1.2 touches nothing per-sequence beyond the C/S panel itself,
    # which is dominated by its 4-memop-per-rotation streaming.
    return _split(stream_flops=6.0 * p.m_total * p.n * p.k,
                  stream_bytes=4.0 * p.m_total * p.n * p.k * p.itemsize)


def cost_unoptimized(p: Problem, plan: Plan) -> float:
    """Alg 1.2: 4 memops per rotation, no reuse (paper SS6 baseline)."""
    hw = p.hardware
    c = _components_unoptimized(p, plan)
    D, comm_s = _dist_terms(p)
    return _roofline_seconds(c["stream_flops"] / hw.vpu_flops / D,
                             c["stream_bytes"] / hw.hbm_bw / D) + comm_s


def _components_wavefront(p: Problem, plan: Plan) -> Dict[str, float]:
    return _split(stream_flops=6.0 * p.m_total * p.n * p.k,
                  stream_bytes=2.0 * p.m_total * p.n * p.k * p.itemsize)


def cost_wavefront(p: Problem, plan: Plan) -> float:
    """Alg 1.3: wavefront fuses column touches to ~2 memops/rotation."""
    hw = p.hardware
    c = _components_wavefront(p, plan)
    D, comm_s = _dist_terms(p)
    return _roofline_seconds(c["stream_flops"] / hw.vpu_flops / D,
                             c["stream_bytes"] / hw.hbm_bw / D) + comm_s


def _tile_grid(p: Problem, n_b: int, k_b: int) -> Tuple[int, int, int]:
    """``(bands, tiles, w)`` of the sheared-tile decomposition (SS5)."""
    w = n_b + k_b
    bands = _bands(p.k, k_b)
    tiles = max(1, math.ceil((p.n + k_b - 1) / n_b))
    return bands, tiles, w


def _pack_bytes(p: Problem, n_b: int, k_b: int) -> float:
    """Per-sequence sheared-tile packing traffic (blocked/accumulated).

    Each band's ``k_b`` waves are packed into ``tiles`` sheared
    ``(w, k_b)`` tiles per wave array before any row of A moves: the
    raw ``(n-1, k)`` panels are read once and the padded tile buffers
    written once.  Signs add a third array.
    """
    bands, tiles, w = _tile_grid(p, n_b, k_b)
    arrays = 3 if p.signs else 2
    read = arrays * p.planes_total
    write = arrays * bands * tiles * w * k_b
    return (read + write) * p.itemsize


def _components_blocked(p: Problem, plan: Plan) -> Dict[str, float]:
    n_b = plan.n_b or 64
    k_b = plan.k_b or 16
    return _split(
        setup_bytes=p.sequences * _pack_bytes(p, n_b, k_b),
        stream_flops=6.0 * p.m_total * p.n * p.k,
        stream_bytes=2.0 * p.m_total * p.n * p.itemsize * _bands(p.k, k_b))


def cost_blocked(p: Problem, plan: Plan) -> float:
    """Blocked wavefront: A streams once per band of k_b waves (SS5)."""
    hw = p.hardware
    c = _components_blocked(p, plan)
    D, comm_s = _dist_terms(p)
    return _roofline_seconds(
        c["stream_flops"] / hw.vpu_flops / D,
        (c["setup_bytes"] + c["stream_bytes"] / D) / hw.hbm_bw) + comm_s


def _accumulated_flops(p: Problem, n_b: int, k_b: int) -> Tuple[float, float]:
    """(MXU sweep flops, per-sequence VPU accumulation flops).

    The GEMM sweep streams every row of every batched target
    (``m_total``); accumulating the tile factors ``Q_t`` happens once
    per *sequence* — amortized by a shared-sequence batch, multiplied
    by ``b`` on the serving path's per-request batches (the cliff
    ``docs/cost-model.md`` walks through at batch 64).
    """
    w = n_b + k_b
    bands, tiles, _ = _tile_grid(p, n_b, k_b)
    sweep = bands * tiles * 2.0 * p.m_total * w * w      # (m,w) @ (w,w)
    accum = bands * tiles * 6.0 * w * n_b * k_b          # Q_t = I rotated
    return sweep, accum


def _components_accumulated(p: Problem, plan: Plan) -> Dict[str, float]:
    n_b = plan.n_b or 128
    k_b = plan.k_b or 128
    sweep, accum = _accumulated_flops(p, n_b, k_b)
    bands, tiles, w = _tile_grid(p, n_b, k_b)
    q_bytes = bands * tiles * w * w * p.itemsize  # Q_t factors written
    return _split(
        setup_flops=p.sequences * accum,
        setup_bytes=p.sequences * (_pack_bytes(p, n_b, k_b) + q_bytes),
        stream_flops=sweep,
        stream_bytes=2.0 * p.m_total * p.n * p.itemsize * _bands(p.k, k_b))


def cost_accumulated(p: Problem, plan: Plan) -> float:
    """rs_gemm: ~4/3 extra flops (n_b = k_b) priced at matmul rate.

    The sweep GEMMs run at MXU rate; the per-sequence ``Q_t``
    accumulation is short-vector VPU work, multiplied by ``b`` for
    per-request batches.
    """
    hw = p.hardware
    c = _components_accumulated(p, plan)
    D, comm_s = _dist_terms(p)
    flop_term = (c["stream_flops"] / hw.mxu_flops / D
                 + c["setup_flops"] / hw.vpu_flops)
    return _roofline_seconds(
        flop_term,
        (c["setup_bytes"] + c["stream_bytes"] / D) / hw.hbm_bw) + comm_s


def _interpret_factor(p: Problem) -> float:
    return 1.0 if p.platform == "tpu" else _INTERPRET_PENALTY


def cost_pallas_wave(p: Problem, plan: Plan) -> float:
    """VPU kernel: blocked-wavefront traffic, carry pinned in VMEM.

    ``supports_vmap=False``: a per-request batch runs as ``b`` separate
    launches, so the latency floor multiplies by the sequence count.
    Comm seconds stay outside the kernel constant and the interpret
    penalty — the wire is neither fused nor interpreted.
    """
    D, comm_s = _dist_terms(p)
    return max(0.7 * (cost_blocked(p, plan) - comm_s)
               * _interpret_factor(p),
               p.sequences * _LATENCY_FLOOR) + comm_s


def cost_pallas_mxu(p: Problem, plan: Plan) -> float:
    """MXU kernel: accumulated-path traffic at kernel-fused constants.

    Like ``pallas_wave``, per-request batches loop-launch per sequence.
    """
    D, comm_s = _dist_terms(p)
    return max(0.7 * (cost_accumulated(p, plan) - comm_s)
               * _interpret_factor(p),
               p.sequences * _LATENCY_FLOOR) + comm_s


def _components_rotseq_batched(p: Problem, plan: Plan) -> Dict[str, float]:
    # The stacked C/S/G panel streams once per grid batch element
    # whether or not the sequence is shared (the kernel's grid walks
    # batch-major), so the panel term scales with ``batch``, not
    # ``sequences`` — the kernel's per-request price is flat, which is
    # exactly why it wins serving buckets.
    return _split(
        setup_bytes=3.0 * max(1, p.batch) * p.planes_total * p.itemsize,
        stream_flops=6.0 * p.m_total * p.planes_live,
        stream_bytes=2.0 * p.m_total * p.n * p.itemsize)


def cost_rotseq_batched(p: Problem, plan: Plan) -> float:
    """Fused multi-request kernel (SS6 applied across requests).

    One launch streams every batched target through HBM exactly once
    (the whole ``(n, m_blk)`` slab lives in VMEM for all ``k`` waves, so
    there is no per-band re-read), the ``3 (n-1) k`` C/S/G panel is
    read once per batch element, and — unlike every other backend —
    the flop term scales with the *live* planes: identity padding from
    ``pad_to`` and ``seq.T`` staircases is skipped, not multiplied
    through.  A problem past the kernel's on-chip budgets gets no
    candidate (:func:`rotseq_batched_tiles`) rather than a price.
    """
    hw = p.hardware
    c = _components_rotseq_batched(p, plan)
    D, comm_s = _dist_terms(p)
    secs = _roofline_seconds(
        c["stream_flops"] / hw.vpu_flops / D,
        (c["setup_bytes"] + c["stream_bytes"] / D) / hw.hbm_bw)
    return max(secs * _interpret_factor(p), _LATENCY_FLOOR) + comm_s


# the setup/stream traffic split behind each cost model, exposed so the
# obs roofline layer attributes dispatches with the *same* numbers the
# planner ranked candidates with (pallas kernels move blocked /
# accumulated traffic; only their seconds constant differs)
_COMPONENT_FNS: Dict[str, Callable[[Problem, Plan], Dict[str, float]]] = {
    "unoptimized": _components_unoptimized,
    "wavefront": _components_wavefront,
    "blocked": _components_blocked,
    "accumulated": _components_accumulated,
    "pallas_wave": _components_blocked,
    "pallas_mxu": _components_accumulated,
    "rotseq_batched": _components_rotseq_batched,
}

# stream flops run at MXU rate for the GEMM family, VPU elsewhere;
# setup flops (Q_t accumulation) are always short-vector VPU work
_MXU_STREAM = ("accumulated", "pallas_mxu")


def cost_components(method: str, problem: Problem,
                    plan: Optional[Plan] = None) -> dict:
    """Predicted traffic + seconds for one dispatch, split by term.

    Returns ``{"flops", "bytes", "seconds", "setup": {...},
    "stream": {...}}``.  Top-level ``flops``/``bytes`` are the summed
    §6 memory-operation analysis of the named backend (zero for
    backends registered without a component entry); ``seconds`` is the
    registered cost model itself, so it always matches what
    ``select_plan`` ranked by — including the interpret penalty.  The
    ``setup``/``stream`` sub-dicts carry the
    per-sequence vs per-row split with *additive, penalty-free*
    attribution seconds (pure traffic over peak rates).
    Sharded problems (``devices > 1``) additionally carry a ``comm``
    sub-dict — the wave-panel broadcast bytes and their link-priced
    seconds (``docs/cost-model.md``, "the communication term"); the
    attribution ``stream`` seconds are *per-shard* (divided by the mesh
    size), matching what each device actually streams.
    Pure arithmetic — safe to call from metrics/snapshot paths (RA5).
    """
    spec = get_backend(method)
    plan = plan if plan is not None else Plan(method=method)
    comp_fn = _COMPONENT_FNS.get(method)
    c = comp_fn(problem, plan) if comp_fn is not None else _ZERO_SPLIT
    hw = problem.hardware
    D, _ = _dist_terms(problem)
    comm = _comm_components(problem)
    stream_rate = hw.mxu_flops if method in _MXU_STREAM else hw.vpu_flops
    setup_s = (c["setup_flops"] / hw.vpu_flops
               + c["setup_bytes"] / hw.hbm_bw)
    stream_s = (c["stream_flops"] / stream_rate
                + c["stream_bytes"] / hw.hbm_bw) / D
    return {
        "flops": float(c["setup_flops"] + c["stream_flops"]),
        "bytes": float(c["setup_bytes"] + c["stream_bytes"]),
        "seconds": float(spec.cost(problem, plan)),
        "setup": {"flops": float(c["setup_flops"]),
                  "bytes": float(c["setup_bytes"]),
                  "seconds": float(setup_s)},
        "stream": {"flops": float(c["stream_flops"]),
                   "bytes": float(c["stream_bytes"]),
                   "seconds": float(stream_s)},
        "comm": {"bytes": float(comm["bytes"]),
                 "hops": float(comm["hops"]),
                 "seconds": float(comm["seconds"])},
    }


# --------------------------------------------------------------------------
# tile candidate grids
# --------------------------------------------------------------------------

def _clip_pairs(p: Problem, pairs, cap: Capability) -> List[Tuple[int, int]]:
    lo_n, lo_k = cap.tile_min
    hi_n, hi_k = cap.tile_max
    seen, out = set(), []
    for n_b, k_b in pairs:
        n_b = max(lo_n, min(n_b, hi_n, max(8, p.n)))
        k_b = max(lo_k, min(k_b, hi_k, max(1, p.k)))
        if (n_b, k_b) not in seen:
            seen.add((n_b, k_b))
            out.append((n_b, k_b))
    return out


def no_tiles(p: Problem) -> List[Plan]:
    return [Plan(method="", n_b=None, k_b=None)]


def blocked_tiles(p: Problem) -> List[Plan]:
    pairs = [(64, 16), (32, 8), (16, 8), (8, 4), (64, 2)]
    cap = get_backend("blocked").capability
    return [Plan("", n_b=a, k_b=b) for a, b in _clip_pairs(p, pairs, cap)]


def accumulated_tiles(p: Problem) -> List[Plan]:
    pairs = [(128, 128), (96, 96), (64, 64), (32, 32), (16, 16), (8, 8),
             (64, 16)]
    cap = get_backend("accumulated").capability
    return [Plan("", n_b=a, k_b=b) for a, b in _clip_pairs(p, pairs, cap)]


def _m_blk_for(p: Problem) -> int:
    if p.platform == "tpu":
        return 256 if p.m_total >= 256 else 128
    return min(256, max(8, 1 << (max(1, p.m_total) - 1).bit_length()))


# What Mosaic compiles.  Off the chip the interpreter runs any kernel;
# on it, a kernel that cannot compile is never offered.  The VPU
# kernels address one row of a slab at a dynamic offset, which a packed
# 16-bit slab does not allow, so they are float32 only.
_VPU_KERNEL_DTYPES = ("float32",)
_MXU_KERNEL_DTYPES = ("float32", "bfloat16")


def _compiles_for(p: Problem, dtypes: Tuple[str, ...]) -> bool:
    return p.platform != "tpu" or p.dtype in dtypes


def pallas_wave_tiles(p: Problem) -> List[Plan]:
    if not _compiles_for(p, _VPU_KERNEL_DTYPES):
        return []
    cap = get_backend("pallas_wave").capability
    pairs = _clip_pairs(p, [(64, 16), (32, 8), (8, 4)], cap)
    mb = _m_blk_for(p)
    return [Plan("", n_b=a, k_b=b, m_blk=mb) for a, b in pairs]


def pallas_mxu_tiles(p: Problem) -> List[Plan]:
    if not _compiles_for(p, _MXU_KERNEL_DTYPES):
        return []
    cap = get_backend("pallas_mxu").capability
    pairs = _clip_pairs(p, [(128, 128), (64, 64), (8, 8)], cap)
    if p.platform == "tpu":
        # the (m_blk, n_b) fresh-column block is lane-major: Mosaic
        # takes it only at a multiple of 128 lanes
        pairs = [(a, b) for a, b in pairs if a % LANES == 0]
    mb = _m_blk_for(p)
    return [Plan("", n_b=a, k_b=b, m_blk=mb) for a, b in pairs]


def rotseq_batched_tiles(p: Problem) -> List[Plan]:
    """The fused kernel tiles only over lanes (whole n stays in VMEM).

    On the chip the scalar-indexed C/S/G panels live in SMEM; a problem
    whose panels exceed ``repro.kernels.limits.SMEM_PANEL_BUDGET`` gets
    no candidate.
    """
    if not _compiles_for(p, _VPU_KERNEL_DTYPES):
        return []
    if p.platform == "tpu" \
            and smem_panel_bytes(p.n - 1, p.k) > SMEM_PANEL_BUDGET:
        return []
    mb = _m_blk_for(p)
    return [Plan("", m_blk=b) for b in dict.fromkeys((mb, 128))]


def pinned_tiles(method: str, problem: Problem) -> dict:
    """Tile kwargs for a backend the caller names instead of ``"auto"``.

    Tiled backends keep the seed tiles ``n_b=64, k_b=16``, except a
    Pallas kernel on the chip, which takes the cheapest tile it offers
    for ``problem`` — Mosaic compiles only those.  Raises
    ``ValueError`` where it offers none.
    """
    spec = get_backend(method)
    if spec.candidates is no_tiles:
        return {}
    if not (spec.capability.needs_pallas and problem.platform == "tpu"):
        return {"n_b": 64, "k_b": 16}
    plans = [dataclasses.replace(c, method=method)
             for c in spec.candidates(problem)]
    if not plans:
        raise ValueError(
            f"method {method!r} cannot compile {problem} for the chip")
    return min(plans, key=lambda pl: spec.cost(problem, pl)).kwargs()


# --------------------------------------------------------------------------
# plan selection + cache
# --------------------------------------------------------------------------

_PLAN_CACHE: Dict[tuple, Plan] = {}
_CACHE_STATS = {"hits": 0, "misses": 0}


def plan_cache_stats() -> dict:
    return dict(_CACHE_STATS, size=len(_PLAN_CACHE))


def clear_plan_cache() -> None:
    """Drop the in-process plan memo and its hit/miss counts."""
    _PLAN_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0


def _jax_version_str() -> str:
    return ".".join(map(str, compat.JAX_VERSION))


def _plan_key(problem: Problem) -> tuple:
    """Memo key for a problem.

    ``(m, n, k, dtype, platform, signs, sharded)`` for a single target;
    batched problems append the batch count, per-request batches
    (``shared_sequence=False``, which price setup × b and can plan
    differently) append a ``"per_req"`` marker after it, and problems
    with a static live-plane count (padded/staircase sequences, which
    plane-skipping backends price differently) append ``("live",
    count)`` last.  Sharded problems put ``("sharded", devices)`` in
    the ``sharded`` slot, so each mesh size has its own entries.
    Problems the model prices differently never share an entry.
    """
    shard = ("sharded", max(1, problem.devices)) if problem.sharded \
        else False
    base = (problem.m, problem.n, problem.k, problem.dtype,
            problem.platform, problem.signs, shard)
    if problem.batch == 1 and problem.live_planes is None:
        return base
    base = base + (problem.batch,)
    if problem.batch > 1 and not problem.shared_sequence:
        base = base + ("per_req",)
    if problem.live_planes is not None:
        base = base + ("live", problem.live_planes)
    return base


def _modeled_plans(problem: Problem) -> List[Plan]:
    """All eligible (backend, tile) plans, costed and sorted ascending.

    Problems small enough to hit the latency floor tie on seconds; the
    tie-break is total modeled traffic (the §6 criterion itself), not
    backend registration order — at floor-bound sizes the
    least-communication plan is still the principled pick.
    """
    plans: List[Plan] = []
    for spec in eligible_backends(problem):
        for cand in spec.candidates(problem):
            plan = dataclasses.replace(cand, method=spec.name)
            cost = spec.cost(problem, plan)
            plans.append(dataclasses.replace(plan, est_seconds=cost))

    def _rank(pl: Plan):
        comp_fn = _COMPONENT_FNS.get(pl.method)
        if comp_fn is None:
            return (pl.est_seconds, float("inf"))
        c = comp_fn(problem, pl)
        return (pl.est_seconds, c["setup_bytes"] + c["stream_bytes"])

    plans.sort(key=_rank)
    return plans


def select_plan(m: int, n: int, k: int, *, dtype="float32",
                platform: Optional[str] = None, signs: bool = False,
                sharded: bool = False, devices: int = 1, batch: int = 1,
                shared_sequence: bool = True,
                live_planes: Optional[int] = None) -> Plan:
    """Pick ``(method, n_b, k_b, m_blk)`` for a problem, memoized.

    The cheapest eligible plan by the cost model wins, and is memoized
    in this process per problem key (:func:`_plan_key`): the same
    problem always gets the same plan, whatever ran before it.

    ``batch`` is the number of independent ``(m, n)`` targets served per
    application (see :class:`Problem`): the amortization terms differ,
    so batch 64 can legitimately pick a different backend than batch 1.
    ``shared_sequence=False`` marks a *per-request* batch (one distinct
    sequence per target, the serving path): per-sequence setup terms
    multiply by ``b`` instead of amortizing, and the key carries a
    ``"per_req"`` marker (docs/cost-model.md, "the per-request
    correction").
    ``live_planes`` is the statically-known count of non-identity
    planes (``RotationSequence.k_live``): plane-skipping backends price
    padded/staircase grids by their live fraction, so a ``seq.T``
    application plans differently from a dense one of the same shape.
    ``devices`` is the mesh size of a sharded execution (``devices > 1``
    implies ``sharded=True``): stream terms divide across shards and
    the wave-panel broadcast is priced at link bandwidth, so
    ``method="auto"`` with a mesh genuinely arbitrates sharded-fused vs
    replicated.
    """
    import jax.numpy as jnp

    platform = platform or compat.default_platform()
    dtype = str(jnp.dtype(dtype))
    batch = max(1, int(batch))
    devices = max(1, int(devices))
    sharded = bool(sharded) or devices > 1
    # a batch of one is its own sequence either way: normalize so both
    # spellings share one key (and plan)
    shared_sequence = bool(shared_sequence) or batch <= 1
    problem = Problem(m=m, n=n, k=k, dtype=dtype, platform=platform,
                      signs=signs, sharded=sharded, batch=batch,
                      shared_sequence=shared_sequence,
                      live_planes=live_planes, devices=devices)
    key = _plan_key(problem)
    cached = _PLAN_CACHE.get(key)
    if cached is not None:
        _CACHE_STATS["hits"] += 1
        obs.inc("registry.plan_cache.hits")
        return cached
    _CACHE_STATS["misses"] += 1
    obs.inc("registry.plan_cache.misses")

    if n < 2 or k < 1 or m < 1:
        # degenerate: zero rotations (or empty A) — application is a
        # no-op; pick the cheapest backend that accepts the arguments
        best = Plan(method="blocked" if signs else "unoptimized",
                    est_seconds=0.0)
        _PLAN_CACHE[key] = best
        return best

    with obs.span("resolve", m=m, n=n, k=k, batch=batch, dtype=dtype,
                  platform=platform) as sp:
        plans = _modeled_plans(problem)
        if not plans:
            raise ValueError(
                f"no registered backend is eligible for {problem}"
            )
        best = plans[0]
        _PLAN_CACHE[key] = best
        sp.set(method=best.method)
    return best

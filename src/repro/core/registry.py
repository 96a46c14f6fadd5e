"""Backend registry + cost-model dispatch for rotation-sequence application.

Every backend registers a :class:`BackendSpec` (capability record, §6
memory-operation cost model split into per-sequence *setup* and per-row
*stream* terms, tile-candidate generator); ``select_plan`` ranks the
eligible (backend, tile) candidates by modeled cost — optionally
re-ranked by measured wall time with ``autotune=True`` — and caches the
winning :class:`Plan` per problem key, write-through to an on-disk store
for measured plans.  :class:`Problem.shared_sequence` distinguishes one
sequence amortized over a batch from the serving path's
one-sequence-per-request batches, which pay setup × b.

The full pricing derivation (every backend's flop/memop/setup formula,
the per-request correction, and a worked batch-64 example) lives in
``docs/cost-model.md``; ``docs/architecture.md`` places this module in
the registry → sequence → serve → stream layer diagram.
"""
from __future__ import annotations

import dataclasses
import json
import math
import os
import tempfile
from typing import Callable, Dict, List, Optional, Tuple

from repro import compat, obs
from repro.obs import timing as _timing

__all__ = [
    "Hardware", "Problem", "Plan", "Capability", "BackendSpec",
    "register", "get_backend", "registered_methods", "eligible_backends",
    "no_tiles", "blocked_tiles", "accumulated_tiles",
    "pallas_wave_tiles", "pallas_mxu_tiles", "rotseq_batched_tiles",
    "pinned_tiles", "select_plan", "plan_cache_stats", "clear_plan_cache",
    "plan_cache_path", "save_plan_cache", "load_plan_cache",
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
        return {"float64": 8, "float32": 4, "bfloat16": 2,
                "float16": 2}.get(self.dtype, 4)

    @property
    def m_total(self) -> int:
        """Total rows streamed per application (``batch * m``)."""
        return self.m * max(1, self.batch)

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
    # "model" (cost-model ranked) | "measured" (autotuned this process) |
    # "persisted" (measured, loaded from disk) | "interpolated" (borrowed
    # from the nearest measured shape)
    source: str = "model"

    def kwargs(self) -> dict:
        kw = {}
        if self.n_b is not None:
            kw["n_b"] = self.n_b
        if self.k_b is not None:
            kw["k_b"] = self.k_b
        if self.m_blk is not None:
            kw["m_blk"] = self.m_blk
        return kw


@dataclasses.dataclass(frozen=True)
class Capability:
    """What a backend can run; consulted before costing it."""
    dtypes: Tuple[str, ...] = ("float32", "bfloat16", "float64", "float16")
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
    attribution seconds (pure traffic over peak rates), so the obs
    roofline ledger — and the bench row that watches the per-request
    accumulated cliff — can attribute ``model_fraction`` per term.
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
    """Drop the *in-memory* plan cache (the on-disk file is untouched)."""
    _PLAN_CACHE.clear()
    _CACHE_STATS["hits"] = _CACHE_STATS["misses"] = 0


# --------------------------------------------------------------------------
# persisted plan cache (measured/autotuned plans survive the process)
# --------------------------------------------------------------------------
#
# Autotuned plans are expensive (each one compiles and times real backends)
# but keyed by pure host facts — (m, n, k, dtype, platform, signs, sharded)
# plus the JAX version — so they are safe to reuse across processes.  Every
# measured plan is written through to a JSON file (atomic tmp+rename) and
# loaded back on import.  Model-ranked plans are cheap to recompute and are
# never persisted.  ``REPRO_PLAN_CACHE`` overrides the path; setting it to
# the empty string or ``off`` disables persistence entirely.

_PLAN_CACHE_ENV = "REPRO_PLAN_CACHE"
_PLAN_CACHE_FORMAT = 1
_PERSISTED_SOURCES = ("measured", "persisted")


def plan_cache_path() -> Optional[str]:
    """Resolved on-disk cache path, or ``None`` when persistence is off."""
    override = os.environ.get(_PLAN_CACHE_ENV)
    if override is not None:
        if override.strip().lower() in ("", "off", "0", "none"):
            return None
        return os.path.expanduser(override)
    base = os.environ.get("XDG_CACHE_HOME",
                          os.path.join(os.path.expanduser("~"), ".cache"))
    return os.path.join(base, "repro", "plans.json")


def _jax_version_str() -> str:
    return ".".join(map(str, compat.JAX_VERSION))


def _read_versioned_json(path: str, fmt: int) -> Optional[dict]:
    """Parse a versioned JSON store; ``None`` when the file is missing,
    corrupt, or stale (other format or JAX version) — the shared
    invalidation rule of every persisted-plan store (registry cache and
    the serving plan store alike)."""
    try:
        with open(path) as f:
            payload = json.load(f)
    except (OSError, ValueError):
        return None
    if not isinstance(payload, dict) or payload.get("format") != fmt \
            or payload.get("jax") != _jax_version_str():
        return None
    return payload


def _atomic_write_json(path: str, payload: dict,
                       prefix: str) -> Optional[str]:
    """tmp+rename atomic JSON write; ``None`` (never raises) on I/O
    errors so a read-only cache dir degrades gracefully."""
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                   prefix=prefix, suffix=".tmp")
        try:
            with os.fdopen(fd, "w") as f:
                json.dump(payload, f, indent=1)
            os.replace(tmp, path)  # atomic on POSIX
        except BaseException:
            os.unlink(tmp)
            raise
    except OSError:
        return None
    return path


def save_plan_cache(path: Optional[str] = None) -> Optional[str]:
    """Atomically write all measured/persisted plans to disk.

    Entries already on disk (same format + JAX version) that this
    process does not hold in memory are merged in before writing — a
    *best-effort* courtesy to concurrent processes autotuning other
    shapes (the unlocked read-merge-replace still has a lost-update
    window; a plan lost to the race is merely re-measured, never
    corrupted, so no file lock is taken).  Returns the
    path written, or ``None`` when persistence is disabled or there is
    nothing durable to save.  Never raises for I/O problems — a
    read-only cache dir degrades to in-memory planning.
    """
    path = path or plan_cache_path()
    if path is None:
        return None
    merged: Dict[tuple, dict] = {}
    on_disk = _read_versioned_json(path, _PLAN_CACHE_FORMAT)
    if on_disk is not None:  # missing/corrupt/stale file: start fresh
        for entry in on_disk.get("plans", []):
            try:
                merged[tuple(entry["key"])] = entry
            except (KeyError, TypeError):
                continue
    for key, plan in _PLAN_CACHE.items():
        if plan.source in _PERSISTED_SOURCES:
            merged[key] = {"key": list(key), "method": plan.method,
                           "n_b": plan.n_b, "k_b": plan.k_b,
                           "m_blk": plan.m_blk,
                           "est_seconds": plan.est_seconds}
    if not merged:
        return None
    payload = {"format": _PLAN_CACHE_FORMAT, "jax": _jax_version_str(),
               "plans": list(merged.values())}
    return _atomic_write_json(path, payload, prefix=".plans.")


def load_plan_cache(path: Optional[str] = None) -> int:
    """Merge persisted plans into the in-memory cache; returns count loaded.

    Entries from a different JAX version (or an unreadable/corrupt file)
    are ignored wholesale — measured timings do not transfer across
    compiler versions.  An in-memory *measured* entry wins over disk.
    """
    path = path or plan_cache_path()
    if path is None:
        return 0
    payload = _read_versioned_json(path, _PLAN_CACHE_FORMAT)
    if payload is None:
        return 0
    loaded = 0
    for entry in payload.get("plans", []):
        try:
            key = tuple(entry["key"])
            plan = Plan(method=str(entry["method"]), n_b=entry.get("n_b"),
                        k_b=entry.get("k_b"), m_blk=entry.get("m_blk"),
                        est_seconds=float(entry.get("est_seconds", 0.0)),
                        source="persisted")
        except (KeyError, TypeError, ValueError):
            continue
        if plan.method not in _REGISTRY:
            continue  # stale entry for an unregistered backend
        cached = _PLAN_CACHE.get(key)
        if cached is not None and cached.source == "measured":
            continue
        _PLAN_CACHE[key] = plan
        loaded += 1
    return loaded


# Maximum summed |log(m/m')| + |log(n/n')| + |log(k/k')| (+ batch term)
# at which a measured plan still transfers: ~4x per dimension on
# average.  Beyond this the regime can differ qualitatively
# (cache-resident vs streaming, VPU- vs MXU-bound) and the cost model is
# the better guess.
_INTERP_MAX_LOGDIST = 3 * math.log(4.0)


def _plan_key(problem: Problem) -> tuple:
    """Cache key for a problem.

    ``batch=1`` keys keep the legacy 7-tuple layout so plan caches
    persisted before the batch field existed stay valid; batched
    problems append the batch count, per-request batches
    (``shared_sequence=False``, which price setup × b and can plan
    differently) append a ``"per_req"`` marker after it, and problems
    with a static live-plane count (padded/staircase sequences, which
    plane-skipping backends price differently) append
    ``("live", count)`` last.

    Sharded problems put ``("sharded", devices)`` in the legacy
    ``sharded`` slot: the mesh size is part of the eligibility *class*
    (``_split_key``), so plans never transfer between device counts —
    or to/from single-device keys, whose slot stays the legacy
    ``False``.  Sharded plans are never persisted (``select_plan``'s
    ``can_measure`` excludes them), so the tuple-valued slot never
    reaches the JSON store.
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


def _split_key(key: tuple):
    """``key -> ((m, n, k, batch), class, live_fraction)``.

    ``class`` is the eligibility tuple ``(dtype, platform, signs,
    sharded, shared_sequence)``.  Shared-sequence and per-request keys
    are distinct classes — a measured plan for one sequence amortized
    over a batch must not transfer at distance 0 to the same shape
    paying setup per request (the backends differ, exactly like dense
    vs live-annotated).  ``live_fraction`` decodes the optional
    trailing ``("live", count)`` marker as ``count / ((n-1) * k)``
    (``None`` when absent): liveness changes which backend wins, so
    dense and live-annotated keys are distinct classes too, with the
    live-fraction ratio added to the distance within the latter.
    """
    m, n, k = key[:3]
    batch = key[7] if len(key) > 7 else 1
    shared = True
    idx = 8
    if len(key) > idx and key[idx] == "per_req":
        shared = False
        idx += 1
    frac = None
    if len(key) > idx + 1 and key[idx] == "live":
        planes = max(1, (n - 1) * k)
        frac = max(1, int(key[idx + 1])) / planes
    return (m, n, k, batch), tuple(key[3:7]) + (shared,), frac


def _interpolated_plan(problem: Problem, key: tuple) -> Optional[Plan]:
    """Borrow the nearest *measured* plan for an unmeasured shape.

    Autotuned timings are expensive; rather than re-running the cost
    model for a shape we have never measured, reuse the closest measured
    (or disk-persisted) plan of the same eligibility class — identical
    ``(dtype, platform, signs, sharded)`` and a backend this problem is
    itself eligible for — ranked by log-distance in ``(m, n, k)`` and
    only within :data:`_INTERP_MAX_LOGDIST` (a far-away measurement
    must not override the cost model).  Borrowed plans are cached under
    the new key with ``source="interpolated"`` (never persisted, and
    upgraded in place by a later ``autotune=True`` call).
    """
    eligible = {spec.name for spec in eligible_backends(problem)}
    best: Optional[Plan] = None
    best_dist = _INTERP_MAX_LOGDIST
    (m1, n1, k1, b1), cls1, frac1 = _split_key(key)
    for cached_key, plan in _PLAN_CACHE.items():
        if plan.source not in _PERSISTED_SOURCES:
            continue
        (m2, n2, k2, b2), cls2, frac2 = _split_key(cached_key)
        if cls2 != cls1:  # (dtype, platform, signs, sharded, shared_seq)
            continue
        if (frac2 is None) != (frac1 is None):
            continue  # dense vs live-annotated: different regimes
        if plan.method not in eligible:
            continue
        if min(m2, n2, k2, b2) < 1:
            continue
        dist = (abs(math.log(m1 / m2))
                + abs(math.log(n1 / n2))
                + abs(math.log(k1 / k2))
                + abs(math.log(b1 / b2)))
        if frac1 is not None:
            dist += abs(math.log(frac1 / frac2))
        if dist < best_dist:
            best, best_dist = plan, dist
    if best is None:
        return None
    # the donor's measured wall-time belongs to the donor's shape; carry
    # the cost model's estimate for *this* problem instead
    borrowed = dataclasses.replace(best, source="interpolated")
    est = get_backend(best.method).cost(problem, borrowed)
    return dataclasses.replace(borrowed, est_seconds=est)


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


def _synthetic_waves(problem: Problem, rng):
    """One ``(C, S, G)`` wave draw matching the problem record.

    A per-entry sign array is included when ``problem.signs`` so
    sign-carrying plans are timed on the code path they will actually
    serve, and a ``live_planes`` bound identity-pads the trailing waves
    so plane-skipping backends are timed on (approximately) the live
    grid they will execute, not a dense one ~grid/live times costlier.
    """
    import numpy as np

    th = rng.standard_normal((problem.n - 1, problem.k))
    Cn, Sn = np.cos(th), np.sin(th)
    if problem.live_planes is not None \
            and problem.live_planes < problem.planes_total:
        live_waves = math.ceil(problem.live_planes
                               / max(1, problem.n - 1))
        Cn[:, live_waves:] = 1.0
        Sn[:, live_waves:] = 0.0
    Gn = None
    if problem.signs:
        Gn = np.where(rng.random((problem.n - 1, problem.k)) < 0.5,
                      1.0, -1.0)
        # identity padding must stay a rotation (a padded reflector is
        # live), or the live_planes-shaped workload above is undone
        Gn[(Cn == 1.0) & (Sn == 0.0)] = -1.0
    return Cn, Sn, Gn


def _time_median(fn: Callable, reps: int) -> float:
    import jax

    jax.block_until_ready(fn())  # compile
    ts = []
    for _ in range(reps):
        t0 = _timing.now()
        jax.block_until_ready(fn())
        ts.append(_timing.now() - t0)
    return sorted(ts)[len(ts) // 2]


def _measure_plan(problem: Problem, plan: Plan, reps: int = 2) -> float:
    """Median wall-time of one real application at ``plan``'s tiles.

    The synthetic workload matches the problem record (signs, live
    planes — see :func:`_synthetic_waves`).  Shared-sequence batches
    execute flattened (rotations are row-wise), so they are timed as
    the ``(batch*m, n)`` problem the dispatch path will actually run;
    per-request batches are timed through
    ``SequencePlan.apply_batched(A, sequences=...)`` with ``batch``
    *distinct* sequences, so the fused / vmap / loop execution strategy
    — and the per-sequence setup this problem re-pays ``b`` times — is
    measured, not a single broadcast sequence that would hide it.
    """
    import jax.numpy as jnp
    import numpy as np

    if problem.batch > 1 and not problem.shared_sequence:
        return _measure_plan_per_request(problem, plan, reps)
    rng = np.random.default_rng(0)
    dt = jnp.dtype(problem.dtype)
    A = jnp.asarray(rng.standard_normal((problem.m_total, problem.n)), dt)
    Cn, Sn, Gn = _synthetic_waves(problem, rng)
    C, S = jnp.asarray(Cn, dt), jnp.asarray(Sn, dt)
    G = None if Gn is None else jnp.asarray(Gn, dt)
    spec = get_backend(plan.method)
    fn = lambda: spec.fn(A, C, S, reflect=False, G=G, **plan.kwargs())
    return _time_median(fn, reps)


def _measure_plan_per_request(problem: Problem, plan: Plan,
                              reps: int) -> float:
    """Per-request-batch measurement: ``batch`` distinct sequences.

    Routed through the same ``apply_batched`` strategy dispatch the
    serving path uses (fused kernel, ``jax.vmap``, or per-element
    loop), because that execution shape — not the flattened broadcast —
    is what a per-request plan will actually run.
    """
    import jax.numpy as jnp
    import numpy as np

    # plan-layer import, deferred: sequence.py imports this module
    from repro.core import sequence as _sequence

    rng = np.random.default_rng(0)
    dt = jnp.dtype(problem.dtype)
    A = jnp.asarray(
        rng.standard_normal((problem.batch, problem.m, problem.n)), dt)
    seqs = []
    for _ in range(problem.batch):
        Cn, Sn, Gn = _synthetic_waves(problem, rng)
        seq = _sequence.RotationSequence(
            jnp.asarray(Cn, dt), jnp.asarray(Sn, dt),
            None if Gn is None else jnp.asarray(Gn, dt))
        if problem.live_planes is not None:
            seq = dataclasses.replace(
                seq, k_live=min(problem.live_planes, problem.planes_total))
        seqs.append(seq)
    sp = _sequence.SequencePlan(seqs[0], plan.method,
                                tuple(sorted(plan.kwargs().items())), plan)
    fn = lambda: sp.apply_batched(A, sequences=seqs, direct=True)
    return _time_median(fn, reps)


def select_plan(m: int, n: int, k: int, *, dtype="float32",
                platform: Optional[str] = None, signs: bool = False,
                sharded: bool = False, devices: int = 1, batch: int = 1,
                shared_sequence: bool = True,
                live_planes: Optional[int] = None,
                autotune: bool = False, autotune_top: int = 3) -> Plan:
    """Pick ``(method, n_b, k_b, m_blk)`` for a problem, with caching.

    Cost-model ranking by default; with ``autotune=True`` the top
    ``autotune_top`` modeled plans are measured end-to-end and the
    fastest wins.  Winning plans are cached per
    ``(m, n, k, dtype, platform, signs, sharded[, batch])`` — an
    autotuned (measured) entry overwrites a model-ranked one for the
    same key and is then reused by plain ``method="auto"`` calls too.

    ``batch`` is the number of independent ``(m, n)`` targets served per
    application (see :class:`Problem`): the amortization terms differ,
    so batch 64 can legitimately pick a different backend than batch 1.
    ``shared_sequence=False`` marks a *per-request* batch (one distinct
    sequence per target, the serving path): per-sequence setup terms
    multiply by ``b`` instead of amortizing, the cache key carries a
    ``"per_req"`` marker, and autotune measures ``b`` distinct
    sequences through the real batched dispatch — additionally timing
    the best candidate of *every* eligible backend, because the
    traffic model cannot see fused/vmap/loop execution constants
    (docs/cost-model.md, "the per-request correction").
    ``live_planes`` is the statically-known count of non-identity
    planes (``RotationSequence.k_live``): plane-skipping backends price
    padded/staircase grids by their live fraction, so a ``seq.T``
    application plans differently from a dense one of the same shape.
    ``devices`` is the mesh size of a sharded execution (``devices > 1``
    implies ``sharded=True``): stream terms divide across shards and
    the wave-panel broadcast is priced at link bandwidth, so
    ``method="auto"`` with a mesh genuinely arbitrates sharded-fused vs
    replicated.  Sharded keys form their own cache class per device
    count and are never persisted or interpolated across mesh sizes.

    Unmeasured shapes first try **cross-shape interpolation**: the
    nearest measured/persisted plan of the same eligibility class
    (identical dtype/platform/signs/sharded, eligible backend) by
    ``(m, n, k, batch)`` log-distance is borrowed
    (``source="interpolated"``) before the cost model is re-run, so
    autotune work transfers to neighbouring problem sizes.  A later
    ``autotune=True`` call upgrades a borrowed entry in place — the
    borrowed plan's tiles join the measured candidate set, and the
    winning measurement is persisted (exactly once) like any other.
    """
    import jax.numpy as jnp

    platform = platform or compat.default_platform()
    dtype = str(jnp.dtype(dtype))
    batch = max(1, int(batch))
    devices = max(1, int(devices))
    sharded = bool(sharded) or devices > 1
    # a batch of one is its own sequence either way: normalize so the
    # legacy cache key (and plan) is shared by both spellings
    shared_sequence = bool(shared_sequence) or batch <= 1
    # Measurements time THIS host's default backend; for any other
    # platform (or a shard_map sub-problem, which can't be reproduced
    # standalone) fall back to model ranking rather than cache bogus
    # numbers — and then accept a cached model-ranked entry, since a
    # measured one can never exist for this key.
    can_measure = platform == compat.default_platform() and not sharded
    autotune = autotune and can_measure
    problem = Problem(m=m, n=n, k=k, dtype=dtype, platform=platform,
                      signs=signs, sharded=sharded, batch=batch,
                      shared_sequence=shared_sequence,
                      live_planes=live_planes, devices=devices)
    key = _plan_key(problem)
    cached = _PLAN_CACHE.get(key)
    if cached is not None and (not autotune
                               or cached.source in _PERSISTED_SOURCES):
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
                  platform=platform, autotune=autotune) as sp:
        if not autotune:
            borrowed = _interpolated_plan(problem, key)
            if borrowed is not None:
                _PLAN_CACHE[key] = borrowed
                obs.inc("registry.plan_cache.interpolated")
                sp.set(method=borrowed.method, source="interpolated")
                return borrowed
        plans = _modeled_plans(problem)
        if not plans:
            raise ValueError(
                f"no registered backend is eligible for {problem}"
            )
        best = plans[0]
        if autotune:
            candidates = plans[:max(1, autotune_top)]
            if batch > 1 and not shared_sequence:
                # Per-request batches execute through fused / vmap /
                # per-element-loop strategies whose constants the §6
                # traffic model cannot see (interpret-mode kernels
                # included), so widen the measured set to the best
                # modeled candidate of every eligible backend and let
                # measurement arbitrate — the model still prunes tiles
                # within each backend.
                seen = {pl.method for pl in candidates}
                for pl in plans:
                    if pl.method not in seen:
                        seen.add(pl.method)
                        candidates.append(pl)
            # an interpolated entry being upgraded is a real hint:
            # measure its tiles too, even when the model does not rank
            # them top-N
            if cached is not None and cached.source == "interpolated" \
                    and not any(
                        (pl.method, pl.n_b, pl.k_b, pl.m_blk)
                        == (cached.method, cached.n_b, cached.k_b,
                            cached.m_blk)
                        for pl in candidates):
                candidates = candidates + [cached]
            # every candidate compiles (the tile generators offer no
            # other), so a measurement that raises is a real failure
            timed = [dataclasses.replace(
                plan, est_seconds=_measure_plan(problem, plan),
                source="measured") for plan in candidates]
            best = min(timed, key=lambda pl: pl.est_seconds)
            if cached is not None:
                # a cached (model/interpolated) entry was replaced by a
                # fresh measurement for the same key
                obs.inc("registry.plan_cache.autotune_upgrade")
        _PLAN_CACHE[key] = best
        sp.set(method=best.method, source=best.source)
    if best.source == "measured":
        save_plan_cache()  # write-through; no-op when persistence is off
    return best

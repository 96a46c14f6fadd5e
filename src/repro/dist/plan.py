"""Sharded execution as a first-class plan: :class:`ShardedSequencePlan`.

The distributed path rides the same plan-once/apply-many spine as
everything else: :func:`plan_sharded` resolves a mesh +
``PartitionSpec`` + backend **exactly once** into a frozen, serializable
:class:`ShardedSequencePlan`, whose ``apply``/``apply_batched`` then
execute row-sharded ``(m, n)`` and batched ``(b, m, n)`` targets through
**one fused ``rotseq_batched`` launch per shard** under ``shard_map``
(or one shard-local call of whatever backend the plan resolved).

Row sharding is communication-free on the stream side — rotations act
on column *pairs*, so row shards are independent and the result is
bit-identical to the replicated execution; the only wire traffic is
replicating the C/S/G wave panels once per plan, which is exactly the
setup-side communication term the §6 cost model now prices
(``repro.core.registry._comm_components``, ``docs/cost-model.md``).
``method="auto"`` therefore genuinely arbitrates **sharded-fused vs
replicated**: the planner resolves both the sharded (``devices=D``,
its own plan-cache class) and the replicated problem, compares their
comm-extended ``cost_components`` seconds, and freezes the winner into
the plan — small-``n`` problems stay replicated (the per-hop link
latency dominates), large-``n`` problems amortize the wire and shard.

Column-sharded (CAQR-style panel) targets delegate to
:mod:`repro.dist.colsharded`, which exchanges boundary planes once per
``k_b``-wave panel instead of per wave.

Autodiff: shard-local execution calls the planned ``custom_vjp`` pair
from :mod:`repro.core.sequence` *inside* ``shard_map``, so
``jax.grad`` through :meth:`ShardedSequencePlan.apply` runs the
transposed-sequence VJP shard-locally with zero extra collectives.

Observability: kernel-side launch accounting is tracer-guarded and
cannot fire under ``shard_map`` tracing, so the plan self-accounts
host-side — ``dist.launches_per_shard`` and ``dist.comm_bytes``,
from the same comm-extended components the planner ranked by, and
``dist.donated_applies`` for the applications made in place.  The
``dist.plan`` span carries the bytes of one device's rows of the target
(``shard_bytes``), also set as the ``dist.shard_bytes`` gauge.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro import compat, obs
from repro.core import registry
from repro.core.accumulate import record_factor_schedule
from repro.core.sequence import (RotationSequence, SequencePlan,
                                 planned_apply, planned_apply_batched,
                                 planned_run, stack_request_waves)

__all__ = ["ShardedSequencePlan", "plan_sharded",
           "SHARDED_PLAN_DICT_FORMAT"]


# sentinel method of degenerate (zero-rotation) plans, mirroring
# SequencePlan's identity dispatch
_IDENTITY = "identity"

# JSON format version of ShardedSequencePlan.to_dict
SHARDED_PLAN_DICT_FORMAT = 1


def _mesh_devices(mesh, axes) -> int:
    """Product of the mesh extents over ``axes`` (the shard count)."""
    if isinstance(axes, str):
        axes = (axes,)
    d = 1
    for a in axes:
        if a not in mesh.shape:
            raise ValueError(
                f"mesh has no axis {a!r}; axes are {tuple(mesh.shape)}")
        d *= mesh.shape[a]
    return int(d)


def plan_sharded(seq: RotationSequence, like=None, *, mesh,
                 row_axes=("data",), m: Optional[int] = None,
                 batch: Optional[int] = None, method: str = "auto",
                 platform: Optional[str] = None,
                 shared_sequence: bool = True,
                 partition: str = "row", col_axis: str = "model",
                 n_b: Optional[int] = None, k_b: Optional[int] = None,
                 **kw) -> "ShardedSequencePlan":
    """Resolve mesh + specs + backend once into a frozen sharded plan.

    ``like``/``m``/``batch`` describe the *global* target exactly as in
    :meth:`RotationSequence.plan` (a 3D ``like`` supplies the batch).
    ``mesh`` is required; ``row_axes`` names the mesh axes the row
    dimension shards over (``devices`` = their extent product).

    ``method="auto"`` resolves **two** problems through the registry —
    the sharded one (``devices=D``, under its own ``"sharded"`` plan
    key, apart from single-device keys) and
    the replicated one — and freezes whichever the comm-extended cost
    model prices cheaper (:attr:`ShardedSequencePlan.execute_sharded`).
    A named ``method`` must be shard_map-capable
    (``Capability.supports_sharding``) and always executes sharded.

    ``partition="column"`` plans the CAQR-style column-panel pipeline
    instead (plain 2D rotation sequences only); ``col_axis`` names its
    mesh axis and ``n_b``/``k_b`` its panel tiles.
    """
    if mesh is None:
        raise TypeError("plan_sharded() missing required argument: 'mesh'")
    if partition not in ("row", "column"):
        raise ValueError(f"partition must be 'row' or 'column', "
                         f"got {partition!r}")
    like_shape = getattr(like, "shape", None)
    if like_shape is not None and len(like_shape) == 3:
        if batch is None:
            batch = like_shape[0]
        if m is None:
            m = like_shape[1]
    if m is None:
        m = like_shape[0] if like_shape is not None else max(seq.n, 1)
    batch = 1 if batch is None else max(1, int(batch))
    dtype = getattr(like, "dtype", None) or seq.dtype
    n, k = seq.n, seq.k

    if partition == "column":
        devices = _mesh_devices(mesh, col_axis)
        if seq.sign is not None or seq.reflect:
            raise ValueError(
                "column-sharded pipeline supports plain rotation "
                "sequences only")
        planned = dict(kw)
        planned["n_b"] = 64 if n_b is None else n_b
        planned["k_b"] = 16 if k_b is None else k_b
        col_method = method if method != "auto" else "blocked"
        return ShardedSequencePlan(
            sequence=seq, mesh=mesh, row_axes=_as_tuple(row_axes),
            method=col_method, kwargs=tuple(sorted(planned.items())),
            plan=None, devices=devices, execute_sharded=True,
            partition="column", col_axis=col_axis)

    devices = _mesh_devices(mesh, row_axes)
    if n < 2 or k < 1 or m < 1:
        return ShardedSequencePlan(
            sequence=seq, mesh=mesh, row_axes=_as_tuple(row_axes),
            method=_IDENTITY, kwargs=(), plan=None, devices=devices,
            execute_sharded=False)

    signs = seq.sign is not None
    if method != "auto":
        spec = registry.get_backend(method)  # raises on unknown
        if signs and not spec.capability.supports_signs:
            raise ValueError(
                f"method {method!r} does not support per-entry signs")
        if not spec.capability.supports_sharding:
            raise ValueError(
                f"method {method!r} cannot run inside shard_map")
        planned = registry.pinned_tiles(method, registry.Problem(
            m=m, n=n, k=k, dtype=str(jnp.dtype(dtype)),
            platform=platform or compat.default_platform(), signs=signs,
            sharded=True, devices=devices, batch=batch,
            shared_sequence=shared_sequence, live_planes=seq.k_live))
        if n_b is not None:
            planned["n_b"] = n_b
        if k_b is not None:
            planned["k_b"] = k_b
        planned.update(kw)
        record_factor_schedule(method, planned, n, k, dtype)
        return ShardedSequencePlan(
            sequence=seq, mesh=mesh, row_axes=_as_tuple(row_axes),
            method=method, kwargs=tuple(sorted(planned.items())),
            plan=None, devices=devices, execute_sharded=True)

    with obs.span("dist.plan", m=m, n=n, k=k, batch=batch,
                  devices=devices) as sp:
        sh_plan = registry.select_plan(
            m, n, k, dtype=dtype, platform=platform, signs=signs,
            sharded=True, devices=devices, batch=batch,
            shared_sequence=shared_sequence, live_planes=seq.k_live)
        rep_plan = registry.select_plan(
            m, n, k, dtype=dtype, platform=platform, signs=signs,
            batch=batch, shared_sequence=shared_sequence,
            live_planes=seq.k_live)
        sh_s, rep_s = modeled_crossover(
            m, n, k, devices=devices, dtype=dtype, platform=platform,
            signs=signs, batch=batch, shared_sequence=shared_sequence,
            live_planes=seq.k_live, sharded_plan=sh_plan,
            replicated_plan=rep_plan)
        execute_sharded = sh_s < rep_s
        chosen = sh_plan if execute_sharded else rep_plan
        shard_bytes = -(-m // devices) * n * jnp.dtype(dtype).itemsize
        obs.gauge("dist.shard_bytes", shard_bytes)
        sp.set(method=chosen.method, sharded=execute_sharded,
               shard_bytes=shard_bytes)
    planned = chosen.kwargs()
    if n_b is not None:
        planned["n_b"] = n_b
    if k_b is not None:
        planned["k_b"] = k_b
    planned.update(kw)
    record_factor_schedule(chosen.method, planned, n, k, dtype)
    return ShardedSequencePlan(
        sequence=seq, mesh=mesh, row_axes=_as_tuple(row_axes),
        method=chosen.method, kwargs=tuple(sorted(planned.items())),
        plan=chosen, devices=devices, execute_sharded=execute_sharded)


def modeled_crossover(m: int, n: int, k: int, *, devices: int,
                      dtype="float32", platform: Optional[str] = None,
                      signs: bool = False, batch: int = 1,
                      shared_sequence: bool = True,
                      live_planes: Optional[int] = None,
                      sharded_plan: Optional[registry.Plan] = None,
                      replicated_plan: Optional[registry.Plan] = None
                      ) -> Tuple[float, float]:
    """``(sharded_seconds, replicated_seconds)`` the arbitration compares.

    Both sides are the registered cost models via ``cost_components``
    (the sharded side carries the comm term and per-shard stream), so a
    test — or a curious caller — can reproduce the ``method="auto"``
    sharded-vs-replicated decision to the digit.
    """
    platform = platform or compat.default_platform()
    if sharded_plan is None:
        sharded_plan = registry.select_plan(
            m, n, k, dtype=dtype, platform=platform, signs=signs,
            sharded=True, devices=devices, batch=batch,
            shared_sequence=shared_sequence, live_planes=live_planes)
    if replicated_plan is None:
        replicated_plan = registry.select_plan(
            m, n, k, dtype=dtype, platform=platform, signs=signs,
            batch=batch, shared_sequence=shared_sequence,
            live_planes=live_planes)
    p_sh = registry.Problem(
        m=m, n=n, k=k, dtype=str(jnp.dtype(dtype)), platform=platform,
        signs=signs, sharded=True, batch=batch,
        shared_sequence=shared_sequence, live_planes=live_planes,
        devices=devices)
    p_rep = dataclasses.replace(p_sh, sharded=False, devices=1)
    sh_s = registry.cost_components(
        sharded_plan.method, p_sh, sharded_plan)["seconds"]
    rep_s = registry.cost_components(
        replicated_plan.method, p_rep, replicated_plan)["seconds"]
    return float(sh_s), float(rep_s)


def _as_tuple(axes) -> Tuple[str, ...]:
    return (axes,) if isinstance(axes, str) else tuple(axes)


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedSequencePlan:
    """A frozen sharded dispatch decision bound to one sequence + mesh.

    Mirrors :class:`~repro.core.sequence.SequencePlan` — frozen,
    rebindable (:meth:`rebind`), serializable (:meth:`to_dict` /
    :meth:`from_dict`), obs-instrumented, differentiable w.r.t. the
    target through the planned ``custom_vjp`` — with the mesh, the
    partition specs, and the sharded-vs-replicated arbitration resolved
    exactly once at plan time.

    ``execute_sharded=False`` (an ``method="auto"`` outcome) means the
    comm-extended cost model priced the replicated execution cheaper:
    ``apply`` then runs the inner single-device :class:`SequencePlan`
    unchanged.  Named methods always execute sharded.
    """

    sequence: RotationSequence
    mesh: Any
    row_axes: Tuple[str, ...]
    method: str
    kwargs: Tuple[Tuple[str, Any], ...]
    plan: Optional[registry.Plan] = None
    devices: int = 1
    execute_sharded: bool = True
    partition: str = "row"
    col_axis: str = "model"

    def __repr__(self) -> str:
        return (f"ShardedSequencePlan(method={self.method!r}, "
                f"devices={self.devices}, "
                f"sharded={self.execute_sharded}, "
                f"partition={self.partition!r}, "
                f"kwargs={dict(self.kwargs)}, seq={self.sequence!r})")

    # -- inner single-device plan (replicated path / shard-local fields) --
    def _inner(self) -> SequencePlan:
        return SequencePlan(self.sequence, self.method, self.kwargs,
                            self.plan)

    # -- execution --------------------------------------------------------
    def apply(self, A, *, direct: bool = False, donate: bool = False):
        """Apply the planned sequence to a ``(m, n)`` target.

        Sharded execution shards rows over ``row_axes`` (``m`` must
        divide by ``devices``) and runs **one** shard-local planned
        backend call per shard; ``direct=True`` keeps the backend's
        native autodiff instead of the transposed-sequence
        ``custom_vjp`` (the ``apply_direct`` analogue).

        ``donate=True`` gives ``A``'s buffers to the application, which
        writes each shard's result in its own buffer, as ``xLASR``
        updates its rows of Z in place: ``A`` is deleted, and a chain of
        applications holds one copy of the target.  It applies to the
        row-sharded path; the default keeps ``A``.
        """
        if self.method == _IDENTITY:
            return A
        if self.partition == "column":
            return self._column_sharded(A)
        if not self.execute_sharded:
            inner = self._inner()
            return inner.apply_direct(A) if direct else inner.apply(A)
        self._check_rows(A.shape[-2])
        if compat.is_tracer(A):
            return self._row_sharded_2d(A, direct, False)
        with obs.span("dist.apply", method=self.method,
                      devices=self.devices, m=int(A.shape[0]),
                      n=int(A.shape[1])):
            out = self._row_sharded_2d(A, direct, donate)
        self._count_dispatch(A, launches=1)
        if donate:
            obs.inc("dist.donated_applies")
        return out

    __call__ = apply

    def apply_batched(self, A, sequences=None, *, direct: bool = False):
        """Apply to a batched ``(b, m, n)`` target, sharding rows.

        The batch axis is replicated and ``m`` shards over
        ``row_axes`` — every shard sees all ``b`` targets' row slices,
        so a fused-capable plan (``rotseq_batched``) executes the whole
        bucket in exactly **one launch per shard**.  ``sequences``
        carries per-request waves exactly as in
        :meth:`SequencePlan.apply_batched` (stacked host-side,
        replicated across the mesh).
        """
        A = jnp.asarray(A)
        if A.ndim != 3:
            raise ValueError(
                f"apply_batched expects A of shape (b, m, n); "
                f"got {A.shape} — use apply() for a single target")
        if self.method == _IDENTITY:
            return A
        if self.partition == "column":
            raise ValueError(
                "column-sharded plans take 2D targets; batch rows "
                "instead (partition='row')")
        if not self.execute_sharded:
            return self._inner().apply_batched(A, sequences=sequences,
                                               direct=direct)
        self._check_rows(A.shape[1])
        launches = self._launches_per_shard(int(A.shape[0]))
        if compat.is_tracer(A):
            return self._row_sharded_batched(A, sequences, direct)
        with obs.span("dist.apply_batched", method=self.method,
                      devices=self.devices, batch=int(A.shape[0]),
                      m=int(A.shape[1]), n=int(A.shape[2])):
            out = self._row_sharded_batched(A, sequences, direct)
        self._count_dispatch(A, launches=launches,
                             shared=sequences is None)
        return out

    # -- sharded executors ------------------------------------------------
    # The shard_map closure + its jit compilation are resolved once per
    # (mode, direct, sign-structure) and cached on the instance —
    # plan-once/apply-many must not pay a re-trace per application.
    # ``rebind`` carries the cache across same-structure rebinds (the
    # closures see waves only as call arguments).
    def _fns(self) -> dict:
        """The compiled fns of this plan and its same-structure rebinds."""
        cache = self.__dict__.get("_fn_cache")
        if cache is None:
            cache = {}
            object.__setattr__(self, "_fn_cache", cache)
        return cache

    def _cached_fn(self, key, builder):
        cache = self._fns()
        fn = cache.get(key)
        if fn is None:
            fn = cache[key] = builder()
        return fn

    def _row_sharded_2d(self, A, direct: bool, donate: bool):
        fn = self._cached_fn(("2d", direct, donate),
                             lambda: self._build_2d(direct, donate))
        return fn(A, self.sequence)

    def _build_2d(self, direct: bool, donate: bool):
        run = planned_run if direct else planned_apply
        method, kwargs = self.method, self.kwargs
        reflect = self.sequence.reflect

        def local_fn(a, sq):
            return run(method, kwargs, reflect, a, sq.cos, sq.sin, sq.sign)

        seq_specs = jax.tree_util.tree_map(lambda _: P(None, None),
                                           self.sequence)
        return jax.jit(compat.shard_map(
            local_fn, mesh=self.mesh,
            in_specs=(P(self.row_axes, None), seq_specs),
            out_specs=P(self.row_axes, None)),
            donate_argnums=(0,) if donate else ())

    def _row_sharded_batched(self, A, sequences, direct: bool):
        seq = self.sequence
        b = int(A.shape[0])

        if sequences is None:
            C, S, G = seq.cos, seq.sin, seq.sign
            shared = True
        else:
            seqs = list(sequences)
            if len(seqs) != b:
                raise ValueError(
                    f"{len(seqs)} sequences for a batch of {b} targets")
            plan_signed = seq.sign is not None
            for s in seqs:
                if not isinstance(s, RotationSequence):
                    raise TypeError(
                        f"expected RotationSequence, got {type(s)}")
                if tuple(s.shape) != tuple(seq.shape):
                    raise ValueError(
                        f"sequence shape {s.shape} != plan shape "
                        f"{seq.shape}; pad_to a stable wave count first")
                if not plan_signed and (s.sign is not None
                                        or s.reflect != seq.reflect):
                    raise ValueError(
                        "mixed sign/reflect structure in one batch; plan "
                        "on a sign-carrying representative first")
            C, S, G = stack_request_waves(seqs, plan_signed)
            shared = False

        key = ("batched", direct, shared, G is None)
        fn = self._cached_fn(
            key, lambda: self._build_batched(direct, shared, G is None))
        if G is None:
            return fn(A, C, S)
        return fn(A, C, S, G)

    def _build_batched(self, direct: bool, shared: bool, g_none: bool):
        cap = registry.get_backend(self.method).capability
        method, kwargs = self.method, self.kwargs
        reflect = self.sequence.reflect
        run = planned_run if direct else planned_apply
        run_fused = planned_run if direct else planned_apply_batched

        def local_batched(a, c, s, g):
            # mirror SequencePlan._apply_batched_impl, shard-locally:
            # one fused launch / one flattened call / vmap / loop
            if cap.batch_via == "fused":
                return run_fused(method, kwargs, reflect, a, c, s, g)
            if shared and cap.batch_via == "flatten":
                bl, ml, nl = a.shape
                out = run(method, kwargs, reflect,
                          a.reshape(bl * ml, nl), c, s, g)
                return out.reshape(bl, ml, nl)
            if shared:
                return jax.vmap(lambda ai: run(method, kwargs, reflect,
                                               ai, c, s, g))(a)
            if cap.supports_vmap:
                in_axes = (0, 0, 0, None if g is None else 0)
                return jax.vmap(
                    lambda ai, ci, si, gi: run(method, kwargs, reflect,
                                               ai, ci, si, gi),
                    in_axes=in_axes)(a, c, s, g)
            return jnp.stack([
                run(method, kwargs, reflect, a[i], c[i], s[i],
                    None if g is None else g[i])
                for i in range(a.shape[0])])

        wave_spec = P(None, None) if shared else P(None, None, None)
        A_spec = P(None, self.row_axes, None)
        if g_none:
            return jax.jit(compat.shard_map(
                lambda a, c, s: local_batched(a, c, s, None),
                mesh=self.mesh,
                in_specs=(A_spec, wave_spec, wave_spec),
                out_specs=A_spec))
        return jax.jit(compat.shard_map(
            local_batched, mesh=self.mesh,
            in_specs=(A_spec, wave_spec, wave_spec, wave_spec),
            out_specs=A_spec))

    def _column_sharded(self, A):
        from repro.dist.colsharded import rot_sequence_column_sharded_padded
        kw = dict(self.kwargs)
        return rot_sequence_column_sharded_padded(
            A, self.sequence, self.mesh, col_axis=self.col_axis,
            n_b=kw.get("n_b", 64), k_b=kw.get("k_b", 16),
            method=self.method)

    # -- bookkeeping ------------------------------------------------------
    def _check_rows(self, m: int) -> None:
        if int(m) % max(1, self.devices) != 0:
            raise ValueError(
                f"row count {m} does not divide over {self.devices} "
                f"shards ({self.row_axes}); pad the target rows")

    def _launches_per_shard(self, b: int) -> int:
        cap = registry.get_backend(self.method).capability
        if cap.batch_via == "fused":
            return 1
        if cap.batch_via == "flatten" or cap.supports_vmap:
            return 1
        return b

    def comm_components(self, *, batch: int = 1,
                        shared_sequence: bool = True, m: int = 0) -> dict:
        """The plan's comm term (``cost_components``-consistent)."""
        seq = self.sequence
        problem = registry.Problem(
            m=max(1, int(m) or seq.n), n=seq.n, k=seq.k,
            dtype=str(seq.dtype), platform=compat.default_platform(),
            signs=seq.sign is not None, sharded=True, batch=batch,
            shared_sequence=shared_sequence, live_planes=seq.k_live,
            devices=self.devices)
        return registry._comm_components(problem)

    def _count_dispatch(self, A, *, launches: int,
                        shared: bool = True) -> None:
        """Host-side obs accounting of one sharded dispatch.

        The fused kernel's own launch accounting is tracer-guarded and
        never fires under ``shard_map`` tracing, so the dist layer is
        the accounting authority for its dispatches: comm bytes come
        from the same comm-extended model the planner ranked with.
        """
        if not obs.enabled():
            return
        seq = self.sequence
        if A.ndim == 3:
            b, m = int(A.shape[0]), int(A.shape[1])
        else:
            b, m = 1, int(A.shape[0])
        problem = registry.Problem(
            m=m, n=seq.n, k=seq.k, dtype=str(A.dtype),
            platform=compat.default_platform(),
            signs=seq.sign is not None, sharded=True, batch=b,
            shared_sequence=shared, live_planes=seq.k_live,
            devices=self.devices)
        obs.inc("dist.applies")
        comm = registry._comm_components(problem)
        obs.inc("dist.comm_bytes", comm["bytes"])
        obs.gauge("dist.devices", self.devices)
        obs.gauge("dist.launches_per_shard", launches)

    # -- rebinding / serialization ----------------------------------------
    def rebind(self, sequence: RotationSequence) -> "ShardedSequencePlan":
        """Bind the frozen decision to a new same-shape sequence."""
        old = self.sequence
        if sequence.shape != old.shape:
            raise ValueError(
                f"rebind needs matching wave shape {old.shape}; "
                f"got {sequence.shape}")
        if (sequence.sign is not None) != (old.sign is not None) \
                and self.method != _IDENTITY:
            spec = registry.get_backend(self.method)
            if sequence.sign is not None \
                    and not spec.capability.supports_signs:
                raise ValueError(
                    f"plan method {self.method!r} cannot carry per-entry "
                    f"signs; re-plan the sign-carrying sequence")
        new = dataclasses.replace(self, sequence=sequence)
        # the jitted shard_map closures see waves only as call
        # arguments, so a same-structure rebind shares the compiled fns,
        # also those compiled after the rebind (by either plan)
        if sequence.reflect == old.reflect \
                and (sequence.sign is None) == (old.sign is None):
            object.__setattr__(new, "_fn_cache", self._fns())
        return new

    def to_dict(self) -> dict:
        """Serialize the sharded dispatch decision (not waves, not mesh).

        Mirrors :meth:`SequencePlan.to_dict` — JAX-version-keyed, wave
        signature included — plus the mesh *shape contract*: device
        count, row axes, partition.  The mesh itself is process state;
        :meth:`from_dict` rebinds to a live mesh and rejects one whose
        extent over the stored axes differs.
        """
        seq = self.sequence
        d = {
            "format": SHARDED_PLAN_DICT_FORMAT,
            "jax": registry._jax_version_str(),
            "method": self.method,
            "kwargs": dict(self.kwargs),
            "devices": self.devices,
            "row_axes": list(self.row_axes),
            "partition": self.partition,
            "col_axis": self.col_axis,
            "execute_sharded": bool(self.execute_sharded),
            "shape": list(seq.shape),
            "dtype": str(seq.dtype),
            "signed": seq.sign is not None,
            "reflect": bool(seq.reflect),
        }
        if self.plan is not None:
            d["plan"] = {"method": self.plan.method, "n_b": self.plan.n_b,
                         "k_b": self.plan.k_b, "m_blk": self.plan.m_blk,
                         "est_seconds": self.plan.est_seconds,
                         "source": self.plan.source}
        return d

    @classmethod
    def from_dict(cls, d: dict, sequence: RotationSequence,
                  mesh) -> "ShardedSequencePlan":
        """Rebuild a frozen sharded plan bound to ``sequence`` + ``mesh``.

        Raises ``ValueError`` on any mismatch (treat as a cache miss):
        format/JAX version, wave signature, unregistered backend, or a
        mesh whose extent over the stored axes is not the stored device
        count — a sharded decision never transfers across mesh sizes,
        exactly like its plan key.
        """
        if d.get("format") != SHARDED_PLAN_DICT_FORMAT:
            raise ValueError(
                f"unsupported ShardedSequencePlan dict format "
                f"{d.get('format')!r}")
        jax_now = registry._jax_version_str()
        if d.get("jax") != jax_now:
            raise ValueError(
                f"plan serialized under JAX {d.get('jax')!r}; running "
                f"{jax_now}")
        if tuple(d.get("shape", ())) != tuple(sequence.shape):
            raise ValueError(
                f"plan serialized for wave shape {d.get('shape')}; "
                f"sequence has {sequence.shape}")
        if d.get("signed", False) != (sequence.sign is not None) \
                or d.get("reflect", False) != bool(sequence.reflect):
            raise ValueError(
                "plan serialized for a different sign/reflect structure")
        if d.get("dtype") != str(sequence.dtype):
            raise ValueError(
                f"plan serialized for dtype {d.get('dtype')!r}; "
                f"sequence is {sequence.dtype}")
        partition = d.get("partition", "row")
        row_axes = tuple(d.get("row_axes", ("data",)))
        col_axis = d.get("col_axis", "model")
        axes = col_axis if partition == "column" else row_axes
        devices = int(d.get("devices", 1))
        if _mesh_devices(mesh, axes) != devices:
            raise ValueError(
                f"plan serialized for {devices} devices over {axes!r}; "
                f"mesh has {_mesh_devices(mesh, axes)} — sharded "
                f"decisions never transfer across mesh sizes")
        method = d["method"]
        if method != _IDENTITY:
            spec = registry.get_backend(method)  # raises on unknown
            if sequence.sign is not None \
                    and not spec.capability.supports_signs:
                raise ValueError(
                    f"serialized method {method!r} cannot carry signs")
        kwargs = tuple(sorted(d.get("kwargs", {}).items()))
        plan = None
        pd = d.get("plan")
        if pd is not None:
            plan = registry.Plan(
                method=str(pd.get("method", method)), n_b=pd.get("n_b"),
                k_b=pd.get("k_b"), m_blk=pd.get("m_blk"),
                est_seconds=float(pd.get("est_seconds", 0.0)),
                source=str(pd.get("source", "model")))
        return cls(sequence=sequence, mesh=mesh, row_axes=row_axes,
                   method=method, kwargs=kwargs, plan=plan,
                   devices=devices,
                   execute_sharded=bool(d.get("execute_sharded", True)),
                   partition=partition, col_axis=col_axis)

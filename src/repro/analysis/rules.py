"""The repo's invariants as named, suppressible analyzer rules.

Five families, each replacing (and strengthening) a Makefile grep gate
or encoding a contract no grep could see:

* **RA1xx compat isolation** — version-sensitive JAX surface only in
  ``repro.compat`` (replaces ``compat-gate``).
* **RA2xx dispatch layering** — one public entry point, registry-only
  kernel dispatch, typed serve/eig layers (replaces ``seq-gate``,
  ``serve-gate``, ``eig-gate``).
* **RA3xx bitwise contract** — every 2x2 plane application routes
  through :func:`repro.core.rotations.plane_update`; no fold-prone
  literal signs in traced code (the PR 5 bug class).
* **RA4xx kernel hygiene** — no host round-trips or grid-dim
  reductions inside Pallas kernel bodies; on-chip budgets and tile
  clamps single-sourced in :mod:`repro.kernels.limits`.
* **RA5xx plan-cache determinism + timing discipline** — no wall-clock
  or RNG in cache-key or cost-model code paths; all measurement clocks
  routed through :mod:`repro.obs.timing`.

Suppress a single line with ``# repro-lint: disable=RA301`` (or the
family, ``disable=RA3``); grandfather legacy hits via the baseline file
(``python -m repro.analysis --update-baseline``).
"""
from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Sequence, Set, Tuple

from .engine import ModuleInfo, Rule, Violation

__all__ = ["ALL_RULES", "all_rules", "rules_matching"]


# --------------------------------------------------------------------------
# shared helpers
# --------------------------------------------------------------------------

def _in_repro(mi: ModuleInfo) -> bool:
    return mi.module == "repro" or mi.module.startswith("repro.")


def _is_simple(node: ast.AST) -> bool:
    """Leaf-ish operand of a product term: name, attr, index, constant."""
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        return _is_simple(node.operand)
    return isinstance(node, (ast.Name, ast.Attribute, ast.Subscript,
                             ast.Constant))


def _leaf(node: ast.AST) -> str:
    return ast.unparse(node)


def _function_references(mi: ModuleInfo, fn: ast.AST) -> List[str]:
    out = []
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            if isinstance(mi.parents.get(node), ast.Attribute):
                continue
            dd = mi.dotted(node)
            if dd:
                out.append(dd)
        elif isinstance(node, ast.Name) and isinstance(node.ctx, ast.Load):
            if isinstance(mi.parents.get(node), ast.Attribute):
                continue
            out.append(mi.aliases.get(node.id, node.id))
    return out


# --------------------------------------------------------------------------
# RA1xx — compat isolation
# --------------------------------------------------------------------------

class RA101VersionSensitiveAttr(Rule):
    """Version-sensitive JAX API used outside ``repro.compat``.

    Incident: between JAX 0.4 and 0.9 ``shard_map``/``typeof``/
    ``pcast``/``pvary`` and the pltpu ``CompilerParams`` spelling all
    moved or changed name, and ``jax.make_mesh`` began to default to
    Explicit axes, which broke every sharded test at once.  The repo
    runs one JAX line; these lookups stay in one module so that the
    next upgrade edits one file.  The old
    ``compat-gate`` grepped for literal spellings and missed aliased
    imports (``from jax.experimental.shard_map import shard_map as
    smap``); this rule resolves every import alias first.
    """

    id = "RA101"
    title = "version-sensitive JAX API outside compat.py"

    BANNED: Tuple[str, ...] = (
        "jax.shard_map",
        "jax.experimental.shard_map",
        "jax.typeof",
        "jax.lax.pcast",
        "jax.lax.pvary",
        "jax.experimental.pallas.tpu.CompilerParams",
        "jax.experimental.pallas.tpu.TPUCompilerParams",
        "jax.make_mesh",
    )

    def _bad(self, dotted: str) -> Optional[str]:
        for b in self.BANNED:
            if dotted == b or dotted.startswith(b + "."):
                return b
        return None

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if mi.module == "repro.compat":
            return
        for line, target in mi.import_targets:
            b = self._bad(target)
            if b:
                yield Violation(self.id, mi.logical, line,
                                f"import of version-sensitive '{b}'; use "
                                f"the repro.compat shim")
        for node, dotted in mi.references():
            b = self._bad(dotted)
            if b:
                yield self.hit(mi, node,
                               f"use of version-sensitive '{b}'; use the "
                               f"repro.compat shim")


class RA102PlatformProbe(Rule):
    """Backend/platform probed outside ``repro.compat``.

    Incident: scattered ``jax.default_backend()`` calls made CPU-vs-TPU
    behaviour (x64 defaults, interpret-mode defaults) diverge between
    the library and the benchmark harness.  All platform questions go
    through ``compat.default_platform()`` / ``compat.is_tpu()`` so one
    module defines what "on TPU" means.
    """

    id = "RA102"
    title = "platform probe outside compat.py"

    PROBES = ("jax.default_backend", "jax.devices", "jax.local_devices",
              "jax.device_count", "jax.local_device_count")

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if mi.module == "repro.compat":
            return
        for node, dotted in mi.references():
            if dotted in self.PROBES:
                yield self.hit(mi, node,
                               f"platform probe '{dotted}'; use "
                               f"repro.compat.default_platform()/is_tpu()")


class RA103X64FlagMutation(Rule):
    """``jax_enable_x64`` flipped directly instead of via compat.

    Incident: a bare ``jax.config.update("jax_enable_x64", True)`` in a
    test leaked x64 mode into every later test in the process; the
    ``compat.enable_x64()`` context manager restores the previous value
    (and uses ``jax.experimental.enable_x64`` where available).
    """

    id = "RA103"
    title = "jax_enable_x64 mutated outside compat.py"

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if mi.module == "repro.compat":
            return
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = mi.dotted(node.func)
            if dotted != "jax.config.update":
                continue
            if (node.args and isinstance(node.args[0], ast.Constant)
                    and node.args[0].value == "jax_enable_x64"):
                yield self.hit(mi, node,
                               "direct jax_enable_x64 mutation; use the "
                               "repro.compat.enable_x64() context manager")


# --------------------------------------------------------------------------
# RA2xx — dispatch layering
# --------------------------------------------------------------------------

class RA201RawApplyOutsideApi(Rule):
    """``apply_rotation_sequence`` used outside ``repro.core.api``.

    Incident: the raw-array wrapper bypasses ``SequencePlan`` caching
    and re-plans on every call; library code must go through
    ``seq.plan(...)``/``plan.apply(...)``.  The old ``seq-gate`` regex
    ``apply_rotation_sequence\\s*\\(`` missed aliased imports (``from
    repro.core.api import apply_rotation_sequence as _ars``) — this
    rule resolves the alias table, so the call site is caught whatever
    the local name is (see the regression fixture).
    """

    id = "RA201"
    title = "apply_rotation_sequence outside core/api.py"

    ALLOWED = {"repro.core.api", "repro.core"}
    TARGETS = {"repro.core.api.apply_rotation_sequence",
               "repro.core.apply_rotation_sequence",
               "repro.apply_rotation_sequence"}

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.ALLOWED:
            return
        for line, target in mi.import_targets:
            if target in self.TARGETS:
                yield Violation(self.id, mi.logical, line,
                                "import of apply_rotation_sequence; use "
                                "seq.plan(...)/plan.apply(...)")
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = mi.dotted(node.func)
            if dotted in self.TARGETS:
                yield self.hit(mi, node,
                               "call to apply_rotation_sequence; use "
                               "seq.plan(...)/plan.apply(...)")


class RA202KernelImportOutsideRegistry(Rule):
    """``repro.kernels.rotseq*`` imported outside the dispatch layer.

    Incident: the registry's cost model can only keep its promises if
    every rotation-sequence kernel launch flows through it; a direct
    ``rot_sequence_batched(...)`` call skips the SMEM/VMEM budget guard
    and can hand Mosaic a panel it cannot compile.  Only
    ``repro.core.api`` (the registered backends) may import the
    ``rotseq*`` kernel packages; kernels may import each other.
    """

    id = "RA202"
    title = "rotseq kernel import outside core/api.py"

    ALLOWED = {"repro.core.api"}
    PREFIX = "repro.kernels.rotseq"

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.ALLOWED:
            return
        if mi.module.startswith("repro.kernels"):
            return
        for line, target in mi.import_targets:
            if target.startswith(self.PREFIX):
                yield Violation(self.id, mi.logical, line,
                                f"direct kernel import '{target}'; "
                                f"dispatch via repro.core.registry")
        for node, dotted in mi.references():
            if dotted.startswith(self.PREFIX):
                yield self.hit(mi, node,
                               f"direct kernel reference '{dotted}'; "
                               f"dispatch via repro.core.registry")


class RA203TypedLayerOnly(Rule):
    """serve/eig layer reaching below the typed sequence API.

    Incident: the eig and serve layers are consumers of the paper's
    apply machinery; when ``tridiagonalize`` briefly imported
    ``core.blocked`` directly it silently pinned one backend and
    bypassed plan caching.  These layers touch only
    ``RotationSequence``/``SequencePlan`` (plus the registry); the
    backend zoo (``rot_sequence_*``) and internal core modules are off
    limits (replaces ``eig-gate``/``serve-gate``).
    ``repro.kernels.limits`` is carved out: it is pure host arithmetic
    (budget constants, tile clamps) with no kernel machinery, designed
    to be importable from every layer.
    """

    id = "RA203"
    title = "serve/eig layer bypassing the typed API"

    LAYERS = ("repro.serve", "repro.eig")
    BANNED_PREFIXES = ("repro.kernels", "repro.core.blocked",
                       "repro.core.accumulate", "repro.core.ref")
    CARVE_OUTS = ("repro.kernels.limits",)
    BANNED_NAMES = {
        "rot_sequence_blocked", "rot_sequence_accumulated",
        "rot_sequence_unoptimized", "rot_sequence_wavefront",
        "rot_sequence_wave", "rot_sequence_mxu", "rot_sequence_batched",
    }

    def _banned(self, dotted: str) -> bool:
        if any(dotted == c or dotted.startswith(c + ".")
               for c in self.CARVE_OUTS):
            return False
        return (any(dotted == p or dotted.startswith(p + ".")
                    for p in self.BANNED_PREFIXES)
                or dotted.rsplit(".", 1)[-1] in self.BANNED_NAMES)

    def _layer(self, mi: ModuleInfo) -> bool:
        return any(mi.module == p or mi.module.startswith(p + ".")
                   for p in self.LAYERS)

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not self._layer(mi):
            return
        for line, target in mi.import_targets:
            if self._banned(target):
                yield Violation(self.id, mi.logical, line,
                                f"layer import '{target}'; serve/eig use "
                                f"RotationSequence/SequencePlan only")
        for node, dotted in mi.references():
            if self._banned(dotted):
                yield self.hit(mi, node,
                               f"layer reference '{dotted}'; serve/eig "
                               f"use RotationSequence/SequencePlan only")


class RA204StreamConcurrencyDiscipline(Rule):
    """Concurrency primitives outside the stream engine; engine reaching
    below the service.

    Incident: the first streaming-engine draft grew a second ad-hoc
    worker thread inside the launcher and called
    ``SequencePlan.apply_batched`` straight from it — the two dispatch
    paths then raced the (pre-lock) obs counters and double-resolved a
    bucket plan, breaking the exactly-once planning invariant that the
    serving tests pin.  Two confinements, statically:

    * thread/queue primitives (``threading``, ``queue``,
      ``concurrent.futures``, ``_thread``, ``multiprocessing``) live
      only in ``repro.serve.stream`` — the serving stack's one
      concurrent component.  Carve-outs for the pre-existing
      infrastructure users: ``repro.obs.*`` (metric/trace locks),
      ``repro.ckpt.manager`` (async checkpoint writer), and
      ``repro.parallel.sharding`` (a ``threading.local`` for axis-rule
      scoping).
    * ``repro.serve.stream`` itself executes only through
      ``RotationService`` bucket internals /
      ``SequencePlan.apply_batched`` handles — importing
      ``repro.core.*`` or ``repro.kernels.*`` machinery from the engine
      would open a second dispatch path next to the service's
      plan-exactly-once state.
    """

    id = "RA204"
    title = "thread/queue primitive outside serve.stream, or engine " \
            "bypassing the service"

    THREAD_ROOTS = ("threading", "queue", "concurrent.futures", "_thread",
                    "multiprocessing")
    ENGINE = "repro.serve.stream"
    CARVE_OUTS = ("repro.obs", "repro.ckpt.manager",
                  "repro.parallel.sharding")
    ENGINE_BANNED_PREFIXES = ("repro.core", "repro.kernels")

    def _thread_primitive(self, dotted: str) -> Optional[str]:
        for root in self.THREAD_ROOTS:
            if dotted == root or dotted.startswith(root + "."):
                return root
        return None

    def _carved_out(self, mi: ModuleInfo) -> bool:
        return any(mi.module == c or mi.module.startswith(c + ".")
                   for c in self.CARVE_OUTS)

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        if mi.module == self.ENGINE:
            # inside the engine: concurrency is fine, core/kernels are not
            for line, target in mi.import_targets:
                if any(target == p or target.startswith(p + ".")
                       for p in self.ENGINE_BANNED_PREFIXES):
                    yield Violation(
                        self.id, mi.logical, line,
                        f"stream engine import '{target}'; execute only "
                        f"through RotationService/SequencePlan handles")
            return
        if self._carved_out(mi):
            return
        imported_roots = {t.split(".")[0] for _l, t in mi.import_targets}
        for line, target in mi.import_targets:
            root = self._thread_primitive(target)
            if root:
                yield Violation(
                    self.id, mi.logical, line,
                    f"thread/queue primitive '{root}' outside "
                    f"repro.serve.stream; the stream engine is the one "
                    f"concurrent serving component")
        for node, dotted in mi.references():
            root = self._thread_primitive(dotted)
            # a local variable that happens to be named `queue` yields
            # dotted chains like "queue.append" — only flag chains whose
            # root module is actually imported here
            if root and dotted.split(".")[0] in imported_roots:
                yield self.hit(
                    mi, node,
                    f"thread/queue reference '{dotted}' outside "
                    f"repro.serve.stream; the stream engine is the one "
                    f"concurrent serving component")


class RA205SilentSharedSequenceDefault(Rule):
    """``Problem(batch>1)`` built outside the plan layers without saying
    whether the batch shares one rotation sequence.

    Incident: the serving path bucketed b independent requests into one
    ``(b, m, n)`` dispatch and priced it as ``Problem(batch=64)`` — the
    ``shared_sequence=True`` default silently claimed the per-sequence
    setup (packing, Q_t accumulation) would be paid once and amortized
    over the batch.  It is paid ``b`` times for per-request traffic, so
    ``method="auto"`` picked ``accumulated``, rebuilt 64 factor sets per
    flush, and ran ~10x slower than the fused kernel at batch 64; the
    serving bench had to pin ``method="rotseq_batched"`` to stay above
    its throughput floor.  The fix threads ``shared_sequence`` from
    every producer, and this rule keeps the default from lying again:
    any ``repro.*`` module outside ``repro.core.registry`` /
    ``repro.core.sequence`` (the layers that *define* the pricing and
    normalize the flag) that constructs a registry ``Problem`` with a
    batch that is not literally 1 must spell ``shared_sequence=``
    explicitly — whichever value it means.
    """

    id = "RA205"
    title = "batched Problem() without explicit shared_sequence"

    ALLOWED = {"repro.core.registry", "repro.core.sequence"}
    TARGETS = {"repro.core.registry.Problem"}

    @staticmethod
    def _batch_may_exceed_one(node: ast.AST) -> bool:
        # literal 0/1 batches price identically either way; anything
        # else (a larger literal, or a runtime value we cannot see
        # through) can be a per-request bucket and must be labelled
        if isinstance(node, ast.Constant) and node.value in (0, 1, True):
            return False
        return True

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.ALLOWED:
            return
        for node in ast.walk(mi.tree):
            if not isinstance(node, ast.Call):
                continue
            if mi.dotted(node.func) not in self.TARGETS:
                continue
            kw_names = {kw.arg for kw in node.keywords}
            if None in kw_names:
                continue  # **splat may carry shared_sequence; can't see
            if "shared_sequence" in kw_names:
                continue
            batch = next((kw.value for kw in node.keywords
                          if kw.arg == "batch"), None)
            if batch is not None and self._batch_may_exceed_one(batch):
                yield self.hit(
                    mi, node,
                    "Problem(batch=...) without shared_sequence=; a "
                    "per-request bucket priced as a shared-sequence "
                    "batch amortizes setup it actually pays b times — "
                    "say shared_sequence=True/False explicitly")


class RA206SpmdConfinement(Rule):
    """SPMD primitive outside the dist layer, or dist importing kernels.

    Incident (PR 10): the distributed path became a first-class plan
    (``repro.dist``) precisely so that sharded execution flows through
    the same registry arbitration, plan cache, and obs attribution as
    everything else.  Two confinements keep it that way:

    * SPMD collectives and mesh primitives (``shard_map``,
      ``ppermute``, ``axis_index``, ``psum``, ``all_gather``, …) live
      only in ``repro.dist`` / ``repro.parallel`` / ``repro.compat``
      (the version shim that *defines* the ``shard_map`` spelling).  A
      stray collective elsewhere is a second distribution path the
      comm-extended cost model cannot see.
    * ``repro.dist`` itself never imports ``repro.kernels.*`` — every
      shard executes through the planned :mod:`repro.core.sequence`
      hooks (``planned_apply`` / ``planned_apply_batched``), so a
      sharded dispatch cannot dodge the registry's SMEM/VMEM budget
      guard or launch accounting.  ``repro.kernels.limits`` stays
      importable (pure host arithmetic, same carve-out as RA203).
    """

    id = "RA206"
    title = "SPMD primitive outside repro.dist, or dist importing kernels"

    ALLOWED = ("repro.dist", "repro.parallel", "repro.compat")
    DIST = "repro.dist"
    SPMD_NAMES = {"shard_map", "ppermute", "axis_index", "psum", "pmean",
                  "all_gather", "psum_scatter", "all_to_all", "pshuffle"}
    KERNEL_PREFIX = "repro.kernels"
    KERNEL_CARVE_OUTS = ("repro.kernels.limits",)

    def _spmd(self, dotted: str) -> bool:
        if dotted.rsplit(".", 1)[-1] not in self.SPMD_NAMES:
            return False
        return dotted.startswith(("jax.", "repro.compat."))

    def _kernel(self, dotted: str) -> bool:
        if any(dotted == c or dotted.startswith(c + ".")
               for c in self.KERNEL_CARVE_OUTS):
            return False
        return (dotted == self.KERNEL_PREFIX
                or dotted.startswith(self.KERNEL_PREFIX + "."))

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        if mi.module == self.DIST or mi.module.startswith(self.DIST + "."):
            for line, target in mi.import_targets:
                if self._kernel(target):
                    yield Violation(
                        self.id, mi.logical, line,
                        f"kernel import '{target}' in repro.dist; shards "
                        f"execute through the planned repro.core.sequence "
                        f"hooks only")
            for node, dotted in mi.references():
                if self._kernel(dotted):
                    yield self.hit(
                        mi, node,
                        f"kernel reference '{dotted}' in repro.dist; "
                        f"shards execute through the planned "
                        f"repro.core.sequence hooks only")
            return
        if any(mi.module == a or mi.module.startswith(a + ".")
               for a in self.ALLOWED):
            return
        for line, target in mi.import_targets:
            if self._spmd(target):
                yield Violation(
                    self.id, mi.logical, line,
                    f"SPMD primitive import '{target}' outside repro.dist; "
                    f"distribution goes through repro.dist plans")
        for node, dotted in mi.references():
            if self._spmd(dotted):
                yield self.hit(
                    mi, node,
                    f"SPMD primitive '{dotted}' outside repro.dist; "
                    f"distribution goes through repro.dist plans")


# --------------------------------------------------------------------------
# RA3xx — bitwise contract
# --------------------------------------------------------------------------

def _mult_terms(node: ast.AST) -> Optional[Tuple[str, str, bool]]:
    """Decompose ``a * b`` into (leaf_a, leaf_b, negated).

    ``-a * b`` (parsed as ``(-a) * b``) and ``-(a * b)`` both normalize
    to the positive pair with ``negated=True`` so the crosswise matcher
    sees ``-s*x + c*y`` and ``s*x - c*y`` as the same subtraction form.
    """
    neg = False
    if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
        neg = True
        node = node.operand
    if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult)):
        return None
    left, right = node.left, node.right
    if isinstance(left, ast.UnaryOp) and isinstance(left.op, ast.USub):
        neg = not neg
        left = left.operand
    if isinstance(right, ast.UnaryOp) and isinstance(right.op, ast.USub):
        neg = not neg
        right = right.operand
    if not (_is_simple(left) and _is_simple(right)):
        return None
    return _leaf(left), _leaf(right), neg


def _two_term_forms(node: ast.BinOp) -> Optional[Tuple[str, Tuple, Tuple]]:
    """Classify ``t1 + t2`` / ``t1 - t2`` of two products as add/sub form.

    Returns ``(form, pair1, pair2)`` where each pair is a frozenset of
    the two leaf strings of one product and ``form`` folds all sign
    information: ``c*x + s*y`` -> add; ``s*x - c*y`` and ``-s*x + c*y``
    -> sub.
    """
    if not isinstance(node.op, (ast.Add, ast.Sub)):
        return None
    t1 = _mult_terms(node.left)
    t2 = _mult_terms(node.right)
    if t1 is None or t2 is None:
        return None
    a1, b1, n1 = t1
    a2, b2, n2 = t2
    sub = isinstance(node.op, ast.Sub)
    # fold term signs: (-u) + v == v - u; u - (-v) == u + v; etc.
    effective_sub = (n1 != n2) != sub
    return ("sub" if effective_sub else "add",
            frozenset((a1, b1)), frozenset((a2, b2)))


class RA301InlinePlaneStencil(Rule):
    """Inline 2x2 plane application instead of ``plane_update``.

    Incident (PR 5): two hand-inlined copies of the rotation stencil
    drifted — one contracted ``g*(s*x - c*y)`` and one ``-s*x + c*y``,
    which XLA fuses into different multiply orders, so the "same"
    sequence produced bit-different planes on different paths and the
    bit-stability suite only caught it on one backend.  Every crosswise
    pair ``{c*x + s*y, s*x - c*y}`` over the same four operands must be
    the one canonical :func:`repro.core.rotations.plane_update`.
    """

    id = "RA301"
    title = "inline 2x2 plane stencil (use plane_update)"

    EXEMPT = {"repro.core.rotations"}

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.EXEMPT:
            return
        for fn in mi.functions():
            adds: List[Tuple[ast.AST, frozenset, frozenset]] = []
            subs: List[Tuple[ast.AST, frozenset, frozenset]] = []
            for node in ast.walk(fn):
                if not isinstance(node, ast.BinOp):
                    continue
                form = _two_term_forms(node)
                if form is None:
                    continue
                kind, p1, p2 = form
                (adds if kind == "add" else subs).append((node, p1, p2))
            reported: Set[int] = set()
            for anode, ap1, ap2 in adds:
                leaves = ap1 | ap2
                if len(leaves) != 4:
                    continue
                for snode, sp1, sp2 in subs:
                    if (sp1 | sp2) != leaves:
                        continue
                    if {sp1, sp2} == {ap1, ap2}:
                        continue  # same pairing: sum/difference, not a plane
                    target = max(anode.lineno, snode.lineno)
                    if target in reported:
                        continue
                    reported.add(target)
                    node = anode if anode.lineno == target else snode
                    yield self.hit(
                        mi, node,
                        "inline 2x2 plane stencil; route through "
                        "repro.core.rotations.plane_update")


class RA302FoldableSignLiteral(Rule):
    """Literal ``±1`` sign handed to ``plane_update`` in traced code.

    Incident (PR 5): passing the reflector sign as a Python scalar let
    XLA constant-fold ``g * (...)`` into a re-associated contraction,
    flipping low-order bits between the fused kernel and the reference.
    In traced (jax/jnp-using) functions the sign must be a runtime
    array (``jnp.where(refl, -1.0, 1.0)``-style), which the fold cannot
    see through.  Host-side numpy recurrences (eig layer) are exempt:
    nothing folds them.
    """

    id = "RA302"
    title = "foldable scalar sign in traced plane_update call"

    def _literal_sign(self, node: ast.AST) -> bool:
        if isinstance(node, ast.UnaryOp) and isinstance(node.op, ast.USub):
            node = node.operand
        return (isinstance(node, ast.Constant)
                and isinstance(node.value, (int, float))
                and abs(node.value) == 1)

    def _traced(self, mi: ModuleInfo, fn: ast.AST) -> bool:
        return any(ref == "jax" or ref.startswith("jax.")
                   for ref in _function_references(mi, fn))

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        for fn in mi.functions():
            traced = None  # lazy: only probe functions that call the API
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                dotted = mi.dotted(node.func) or ""
                if not (dotted == "plane_update"
                        or dotted.endswith(".plane_update")):
                    continue
                g = node.args[4] if len(node.args) >= 5 else None
                for kw in node.keywords:
                    if kw.arg == "g":
                        g = kw.value
                if g is None or not self._literal_sign(g):
                    continue
                if traced is None:
                    traced = self._traced(mi, fn)
                if traced:
                    yield self.hit(
                        mi, node,
                        "literal ±1 sign in traced plane_update call; "
                        "pass a runtime array so XLA cannot fold it")


# --------------------------------------------------------------------------
# RA4xx — kernel hygiene
# --------------------------------------------------------------------------

def _kernel_bodies(mi: ModuleInfo) -> List[ast.AST]:
    """FunctionDefs that are Pallas kernel bodies in this module.

    Resolves the repo's idiom: ``kernel = functools.partial(_kern, ...)``
    then ``pl.pallas_call(kernel, ...)`` — the first pallas_call
    argument is unwrapped through the partial assignment to the
    underlying FunctionDef.
    """
    defs: Dict[str, ast.AST] = {
        fn.name: fn for fn in mi.functions()}
    partial_of: Dict[str, str] = {}
    for node in ast.walk(mi.tree):
        if not (isinstance(node, ast.Assign) and len(node.targets) == 1
                and isinstance(node.targets[0], ast.Name)
                and isinstance(node.value, ast.Call)):
            continue
        dotted = mi.dotted(node.value.func) or ""
        if dotted == "functools.partial" and node.value.args \
                and isinstance(node.value.args[0], ast.Name):
            partial_of[node.targets[0].id] = node.value.args[0].id
    bodies: List[ast.AST] = []
    for node in ast.walk(mi.tree):
        if not isinstance(node, ast.Call):
            continue
        dotted = mi.dotted(node.func) or ""
        if not dotted.endswith(".pallas_call"):
            continue
        if not node.args:
            continue
        arg = node.args[0]
        name = None
        if isinstance(arg, ast.Name):
            name = partial_of.get(arg.id, arg.id)
        elif isinstance(arg, ast.Call):  # inline functools.partial(...)
            inner = mi.dotted(arg.func) or ""
            if inner == "functools.partial" and arg.args \
                    and isinstance(arg.args[0], ast.Name):
                name = arg.args[0].id
        if name and name in defs:
            bodies.append(defs[name])
    return bodies


class RA401KernelHostRoundTrip(Rule):
    """Host round-trip inside a Pallas kernel body.

    Incident: an ``.item()`` debug probe in an interpret-mode kernel
    ran green locally, then failed Mosaic lowering on TPU — interpret
    mode executes host Python that compiled kernels cannot.  Kernel
    bodies stay pure traced code: no ``float()``/``bool()`` on traced
    values, no ``.item()``, no host numpy, no ``jax.device_get``.
    """

    id = "RA401"
    title = "host round-trip in Pallas kernel body"

    HOST_CALLS = {"float", "bool"}

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        for body in _kernel_bodies(mi):
            for node in ast.walk(body):
                if isinstance(node, ast.Call):
                    if isinstance(node.func, ast.Name) \
                            and node.func.id in self.HOST_CALLS \
                            and node.func.id not in mi.aliases:
                        yield self.hit(
                            mi, node,
                            f"host conversion {node.func.id}() in kernel "
                            f"body; kernels must stay traced")
                    elif isinstance(node.func, ast.Attribute) \
                            and node.func.attr == "item":
                        yield self.hit(
                            mi, node,
                            ".item() in kernel body; kernels must stay "
                            "traced")
                dotted = None
                if isinstance(node, ast.Attribute) and not isinstance(
                        mi.parents.get(node), ast.Attribute):
                    dotted = mi.dotted(node)
                if dotted and (dotted.startswith("numpy.")
                               or dotted == "jax.device_get"):
                    yield self.hit(
                        mi, node,
                        f"host reference '{dotted}' in kernel body; "
                        f"kernels must stay traced")


class RA402GridDimReduction(Rule):
    """``jnp`` reduction over a traced grid index in a kernel body.

    Incident: reducing an expression built from ``pl.program_id``
    inside a kernel re-materializes the grid dimension as data — it
    traces in interpret mode but defeats the revisiting/pipelining
    analysis the grid exists to express, and Mosaic lowers it to a
    serialized scan.  Grid-dim logic belongs in index maps, not
    reductions.
    """

    id = "RA402"
    title = "jnp reduction over traced grid dim in kernel body"

    REDUCTIONS = {"sum", "max", "min", "prod", "mean", "any", "all",
                  "argmax", "argmin", "cumsum", "cumprod"}
    GRID_FNS = (".program_id", ".num_programs")

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        for body in _kernel_bodies(mi):
            for node in ast.walk(body):
                if not isinstance(node, ast.Call):
                    continue
                dotted = mi.dotted(node.func) or ""
                if not (dotted.startswith("jax.numpy.")
                        and dotted.rsplit(".", 1)[-1] in self.REDUCTIONS):
                    continue
                hit = False
                for a in node.args:
                    for sub in ast.walk(a):
                        if isinstance(sub, ast.Call):
                            inner = mi.dotted(sub.func) or ""
                            if inner.endswith(self.GRID_FNS):
                                hit = True
                    if hit:
                        break
                if hit:
                    yield self.hit(
                        mi, node,
                        "jnp reduction over pl.program_id/num_programs; "
                        "express grid logic in index maps instead")


class RA403BudgetConstantOutsideLimits(Rule):
    """On-chip budget constant defined outside ``repro.kernels.limits``.

    Incident (PR 5): the SMEM panel budget lived in the registry cost
    guard while the kernel wrapper carried its own copy of the clamp,
    coupled only by a "mirror the kernel" comment; retuning one side
    would silently misprice the other.  Budget constants
    (``*_BUDGET``) are defined once in :mod:`repro.kernels.limits` and
    imported everywhere else.
    """

    id = "RA403"
    title = "budget constant defined outside kernels/limits.py"

    EXEMPT = {"repro.kernels.limits"}
    NAME_RE = re.compile(r"^_?[A-Z0-9]*(SMEM|VMEM)[A-Z0-9_]*BUDGET$|"
                         r"^_?[A-Z0-9_]*BUDGET$")

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.EXEMPT:
            return
        for node in ast.walk(mi.tree):
            targets: List[ast.AST] = []
            if isinstance(node, ast.Assign):
                targets = node.targets
            elif isinstance(node, ast.AnnAssign) and node.value is not None:
                targets = [node.target]
            for t in targets:
                if isinstance(t, ast.Name) and self.NAME_RE.match(t.id) \
                        and t.id not in mi.aliases:
                    yield self.hit(
                        mi, node,
                        f"budget constant '{t.id}' defined here; define "
                        f"in repro.kernels.limits and import it")


def _is_round_up_expr(node: ast.AST) -> bool:
    """Match the hand-inlined ``((x + M-1) // M) * M`` round-up shape."""
    if not (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Mult)):
        return False
    for div, mult in ((node.left, node.right), (node.right, node.left)):
        if not (isinstance(div, ast.BinOp)
                and isinstance(div.op, ast.FloorDiv)):
            continue
        add = div.left
        m_str = ast.unparse(mult)
        if ast.unparse(div.right) != m_str:
            continue
        # ((x + mult - 1) // mult) parses the numerator as Sub(Add(..), 1)
        if isinstance(add, ast.BinOp) and isinstance(add.op, ast.Sub) \
                and isinstance(add.right, ast.Constant) \
                and add.right.value == 1:
            inner = add.left
            if isinstance(inner, ast.BinOp) \
                    and isinstance(inner.op, ast.Add) \
                    and m_str in (ast.unparse(inner.left),
                                  ast.unparse(inner.right)):
                return True
        if not (isinstance(add, ast.BinOp) and isinstance(add.op, ast.Add)):
            continue
        for k in (add.left, add.right):
            if isinstance(k, ast.Constant) and isinstance(mult, ast.Constant) \
                    and isinstance(k.value, int) \
                    and k.value == mult.value - 1:
                return True
            if ast.unparse(k) == f"{m_str} - 1":
                return True
    return False


class RA404RederivedClamp(Rule):
    """Tile round-up/clamp re-derived instead of imported from limits.

    Incident (PR 5): three private ``_round_up`` copies plus an inline
    ``((m + 7) // 8) * 8`` in the registry meant the cost guard's idea
    of the kernel's padded shape could drift from the kernel's own.
    :func:`repro.kernels.limits.round_up` and
    :func:`repro.kernels.limits.clamp_m_blk` are the only definitions.
    """

    id = "RA404"
    title = "round-up/clamp re-derived outside kernels/limits.py"

    EXEMPT = {"repro.kernels.limits"}
    HELPER_NAMES = {"round_up", "_round_up", "clamp_m_blk", "_clamp_m_blk"}

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi) or mi.module in self.EXEMPT:
            return
        for fn in mi.functions():
            if fn.name in self.HELPER_NAMES:
                yield self.hit(
                    mi, fn,
                    f"local helper '{fn.name}' shadows "
                    f"repro.kernels.limits; import it instead")
        for node in ast.walk(mi.tree):
            if isinstance(node, ast.BinOp) and _is_round_up_expr(node):
                yield self.hit(
                    mi, node,
                    "inline ((x + M-1) // M) * M round-up; use "
                    "repro.kernels.limits.round_up/clamp_m_blk")


# --------------------------------------------------------------------------
# RA5xx — plan-cache determinism
# --------------------------------------------------------------------------

class RA501NondeterministicKeyPath(Rule):
    """Wall-clock or RNG in a cache-key or cost-model function.

    Incident: ``method="auto"`` must be a pure function of the problem.
    The registry memoizes plans in process by key, and the benchmark
    ledger compares runs that must resolve the same plans; a timestamp
    or RNG draw folded into a plan key (or a cost estimate) makes two
    identical problems hash to different plans, silently defeating the
    memo and making a ledger line irreproducible.  ``cost_*``/``*_key``
    functions must be pure.
    """

    id = "RA501"
    title = "time/random in cache-key or cost-model path"

    FUNC_RE = re.compile(r"^(cost_|plan_key$|cache_key$|fingerprint)|_key$")
    BANNED_ROOTS = ("time", "random", "secrets", "uuid")
    BANNED_PREFIXES = ("numpy.random", "datetime", "os.urandom",
                       "jax.random")

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not _in_repro(mi):
            return
        for fn in mi.functions():
            if not self.FUNC_RE.search(fn.name):
                continue
            for node in ast.walk(fn):
                dotted = None
                if isinstance(node, ast.Attribute) and not isinstance(
                        mi.parents.get(node), ast.Attribute):
                    dotted = mi.dotted(node)
                if not dotted:
                    continue
                root = dotted.split(".")[0]
                if root in self.BANNED_ROOTS or any(
                        dotted == p or dotted.startswith(p + ".")
                        for p in self.BANNED_PREFIXES):
                    yield self.hit(
                        mi, node,
                        f"nondeterministic '{dotted}' in key/cost path "
                        f"'{fn.name}'; keys and costs must be pure")


class RA502AdHocTiming(Rule):
    """Ad-hoc wall-clock timing outside ``repro.obs``.

    Incident (PR 7): a benchmark suite, the train loop, the serve
    launcher and three examples each carried a private
    ``time.perf_counter()`` stopwatch.  When a serving throughput number
    was found to count identity pad slots as served requests, every
    copy had to be audited by hand to establish which numbers were
    comparable.  :mod:`repro.obs.timing` is the single sanctioned clock;
    library and example code must not reference the stdlib clocks or
    ``timeit`` directly.  Tests are out of scope: they assert on
    behaviour, not on published numbers.  Speed is read from device
    traces by the benchmark under ``bench/``, outside this rule's
    scope.
    """

    id = "RA502"
    title = "ad-hoc timing outside repro.obs"

    SCOPES = ("repro", "examples")
    EXEMPT = {"repro.obs"}
    CLOCKS = ("time.time", "time.time_ns", "time.perf_counter",
              "time.perf_counter_ns", "time.monotonic",
              "time.monotonic_ns", "time.process_time",
              "time.process_time_ns")

    def _scoped(self, mi: ModuleInfo) -> bool:
        if not any(mi.module == s or mi.module.startswith(s + ".")
                   for s in self.SCOPES):
            return False
        return not (mi.module in self.EXEMPT
                    or mi.module.startswith("repro.obs."))

    @staticmethod
    def _is_timeit(dotted: str) -> bool:
        return dotted == "timeit" or dotted.startswith("timeit.")

    def check(self, mi: ModuleInfo) -> Iterable[Violation]:
        if not self._scoped(mi):
            return
        for line, target in mi.import_targets:
            if self._is_timeit(target):
                yield Violation(self.id, mi.logical, line,
                                "import of timeit; time through "
                                "repro.obs.timing instead")
            elif target in self.CLOCKS:
                yield Violation(self.id, mi.logical, line,
                                f"import of stdlib clock '{target}'; use "
                                f"repro.obs.timing.now()")
        for node, dotted in mi.references():
            if dotted in self.CLOCKS:
                yield self.hit(
                    mi, node,
                    f"ad-hoc clock '{dotted}'; repro.obs.timing is the "
                    f"single sanctioned timing home")
            elif self._is_timeit(dotted):
                yield self.hit(
                    mi, node,
                    f"timeit reference '{dotted}'; time through "
                    f"repro.obs.timing instead")


# --------------------------------------------------------------------------
# registry
# --------------------------------------------------------------------------

ALL_RULES: Tuple[type, ...] = (
    RA101VersionSensitiveAttr,
    RA102PlatformProbe,
    RA103X64FlagMutation,
    RA201RawApplyOutsideApi,
    RA202KernelImportOutsideRegistry,
    RA203TypedLayerOnly,
    RA204StreamConcurrencyDiscipline,
    RA205SilentSharedSequenceDefault,
    RA206SpmdConfinement,
    RA301InlinePlaneStencil,
    RA302FoldableSignLiteral,
    RA401KernelHostRoundTrip,
    RA402GridDimReduction,
    RA403BudgetConstantOutsideLimits,
    RA404RederivedClamp,
    RA501NondeterministicKeyPath,
    RA502AdHocTiming,
)


def all_rules() -> List[Rule]:
    return [cls() for cls in ALL_RULES]


def rules_matching(selectors: Sequence[str]) -> List[Rule]:
    """Instantiate rules whose id matches any selector prefix.

    ``RA2`` selects the whole family; ``RA203`` one rule.
    """
    out = []
    for cls in ALL_RULES:
        rule = cls()
        if any(rule.id.startswith(sel) for sel in selectors):
            out.append(rule)
    return out

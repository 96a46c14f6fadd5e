"""Distributed tests run in subprocesses with 8 host devices so the main
pytest process keeps a single device (the dry-run owns 512).  Pure
cost-model/plan-key tests (no mesh needed) run in-process."""
import math
import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str):
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = REPO_SRC + os.pathsep + env.get("PYTHONPATH", "")
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, timeout=900, env=env)
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    return r.stdout


def test_row_and_column_sharded_rotseq():
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro.core.rotations import random_sequence
        from repro.core.ref import rot_sequence_numpy
        from repro.dist import (rot_sequence_row_sharded,
            rot_sequence_column_sharded_padded)
        mesh = compat.make_mesh((2, 4), ("data", "model"))
        rng = np.random.default_rng(5)
        for (m, n, k, n_b, k_b, method) in [
                (8, 32, 5, 4, 2, "blocked"), (16, 64, 7, 8, 4, "blocked"),
                (8, 32, 9, 8, 3, "accumulated"),
                (4, 64, 2, 16, 8, "accumulated")]:
            A = rng.standard_normal((m, n)).astype(np.float32)
            seq = random_sequence(jax.random.key(n + k), n, k)
            ref = rot_sequence_numpy(A, seq.cos, seq.sin)
            o1 = rot_sequence_row_sharded(jnp.array(A), seq, mesh,
                                          n_b=n_b, k_b=k_b)
            o2 = rot_sequence_column_sharded_padded(
                jnp.array(A), seq, mesh, col_axis="model",
                n_b=n_b, k_b=k_b, row_axes=("data",), method=method)
            for o in (o1, o2):
                err = np.abs(np.asarray(o, np.float64) - ref).max()
                assert err < 1e-4, (m, n, k, method, err)
        # the deprecated raw (A, C, S, mesh) positional form is removed:
        # passing bare cos arrays is now a plain TypeError, not a warning
        A = rng.standard_normal((8, 32)).astype(np.float32)
        seq = random_sequence(jax.random.key(0), 32, 5)
        ref = rot_sequence_numpy(A, seq.cos, seq.sin)
        try:
            rot_sequence_row_sharded(jnp.array(A), seq.cos, seq.sin,
                                     mesh, n_b=4, k_b=2)
        except TypeError:
            pass  # too many positional arguments
        else:
            raise AssertionError("raw (A, C, S, mesh) form must raise")
        try:
            rot_sequence_row_sharded(jnp.array(A), seq.cos, mesh=mesh)
        except TypeError as e:
            assert "RotationSequence" in str(e), e
        else:
            raise AssertionError("raw-array seq must raise TypeError")
        # mesh accepted as a keyword; forgetting it is a clear TypeError
        o = rot_sequence_row_sharded(jnp.array(A), seq, mesh=mesh,
                                     n_b=4, k_b=2)
        assert np.abs(np.asarray(o, np.float64) - ref).max() < 1e-4
        try:
            rot_sequence_row_sharded(jnp.array(A), seq)
        except TypeError as e:
            assert "mesh" in str(e), e
        else:
            raise AssertionError("missing mesh must raise TypeError")
        print("DIST OK")
    """)
    assert "DIST OK" in out


def test_core_distributed_compat_wrapper():
    """repro.core.distributed delegates to repro.dist with a
    DeprecationWarning and identical results."""
    out = _run("""
        import warnings
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro.core.rotations import random_sequence
        from repro import dist
        mesh = compat.make_mesh((8,), ("data",))
        rng = np.random.default_rng(7)
        A = jnp.asarray(rng.standard_normal((16, 32)), jnp.float32)
        seq = random_sequence(jax.random.key(3), 32, 5)
        ref = dist.rot_sequence_row_sharded(A, seq, mesh, n_b=8, k_b=2)
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            from repro.core.distributed import rot_sequence_row_sharded
            o = rot_sequence_row_sharded(A, seq, mesh, n_b=8, k_b=2)
        assert any(issubclass(x.category, DeprecationWarning) for x in w), \\
            [x.category for x in w]
        assert any("repro.dist" in str(x.message) for x in w)
        assert jnp.array_equal(o, ref)
        print("COMPAT OK")
    """)
    assert "COMPAT OK" in out


def test_sharded_fused_parity_and_obs():
    """Acceptance bar: a batch bucket row-sharded over the forced
    8-device mesh executes one planned launch per shard and is
    bit-identical to the replicated ``apply_batched`` — for plain,
    signed, and reflector sequences."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro import dist, obs
        from repro.core.rotations import random_sequence
        from repro.core.sequence import RotationSequence
        mesh = compat.make_mesh((8,), ("data",))
        rng = np.random.default_rng(0)
        b, m, n, k = 8, 64, 32, 6
        A = jnp.asarray(rng.standard_normal((b, m, n)), jnp.float32)
        base = random_sequence(jax.random.key(1), n, k)
        G = jnp.asarray(np.where(rng.standard_normal((n - 1, k)) > 0,
                                 1.0, -1.0), jnp.float32)
        variants = {
            "plain": base,
            "signed": RotationSequence(base.cos, base.sin, G),
            "reflector": RotationSequence(base.cos, base.sin, None, True),
        }
        for name, seq in variants.items():
            plan = dist.plan_sharded(seq, like=A, mesh=mesh,
                                     method="blocked")
            rep = seq.plan(like=A, method="blocked",
                           shared_sequence=True).apply_batched(A)
            out = plan.apply_batched(A)
            assert jnp.array_equal(out, rep), name
        # obs accounting: exactly one planned launch per shard, a
        # modeled comm-bytes counter, the mesh size as a gauge, and a
        # span around each dispatch that never waits for the device
        obs.set_enabled(True)
        obs.reset()
        obs.runtime.set_trace_path("unused.json")
        plan = dist.plan_sharded(variants["plain"], like=A, mesh=mesh,
                                 method="blocked")
        want = plan.apply_batched(A)   # compiled outside the check
        obs.reset()
        wait = jax.block_until_ready
        def refuse(*a, **k):
            raise AssertionError("a sharded apply waited for the device")
        jax.block_until_ready = refuse
        out2 = plan.apply(A[0])
        out3 = plan.apply_batched(A)
        jax.block_until_ready = wait
        snap = obs.snapshot()
        spans = obs.trace.events()
        obs.set_enabled(False)
        assert jnp.array_equal(out3, want), "obs changed the output"
        assert out2.shape == A[0].shape
        assert snap["gauges"]["dist.launches_per_shard"] == 1.0, snap
        assert snap["gauges"]["dist.devices"] == 8.0
        assert snap["counters"]["dist.comm_bytes"] > 0
        assert snap["counters"]["dist.applies"] == 2
        assert "roofline" not in snap
        assert "dist.apply_seconds" not in snap["histograms"]
        assert [e["name"] for e in spans] == ["dist.apply",
                                              "dist.apply_batched"], spans
        assert spans[1]["args"]["devices"] == 8, spans
        print("PARITY OK")
    """)
    assert "PARITY OK" in out


def test_sharded_plan_grad_and_roundtrip():
    """custom_vjp parity through ``ShardedSequencePlan.apply`` and the
    to_dict/from_dict round-trip (mesh re-supplied at load)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro import dist
        from repro.dist import ShardedSequencePlan
        from repro.core.rotations import random_sequence
        mesh = compat.make_mesh((8,), ("data",))
        rng = np.random.default_rng(2)
        A = jnp.asarray(rng.standard_normal((64, 32)), jnp.float32)
        seq = random_sequence(jax.random.key(4), 32, 6)
        plan = dist.plan_sharded(seq, like=A, mesh=mesh, method="blocked")
        rp = seq.plan(like=A, method="blocked")
        g_sh = jax.grad(lambda x: (plan.apply(x) ** 2).sum())(A)
        g_rep = jax.grad(lambda x: (rp.apply(x) ** 2).sum())(A)
        assert jnp.allclose(g_sh, g_rep, rtol=1e-5, atol=1e-5)
        # serialization round-trip: the mesh cannot ride in JSON, so it
        # is re-supplied; the restored plan applies identically
        d = plan.to_dict()
        import json
        d = json.loads(json.dumps(d))
        plan2 = ShardedSequencePlan.from_dict(d, seq, mesh)
        assert plan2.devices == plan.devices
        assert plan2.execute_sharded == plan.execute_sharded
        assert jnp.array_equal(plan2.apply(A), plan.apply(A))
        print("GRAD OK")
    """)
    assert "GRAD OK" in out


def test_auto_crossover_small_and_large():
    """``method="auto"`` with ``mesh=`` picks replicated for small n and
    sharded for large n, consistently with ``modeled_crossover`` (the
    comm-extended ``cost_components`` arbitration)."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from repro import compat
        from repro import dist
        from repro.core.rotations import random_sequence
        mesh = compat.make_mesh((8,), ("data",))
        rng = np.random.default_rng(3)
        for (m, n, k), expect_sharded in [((64, 32, 8), False),
                                          ((2048, 512, 64), True)]:
            A = jnp.asarray(rng.standard_normal((m, n)), jnp.float32)
            seq = random_sequence(jax.random.key(n), n, k)
            plan = dist.plan_sharded(seq, like=A, mesh=mesh, method="auto")
            sh_s, rep_s = dist.modeled_crossover(m, n, k, devices=8)
            assert (sh_s < rep_s) == expect_sharded, (n, sh_s, rep_s)
            assert plan.execute_sharded == expect_sharded, \\
                (n, plan.execute_sharded, sh_s, rep_s)
        print("AUTO OK")
    """)
    assert "AUTO OK" in out


def test_comm_term_monotone_in_devices():
    """The §6 communication term: zero when unsharded or D=1, and
    monotonically increasing bytes/seconds in the mesh size."""
    from repro.core.registry import Problem, cost_components

    zero = cost_components("blocked", Problem(256, 64, 16))["comm"]
    assert zero == {"bytes": 0.0, "hops": 0.0, "seconds": 0.0}
    one = cost_components("blocked",
                          Problem(256, 64, 16, sharded=True,
                                  devices=1))["comm"]
    assert one["bytes"] == 0.0 and one["seconds"] == 0.0

    prev_bytes, prev_secs = 0.0, 0.0
    for D in (2, 4, 8, 16):
        comm = cost_components(
            "blocked", Problem(256, 64, 16, sharded=True,
                               devices=D))["comm"]
        assert comm["bytes"] > prev_bytes, (D, comm)
        assert comm["seconds"] > prev_secs, (D, comm)
        assert comm["hops"] == math.ceil(math.log2(D))
        prev_bytes, prev_secs = comm["bytes"], comm["seconds"]


def test_sharded_plan_cache_key_isolation():
    """Sharded plan keys carry ``("sharded", devices)`` in the legacy
    slot, so plans never transfer between device counts or to
    single-device keys (distinct ``_split_key`` classes)."""
    from repro.core.registry import Problem, _plan_key, _split_key

    k1 = _plan_key(Problem(64, 32, 8))
    k8 = _plan_key(Problem(64, 32, 8, sharded=True, devices=8))
    k4 = _plan_key(Problem(64, 32, 8, sharded=True, devices=4))
    assert k1[6] is False
    assert k8[6] == ("sharded", 8)
    assert k4[6] == ("sharded", 4)

    (_, cls1, _), (_, cls8, _), (_, cls4, _) = map(
        _split_key, (k1, k8, k4))
    assert len({cls1, cls8, cls4}) == 3, (cls1, cls8, cls4)
    # round-trip through the key: same problem -> identical key/class
    assert _plan_key(Problem(64, 32, 8, sharded=True, devices=8)) == k8
    # batch/per-request markers survive alongside the sharded slot
    kb = _plan_key(Problem(64, 32, 8, sharded=True, devices=8, batch=16,
                           shared_sequence=False))
    assert kb[6] == ("sharded", 8) and kb[7] == 16 and kb[8] == "per_req"


def test_column_sharded_comm_bytes_live_window():
    """Per-wave liveness accounting: identity-padded bands are
    exchange-free, so a padded sequence prices fewer live bands than
    the dense grid (the dense default stays backward compatible)."""
    import jax
    from repro.core.rotations import random_sequence
    from repro.dist import column_sharded_comm_bytes

    m_loc, n, k, D, n_b, k_b = 64, 32, 16, 4, 8, 4
    dense = column_sharded_comm_bytes(m_loc, n, k, D, n_b, k_b)
    assert dense["bands"] == 4 and dense["live_bands"] == 4
    # a sequence with only the first 2 of 16 waves live: pad_to tail
    live = random_sequence(jax.random.key(0), n, 2).pad_to(k)
    win = column_sharded_comm_bytes(m_loc, n, k, D, n_b, k_b,
                                    sequence=live)
    assert win["bands"] == 4 and win["live_bands"] == 1, win
    assert win["pipelined"] < dense["pipelined"]
    assert win["allgather"] < dense["allgather"]
    # the static k_live bound gives the same window without the arrays
    bound = column_sharded_comm_bytes(m_loc, n, k, D, n_b, k_b,
                                      live_planes=2 * (n - 1))
    assert bound["live_bands"] == win["live_bands"]
    # shape mismatch is a clear error, not silent dense pricing
    with pytest.raises(ValueError):
        column_sharded_comm_bytes(m_loc, n, k + 1, D, n_b, k_b,
                                  sequence=live)


def test_mini_dryrun_multipod_mesh():
    """(2,2,2) pod/data/model mini-mesh: lower+compile a reduced arch with
    the same code path as the production dry-run."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro import compat
        from repro.configs import get_config
        from repro.launch.mesh import make_rules_for_mesh
        from repro.launch.specs import (abstract_opt_state, input_specs,
                                        sharding_trees)
        from repro.models import build_model
        from repro.optim import AdamW
        from repro.parallel.sharding import axis_rules
        from repro.train import make_train_step
        from repro.configs.base import ShapeConfig

        mesh = compat.make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = get_config("smollm-135m").reduced()
        shape = ShapeConfig("mini", 64, 8, "train")
        rules = make_rules_for_mesh(mesh)
        model = build_model(cfg)
        opt = AdamW(lr=1e-4)
        with axis_rules(rules, mesh=mesh):
            trees = sharding_trees(model, cfg, shape, opt, rules, mesh)
            step = make_train_step(model, cfg, opt)
            jf = jax.jit(step,
                         in_shardings=(trees["params"], trees["opt"],
                                       trees["batch"]),
                         out_shardings=(trees["params"], trees["opt"],
                                        None))
            lowered = jf.lower(trees["params_abs"],
                               abstract_opt_state(opt, trees["params_abs"]),
                               input_specs(cfg, shape))
            compiled = lowered.compile()
        assert compiled.memory_analysis().temp_size_in_bytes > 0
        txt = compiled.as_text()
        assert any(c in txt for c in ("all-reduce", "all-gather",
                                      "reduce-scatter")), "no collectives?"
        print("MINI DRYRUN OK")
    """)
    assert "MINI DRYRUN OK" in out


def test_hlo_collectives_accounting():
    """Collective bytes from the loop-aware analyzer: an all-reduce inside
    a scan of length L must count L times."""
    out = _run("""
        import jax, jax.numpy as jnp
        from repro import compat
        from jax.sharding import PartitionSpec as P, NamedSharding
        from repro.launch.hlo_analysis import analyze_hlo
        mesh = compat.make_mesh((8,), ("d",))
        L, M = 5, 64

        def f(x, ws):
            def body(x, w):
                return jnp.tanh(x @ w), None
            x, _ = jax.lax.scan(body, x, ws)
            return x

        sh_x = NamedSharding(mesh, P(None, "d"))
        sh_w = NamedSharding(mesh, P(None, "d", None))
        jf = jax.jit(f, in_shardings=(sh_x, sh_w),
                     out_shardings=sh_x)
        comp = jf.lower(jax.ShapeDtypeStruct((M, M), jnp.float32),
                        jax.ShapeDtypeStruct((L, M, M), jnp.float32)
                        ).compile()
        hc = analyze_hlo(comp.as_text())
        total_coll = sum(hc.collective_bytes.values())
        n_coll = sum(hc.collective_counts.values())
        assert n_coll >= L, (n_coll, hc.collective_counts)
        assert hc.flops >= L * 2 * M * M * (M // 8) * 0.9
        print("HLO COLL OK", hc.collective_counts)
    """)
    assert "HLO COLL OK" in out


def test_donated_sharded_application_matches_the_reference():
    """Eigenvector accumulation's update on four shards, made in place:
    ``apply(V, donate=True)`` through a plan rebound to fresh waves, at
    m = n = 256, k = 8, against ``core/ref`` on seeded random waves.
    Float32 through the GEMM sweep reads ~1e-6 against float64; the
    tolerance is 1e-4 absolute on entries of order one.  The donated
    input reads as deleted afterwards, and the default keeps it."""
    out = _run("""
        import numpy as np, jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro import compat, dist
        from repro.core.ref import rot_sequence_numpy
        from repro.core.rotations import random_sequence
        mesh = compat.make_mesh((4,), ("data",), devices=jax.devices()[:4])
        rows = NamedSharding(mesh, P("data", None))
        rng = np.random.default_rng(11)
        m = n = 256
        k = 8
        V0 = rng.standard_normal((m, n)).astype(np.float32)
        seqs = [random_sequence(jax.random.key(20 + i), n, k)
                for i in range(2)]
        plan = dist.plan_sharded(seqs[0], like=V0, mesh=mesh,
                                 method="accumulated")
        assert plan.execute_sharded
        # rebound before any application, as a flush loop does: the
        # rebinds share one compiled program
        plans = [plan.rebind(s) for s in seqs]
        V = jax.device_put(V0, rows)
        want = V0.astype(np.float64)
        for p, s in zip(plans, seqs):
            prev = V
            V = p.apply(V, donate=True)
            assert prev.is_deleted()
            want = rot_sequence_numpy(want, s.cos, s.sin)
        assert plans[0]._fns() is plans[1]._fns() is plan._fns()
        assert list(plan._fns()) == [("2d", False, True)]
        err = np.abs(np.asarray(V, np.float64) - want).max()
        assert err < 1e-4, err
        kept = jax.device_put(V0, rows)
        plan.apply(kept)
        assert not kept.is_deleted()
        print("DONATE OK", err)
    """)
    assert "DONATE OK" in out


@pytest.mark.parametrize("m,n,k,devices,tiles", [
    (3840, 3840, 180, 1, (96, 96)),
    (960, 960, 180, 1, (64, 64)),
    (65536, 65536, 32, 4, (64, 32)),
])
def test_auto_plans_at_the_benchmark_sizes(m, n, k, devices, tiles):
    """The plans the benchmark's cells run, for a described v5e: the
    paper's squares take ``accumulated`` with their tiles and nothing
    else, and the 65536-wide eigenvector matrix on four chips is priced
    cheaper sharded than replicated, so ``auto`` shards it."""
    from repro.core import registry
    from repro.dist import modeled_crossover

    registry.clear_plan_cache()
    plan = registry.select_plan(m, n, k, platform="tpu",
                                sharded=devices > 1, devices=devices)
    if devices > 1:
        sh_s, rep_s = modeled_crossover(m, n, k, devices=devices,
                                        platform="tpu", sharded_plan=plan)
        assert sh_s < rep_s, (sh_s, rep_s)
    registry.clear_plan_cache()
    assert (plan.method, plan.n_b, plan.k_b) == ("accumulated", *tiles)
    assert plan.kwargs() == {"n_b": tiles[0], "k_b": tiles[1]}

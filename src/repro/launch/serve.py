"""Serving launcher: batched LM decoding, or batched rotation serving.

LM mode (default) drives the ServeEngine::

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-135m \
      --reduced --batch 4 --max-new 16

Rotation mode drives the shape-bucketed RotationService end-to-end: a
mixed-shape stream of recorded rotation sequences is admitted into
buckets, executed through one frozen plan per bucket, checked against
per-request application, and timed::

  PYTHONPATH=src python -m repro.launch.serve --rotations \
      --requests 64 --slots 8

Stream mode drives the async continuous-batching engine
(:class:`repro.serve.StreamEngine`) over the same synthetic stream:
requests are submitted as tickets, batches close on the size-or-age
policy, and results are checked bit-for-bit against the synchronous
service::

  PYTHONPATH=src python -m repro.launch.serve --rotations --stream \
      --requests 64 --slots 8

With ``--metrics-json PATH`` the run executes with ``repro.obs``
enabled and writes the metrics snapshot (plan-cache counters, apply
counts per backend, admit→drain latency histogram p50/p99) to
``PATH``; ``--trace PATH`` additionally exports a Perfetto-loadable
Chrome trace of the plan / admit / drain / apply spans, stamped in
Unix-epoch microseconds (the clock of a ``jax.profiler`` trace's host
plane, where the same spans appear as ``repro.<name>``).  ``make obs-report`` packages the canonical invocations
(synchronous and streaming, each with its own artifact pair).
"""
from __future__ import annotations

import argparse

import jax

from repro import compat, obs
from repro.configs import get_config
from repro.models import build_model
from repro.serve import ServeEngine


def _run_lm(args) -> None:
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))

    eng = ServeEngine(model, cfg, params, batch=args.batch,
                      max_len=args.max_len)
    prompts = [[(7 * i + j) % cfg.vocab for j in range(4 + i)]
               for i in range(args.batch)]
    t0 = obs.timing.now()
    outs = eng.generate(prompts, max_new=args.max_new)
    dt = obs.timing.now() - t0
    toks = sum(len(o) for o in outs)
    for p, o in zip(prompts, outs):
        print(f"prompt {p} -> {o}")
    print(f"{toks} tokens in {dt:.2f}s ({toks/dt:.1f} tok/s batched)")


def _run_rotations(args) -> None:
    import jax.numpy as jnp

    from repro.core.registry import plan_cache_stats
    from repro.serve import RotationService
    from repro.serve.rotations import synthetic_stream

    # canonical mixed-shape stream: >= 3 shape buckets by construction
    requests = synthetic_stream(args.requests, seed=args.seed)

    svc = RotationService(slots=args.slots, autotune=args.autotune)
    misses0 = plan_cache_stats()["misses"]
    t0 = obs.timing.now()
    outs = svc.apply_many(requests)
    jax.block_until_ready(outs[-1])
    dt = obs.timing.now() - t0
    resolved = plan_cache_stats()["misses"] - misses0

    if args.check:
        for (seq, A), out in zip(requests, outs):
            ref = seq.plan(like=A).apply(A)
            err = float(jnp.abs(out - ref).max())
            assert err < 1e-5, f"serving diverged from per-request: {err}"
        print("check: serving matches per-request application")

    s = svc.stats
    # req/s counts *real* requests only — identity pad slots on
    # partially-full buckets are accounted separately, never toward
    # throughput
    rps = s["requests"] / dt
    print(f"{s['requests']} requests in {dt*1e3:.1f} ms "
          f"({rps:.0f} req/s batched; {s['padded_slots']} pad slots of "
          f"{s['slots_executed']} executed)")
    print(f"buckets={len(svc._plans)} batches={s['batches']} "
          f"plans_resolved={s['plans_resolved']} (registry misses "
          f"{resolved}) warm_plans={s['warm_plans']} "
          f"padded_slots={s['padded_slots']}")

    if args.metrics_json:
        snap = obs.write_metrics_json(
            args.metrics_json,
            extra={"mode": "rotations", "requests": s["requests"],
                   "slots": args.slots, "seconds": dt})
        lat = snap["histograms"].get("serve.request_latency_seconds", {})
        print(f"metrics -> {args.metrics_json} "
              f"(latency p50={lat.get('p50', 0)*1e3:.2f} ms "
              f"p99={lat.get('p99', 0)*1e3:.2f} ms)")
    if args.trace:
        n_ev = obs.write_trace(args.trace)
        print(f"trace -> {args.trace} ({n_ev} events)")


def _run_stream(args) -> None:
    import numpy as np

    from repro.serve import StreamEngine

    requests = synthetic_stream_for(args)
    with StreamEngine(slots=args.slots, autotune=args.autotune) as eng:
        t0 = obs.timing.now()
        tickets = [eng.submit(seq, A) for seq, A in requests]
        outs = [t.result(timeout=600.0) for t in tickets]
        dt = obs.timing.now() - t0
    # context exit drains: every ticket is fulfilled here
    if args.check:
        from repro.serve import RotationService

        refs = RotationService(slots=args.slots).apply_many(requests)
        for ref, out in zip(refs, outs):
            if not np.array_equal(np.asarray(ref), np.asarray(out)):
                raise AssertionError(
                    "streamed result diverged from synchronous drain")
        print("check: streamed results bit-equal to synchronous drains")

    s = eng.stats
    print(f"{s['completed']} requests in {dt*1e3:.1f} ms "
          f"({s['completed']/dt:.0f} req/s streamed; closes: "
          f"size={s['closes_size']} age={s['closes_age']} "
          f"drain={s['closes_drain']}; shed={s['shed']})")

    if args.metrics_json:
        snap = obs.write_metrics_json(
            args.metrics_json,
            extra={"mode": "stream", "requests": s["completed"],
                   "slots": args.slots, "seconds": dt})
        lat = snap["histograms"].get("serve.request_latency_seconds", {})
        print(f"metrics -> {args.metrics_json} "
              f"(latency p50={lat.get('p50', 0)*1e3:.2f} ms "
              f"p99={lat.get('p99', 0)*1e3:.2f} ms)")
    if args.trace:
        n_ev = obs.write_trace(args.trace)
        print(f"trace -> {args.trace} ({n_ev} events)")


def synthetic_stream_for(args):
    from repro.serve.rotations import synthetic_stream

    return synthetic_stream(args.requests, seed=args.seed)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--rotations", action="store_true",
                    help="serve rotation-application requests instead of "
                         "LM decoding")
    ap.add_argument("--stream", action="store_true",
                    help="rotation mode: drive the async StreamEngine "
                         "instead of the synchronous service")
    ap.add_argument("--arch", default=None)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--requests", type=int, default=24,
                    help="rotation mode: number of requests to stream")
    ap.add_argument("--slots", type=int, default=8,
                    help="rotation mode: per-bucket batch capacity")
    ap.add_argument("--autotune", action="store_true",
                    help="rotation mode: measure bucket plans")
    ap.add_argument("--check", action="store_true",
                    help="rotation mode: verify against per-request apply")
    ap.add_argument("--metrics-json", default=None,
                    help="enable repro.obs and write the metrics "
                         "snapshot here")
    ap.add_argument("--trace", default=None,
                    help="enable span tracing and write Chrome trace "
                         "JSON here, timestamps in Unix-epoch "
                         "microseconds (view in ui.perfetto.dev)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    compat.enable_compile_cache()

    if args.metrics_json or args.trace:
        obs.set_enabled(True)
        if args.trace:
            obs.runtime.set_trace_path(args.trace)

    if args.rotations:
        if args.stream:
            _run_stream(args)
        else:
            _run_rotations(args)
        return
    if args.arch is None:
        ap.error("--arch is required unless --rotations is given")
    _run_lm(args)


if __name__ == "__main__":
    main()

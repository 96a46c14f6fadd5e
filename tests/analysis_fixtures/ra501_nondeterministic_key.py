# repro-lint: fixture-as=src/repro/core/bad_keys.py
"""RA501 fixture: wall-clock/RNG in cache-key and cost-model paths.

A timestamped plan key makes identical problems hash to different
plans, silently defeating the in-process plan memo.
"""
import random
import time

import numpy as np


def plan_key(problem) -> tuple:
    return (problem.m, problem.n, time.time())  # expect: RA501  # expect: RA502


def cost_flaky(problem, plan) -> float:
    return 6.0 * problem.m * problem.k * random.random()  # expect: RA501


def _bucket_key(seq) -> tuple:
    return (seq.n, np.random.default_rng().integers(10))  # expect: RA501


def _time_call(fn):
    # helpers outside the key/cost name pattern escape RA501 but still
    # trip RA502: any code that times must source its clock from
    # repro.obs.timing
    t0 = time.perf_counter()  # expect: RA502
    fn()
    return time.perf_counter() - t0  # expect: RA502

"""Threaded regression tests for the plan cache's single-flight path.

The serving engine dispatches groups on background threads, so the
process-global plan cache sees concurrent traffic: N tenants hitting a
new circuit family at once must cost ONE planning run (single-flight),
hits must stay safe under simultaneous eviction, and a leader whose
planning run raises must not wedge the key for everyone behind it.
These tests hammer :meth:`repro.lowering.cache.PlanCache.single_flight`
directly with barrier-released threads, then once through the real
``plan_compiled`` path.
"""

from __future__ import annotations

import threading
import time

import pytest

from repro.lowering.cache import HoistCache, PlanCache, PlanEntry


def _hammer(n_threads: int, fn):
    """Release ``n_threads`` through a barrier into ``fn(i)``; re-raise
    the first worker exception in the test thread."""
    barrier = threading.Barrier(n_threads)
    results: list = [None] * n_threads
    errors: list = []

    def work(i):
        try:
            barrier.wait()
            results[i] = fn(i)
        except BaseException as e:  # noqa: BLE001 — surfaced below
            errors.append(e)

    threads = [
        threading.Thread(target=work, args=(i,)) for i in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise errors[0]
    return results


def test_single_flight_one_factory_run():
    cache = PlanCache(maxsize=8)
    calls = []

    def factory():
        calls.append(threading.get_ident())
        time.sleep(0.05)  # widen the in-flight window
        return PlanEntry(plan="the-plan", report=None)

    results = _hammer(16, lambda i: cache.single_flight("fam", factory))
    assert len(calls) == 1  # one leader planned; 15 waiters were served
    assert all(r is results[0] for r in results)
    assert cache.misses == 1 and cache.hits == 15
    assert cache.single_flight("fam", factory) is results[0]
    assert len(calls) == 1


def test_single_flight_distinct_keys_run_concurrently():
    """Leaders for different families must not serialize on each other:
    the factory runs outside the cache lock."""
    cache = PlanCache(maxsize=8)
    inside = threading.Barrier(4, timeout=30)

    def factory():
        inside.wait()  # only passes if all 4 leaders are inside at once
        return PlanEntry(plan=object(), report=None)

    results = _hammer(
        4, lambda i: cache.single_flight(f"fam-{i}", factory)
    )
    assert len({id(r) for r in results}) == 4
    assert cache.misses == 4


def test_single_flight_leader_failure_promotes_waiter():
    cache = PlanCache(maxsize=8)
    attempts = []

    def factory():
        attempts.append(None)
        time.sleep(0.02)
        if len(attempts) == 1:
            raise RuntimeError("transient planning failure")
        return PlanEntry(plan="recovered", report=None)

    def req(i):
        try:
            return cache.single_flight("fam", factory)
        except RuntimeError:
            return None  # the failed leader's own exception propagates

    results = _hammer(8, req)
    ok = [r for r in results if r is not None]
    assert results.count(None) == 1  # exactly the failed leader
    assert len(ok) == 7 and all(r.plan == "recovered" for r in ok)
    assert len(attempts) == 2  # failure + one retry, not a stampede
    # key is not wedged afterwards
    assert cache.single_flight("fam", factory).plan == "recovered"


def test_hits_safe_under_concurrent_eviction():
    """Readers churning one key while writers overflow the LRU: every
    read returns either a valid entry or triggers exactly one rebuild —
    never a torn/None result or a crash."""
    cache = PlanCache(maxsize=2)
    stop = threading.Event()

    def churn(i):
        if i < 2:  # writers: force evictions of everything else
            k = 0
            while not stop.is_set():
                cache.put(f"w{i}-{k % 8}", PlanEntry(plan=k, report=None))
                k += 1
            return None
        out = []
        for _ in range(300):
            ent = cache.single_flight(
                "hot", lambda: PlanEntry(plan="hot", report=None)
            )
            out.append(ent.plan)
        if i == 2:
            stop.set()
        return out

    results = _hammer(6, churn)
    for r in results[2:]:
        assert r is not None and all(p == "hot" for p in r)
    assert len(cache) <= 2


def test_hoist_cache_single_flight_byte_accounting():
    """HoistCache inherits single_flight; its put() must keep the byte
    ledger consistent under threaded inserts + evictions."""
    import numpy as np

    cache = HoistCache(maxsize=4, max_bytes=4 * 800)

    def factory(i):
        return ([np.zeros(100, np.float64)], (), {})  # 800 bytes

    _hammer(12, lambda i: cache.single_flight(f"k{i % 6}", lambda: factory(i)))
    st = cache.stats()
    assert st["size"] <= 4
    assert st["total_bytes"] == st["size"] * 800
    assert st["total_bytes"] <= cache.max_bytes


def test_plan_compiled_threaded_single_flight():
    """End-to-end: N threads requesting the same new family through
    ``plan_compiled`` produce one miss, N-1 hits, and the same live plan
    object (shared jit memoization)."""
    from repro.core.api import plan_compiled
    from repro.core.executor import simplify_network
    from repro.lowering.cache import PLAN_CACHE
    from repro.quantum.circuits import circuit_to_network, random_1d_circuit

    c = random_1d_circuit(8, 6, seed=11)
    tn, arrays = circuit_to_network(c, bitstring="0" * 8)
    tn, arrays = simplify_network(tn, arrays)
    # other tests plan 8-qubit, depth-6 chains of the same structure in
    # this process, and the cache keys a family by structure alone
    PLAN_CACHE.clear()
    h0, m0 = PLAN_CACHE.hits, PLAN_CACHE.misses

    results = _hammer(8, lambda i: plan_compiled(tn, 10))
    plans = {id(p) for p, _ in results}
    assert len(plans) == 1  # everyone shares the one planned artifact
    assert PLAN_CACHE.misses == m0 + 1
    assert PLAN_CACHE.hits == h0 + 7
    reports = [r for _, r in results]
    assert sum(1 for r in reports if not r.cache_hit) == 1
    assert sum(1 for r in reports if r.cache_hit) == 7

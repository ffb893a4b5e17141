"""Compile farm: multi-worker generation, gain-priority queue, caps.

Determinism tests run the ``"manual"`` farm on the ``VirtualClock`` with
declared compile costs — one ``run_pending()`` completes one *batch* of
up to ``workers`` jobs in priority order (max-overlap semantics: the
batch's wall time hides inside the serving interval, the budget is
billed the full sum, ``gen_stall_s`` stays exactly 0). The thread
backend gets targeted concurrency and lifecycle tests.
"""

import json
import threading

import pytest

from repro.core import (
    CompileFarm,
    Param,
    RegenerationPolicy,
    VirtualClock,
    VirtualClockEvaluator,
    product_space,
    virtual_compilette,
    virtual_kernel,
)
from repro.runtime.coordinator import TuningCoordinator
from repro.runtime.lifecycle import TunerLifecycle

GEN_COST = 0.010


def space(n=4):
    return product_space([Param("unroll", (1, 2, 4, 8)[:n], phase=1)])


def cost(p):
    return 0.008 / p["unroll"]


def tracked_compilette(clock, name="k", order=None, gen_cost_s=GEN_COST):
    """virtual_compilette recording generation ORDER into ``order``."""
    comp = virtual_compilette(clock, name, space(), cost,
                              gen_cost_s=gen_cost_s)
    if order is not None:
        inner = comp._generate

        def tracking(point, **spec):
            order.append((name, dict(point)))
            return inner(point, **spec)

        comp._generate = tracking
    return comp


# --------------------------------------------------------- batch semantics
def test_run_pending_completes_one_batch_of_workers():
    """Manual mode: one run_pending = up to ``workers`` completions (the
    M-workers-one-pump-interval overlap model), drain() flushes all."""
    clock = VirtualClock()
    farm = CompileFarm("manual", workers=2)
    comp = tracked_compilette(clock)
    tickets = [farm.submit(comp, {"unroll": u}, {}) for u in (1, 2, 4, 8)]
    assert farm.in_flight == 4
    assert farm.run_pending() == 2           # one batch of 2
    assert [t.done for t in tickets] == [True, True, False, False]
    assert farm.run_pending() == 2
    assert all(t.done for t in tickets)
    assert farm.run_pending() == 0           # queue empty
    # virtual clock never advanced: the batch overlapped with serving
    assert clock() == 0.0
    # ...but every job's cost is billed on its ticket
    assert all(t.gen_charge_s == GEN_COST for t in tickets)


def test_drain_flushes_whole_queue_regardless_of_workers():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers=2)
    comp = tracked_compilette(clock)
    for u in (1, 2, 4, 8):
        farm.submit(comp, {"unroll": u}, {})
    assert farm.drain() == 4
    assert farm.in_flight == 0


# --------------------------------------------------------- priority order
def test_priority_queue_pops_highest_gain_first():
    clock = VirtualClock()
    order = []
    farm = CompileFarm("manual", workers=1)
    a = tracked_compilette(clock, "a", order)
    b = tracked_compilette(clock, "b", order)
    c = tracked_compilette(clock, "c", order)
    farm.submit(a, {"unroll": 1}, {}, priority=0.5)
    farm.submit(b, {"unroll": 1}, {}, priority=2.0)
    farm.submit(c, {"unroll": 1}, {}, priority=1.0)
    farm.drain()
    assert [n for n, _ in order] == ["b", "c", "a"]


def test_requests_preempt_speculation_at_equal_priority():
    clock = VirtualClock()
    order = []
    farm = CompileFarm("manual", workers=1)
    a = tracked_compilette(clock, "a", order)
    b = tracked_compilette(clock, "b", order)
    billed = []
    farm.submit(a, {"unroll": 1}, {}, speculative=True, priority=1.0,
                charge_cb=lambda t, s: billed.append(s))
    farm.submit(b, {"unroll": 1}, {}, priority=1.0)
    farm.drain()
    # b submitted LATER but non-speculative: it wins the tie
    assert [n for n, _ in order] == ["b", "a"]
    assert billed == [GEN_COST]              # prefetch billed via callback


def test_equal_priority_requests_keep_submission_order():
    clock = VirtualClock()
    order = []
    farm = CompileFarm("manual", workers=1)
    comps = [tracked_compilette(clock, n, order) for n in ("x", "y", "z")]
    for comp in comps:
        farm.submit(comp, {"unroll": 1}, {}, priority=1.0)
    farm.drain()
    assert [n for n, _ in order] == ["x", "y", "z"]


# ------------------------------------------------------- per-kernel caps
def test_per_kernel_cap_rejects_only_speculation():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers=4, per_kernel_cap=2)
    a = tracked_compilette(clock, "a")
    b = tracked_compilette(clock, "b")
    # the tuner's own request + one prefetch fill kernel a's quota
    assert farm.submit(a, {"unroll": 1}, {}) is not None
    assert farm.submit(a, {"unroll": 2}, {}, speculative=True) is not None
    assert farm.kernel_in_flight("a") == 2
    # further speculation for a is REJECTED...
    assert farm.submit(a, {"unroll": 4}, {}, speculative=True) is None
    assert farm.stats()["rejected_speculative"] == 1
    # ...but another kernel's jobs keep flowing
    assert farm.submit(b, {"unroll": 1}, {}, speculative=True) is not None
    # and a non-speculative request is ALWAYS admitted (one per tuner)
    assert farm.submit(a, {"unroll": 4}, {}) is not None
    assert farm.kernel_in_flight("a") == 3
    farm.drain()
    assert farm.kernel_in_flight("a") == 0
    assert farm.in_flight == 0


def test_saturated_kernel_cannot_starve_the_farm():
    """With the cap, a wide-space kernel's speculation leaves slots for
    every other kernel even under saturation."""
    clock = VirtualClock()
    farm = CompileFarm("manual", workers=2, per_kernel_cap=2)
    wide = tracked_compilette(clock, "wide")
    admitted = sum(
        farm.submit(wide, {"unroll": u}, {}, speculative=True) is not None
        for u in (1, 2, 4, 8))
    assert admitted == 2                       # quota, not queue length
    order = []
    other = tracked_compilette(clock, "other", order)
    farm.submit(other, {"unroll": 1}, {}, priority=5.0)
    assert farm.run_pending() == 2             # first batch
    assert order and order[0][0] == "other"    # gain-priority: other first


# ------------------------------------------------ determinism across M
def _scripted_coordinator(clock, workers):
    coord = TuningCoordinator(
        policy=RegenerationPolicy(1.0, 0.5), device="test:v", clock=clock,
        async_generation=True, prefetch=1, compile_workers=workers,
        lifecycle=TunerLifecycle(seq_buckets=True, idle_evict_s=None))
    ev = VirtualClockEvaluator(clock)
    handles = []
    for i, name in enumerate(("k0", "k1", "k2", "k3")):
        comp = virtual_compilette(
            clock, name, space(), cost, gen_cost_s=GEN_COST * (i + 1))
        handles.append(coord.register(
            name, comp, ev,
            reference_fn=virtual_kernel(clock, 0.008)))
    return coord, handles


def _drive_scripted(workers, steps=400):
    clock = VirtualClock()
    coord, handles = _scripted_coordinator(clock, workers)
    for i in range(steps):
        for h in handles:
            h(i)
        clock.advance(0.0005)
        coord.pump()
    stats = coord.stats()
    stats["farm"] = coord.generator.stats()
    return stats


@pytest.mark.parametrize("workers", [1, 2, 4])
def test_same_seed_same_costs_byte_identical_stats(workers):
    """Acceptance: two identical runs at every M produce byte-identical
    stats — scheduling order, billing and farm counters are all
    deterministic functions of (seed, scripted costs, M)."""
    a = _drive_scripted(workers)
    b = _drive_scripted(workers)
    assert json.dumps(a, sort_keys=True) == json.dumps(b, sort_keys=True)
    assert a["farm"]["workers"] == workers
    assert a["gen_stall_s"] == 0.0
    assert a["gen_spent_s"] > 0
    # rollup reconciliation: per-kernel accounts + tombstone == aggregate
    for f in ("gen_spent_s", "gen_stall_s", "eval_spent_s"):
        rollup = (sum(k[f] for k in a["kernels"].values())
                  + a["retired_accounts"][f])
        assert rollup == pytest.approx(a[f], abs=1e-12), f


def test_more_workers_never_slow_time_to_best():
    """Cold-start time-to-best (virtual clock time at which the LAST
    kernel finishes exploring) shrinks monotonically with M."""

    def time_to_best(workers):
        clock = VirtualClock()
        coord, handles = _scripted_coordinator(clock, workers)
        for i in range(4000):
            for h in handles:
                h(i)
            clock.advance(0.0005)
            coord.pump()
            if all(h.tuner.explorer.finished for h in handles):
                return clock()
        raise AssertionError("never converged")

    t1, t2, t4 = (time_to_best(w) for w in (1, 2, 4))
    assert t4 <= t2 <= t1
    assert t4 < t1                      # strictly better at M=4


# -------------------------------------------------------- thread backend
def test_thread_workers_compile_concurrently():
    """workers=2 must run two generates at the same time: each generate
    blocks on a 2-party barrier, so a serialized farm would deadlock."""
    clock = VirtualClock()
    barrier = threading.Barrier(2, timeout=10.0)
    farm = CompileFarm("thread", workers=2)
    comp = virtual_compilette(clock, "k", space(), cost, gen_cost_s=GEN_COST)
    inner = comp._generate

    def rendezvous(point, **spec):
        barrier.wait()                  # passes only if both run at once
        return inner(point, **spec)

    comp._generate = rendezvous
    t1 = farm.submit(comp, {"unroll": 1}, {})
    t2 = farm.submit(comp, {"unroll": 2}, {})
    for _ in range(2000):
        if t1.done and t2.done:
            break
        threading.Event().wait(0.005)
    assert t1.done and t2.done               # both completed, no deadlock
    farm.shutdown()


def test_idle_retirement_never_loses_a_submission():
    """Regression (satellite): a job enqueued while the worker is timing
    out idle must still be served — retire-check and deregistration are
    one critical section under the submit mutex."""
    clock = VirtualClock()
    # timeout so small every submit races the retirement path
    farm = CompileFarm("thread", workers=1, worker_idle_timeout_s=0.001)
    comp = virtual_compilette(clock, "k", space(), cost, gen_cost_s=0.0)
    for i in range(200):
        # fresh key every time (cycle the space, vary specialization)
        ticket = farm.submit(comp, {"unroll": (1, 2, 4, 8)[i % 4]},
                             {"rep": i // 4})
        for _ in range(2000):
            if ticket.done:
                break
            threading.Event().wait(0.001)
        assert ticket.done, f"submission {i} lost to idle retirement"
    assert farm.completed == 200
    farm.shutdown()


def test_shutdown_leaves_farm_reusable():
    clock = VirtualClock()
    farm = CompileFarm("thread", workers=2)
    comp = virtual_compilette(clock, "k", space(), cost, gen_cost_s=0.0)
    t = farm.submit(comp, {"unroll": 1}, {})
    for _ in range(2000):
        if t.done:
            break
        threading.Event().wait(0.001)
    farm.shutdown()
    assert not farm._threads
    t2 = farm.submit(comp, {"unroll": 2}, {})     # respawns workers
    for _ in range(2000):
        if t2.done:
            break
        threading.Event().wait(0.001)
    assert t2.done and t2.error is None
    farm.shutdown()


# --------------------------------------------------------- adaptive sizing
def test_auto_farm_grows_under_sustained_backlog():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers="auto", max_workers=4)
    assert farm.auto_sized and farm.workers == 1
    comp = tracked_compilette(clock)
    # every submit sees more queued work than workers: backlog pressure
    for i, u in enumerate((1, 2, 4, 8)):
        farm.submit(comp, {"unroll": u}, {})
    assert farm.workers > 1, "sustained backlog must grow the pool"
    assert farm.stats()["grown"] == farm.workers - 1
    assert farm.workers <= farm.max_workers
    farm.drain()


def test_auto_farm_never_exceeds_max_workers():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers="auto", max_workers=2)
    # distinct compilettes so every submit is a fresh (uncached) job
    for wave in range(5):
        comp = tracked_compilette(clock, f"k{wave}", gen_cost_s=0.001)
        for u in (1, 2, 4, 8):
            farm.submit(comp, {"unroll": u}, {})
        farm.drain()
        assert farm.workers <= 2
    assert farm.stats()["max_workers"] == 2


def test_auto_farm_shrinks_when_observed_idle():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers="auto", max_workers=4)
    comp = tracked_compilette(clock)
    for u in (1, 2, 4, 8):
        farm.submit(comp, {"unroll": u}, {})
    farm.drain()
    grown_to = farm.workers
    assert grown_to > 1
    # idle pumps: the pool cools back down one worker at a time
    for _ in range(farm.AUTO_SHRINK_AFTER * (grown_to - 1)):
        farm.run_pending()
    assert farm.workers == 1
    assert farm.stats()["shrunk"] == grown_to - 1


def test_auto_farm_manual_mode_is_deterministic():
    """Two same-seed runs through an auto-sized manual farm complete the
    same batches in the same order: resize decisions are queue-state
    functions, never wall-clock ones."""

    def one_run():
        clock = VirtualClock()
        order = []
        farm = CompileFarm("manual", workers="auto", max_workers=4)
        comps = [tracked_compilette(clock, n, order)
                 for n in ("a", "b", "c")]
        log = []
        for wave in range(4):
            for j, comp in enumerate(comps):
                farm.submit(comp, {"unroll": (1, 2, 4, 8)[wave]}, {},
                            priority=float(j))
            done = farm.run_pending()
            log.append((done, farm.workers))
        farm.drain()
        s = farm.stats()
        return order, log, (s["grown"], s["shrunk"], s["workers"])

    assert one_run() == one_run()


def test_fixed_farm_ignores_adaptive_signals():
    clock = VirtualClock()
    farm = CompileFarm("manual", workers=2)
    assert not farm.auto_sized
    comp = tracked_compilette(clock)
    for u in (1, 2, 4, 8):
        farm.submit(comp, {"unroll": u}, {})
    for _ in range(farm.AUTO_SHRINK_AFTER * 2):
        farm.run_pending()
    s = farm.stats()
    assert (farm.workers, s["grown"], s["shrunk"]) == (2, 0, 0)
    assert s["max_workers"] == 2


def test_auto_workers_validated_through_config():
    from repro.api import TuningConfig

    cfg = TuningConfig(compile_workers="auto")
    assert cfg.compile_workers == "auto"
    with pytest.raises(ValueError):
        TuningConfig(compile_workers="fast")
    coord = TuningCoordinator(device="test:v", clock=VirtualClock(),
                              async_generation=True, compile_workers="auto")
    assert coord.generator.auto_sized
    assert coord.generator.stats()["auto_sized"]

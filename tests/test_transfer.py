"""Transfer plane: trait vectors, similarity ranking, cross-device seeds.

Deterministic on the VirtualClock; virtual compilettes carry a
``virtual = (clock, profile)`` marker so traits derive from the exact
:class:`~repro.core.DeviceProfile`. The contract under test:

  * every registry best carries the device's trait vector and it
    round-trips through save/load;
  * on a fingerprint miss, ``transfer_seeds`` ranks foreign bests by
    trait similarity, floors, dedups, and never proposes a point
    condemned anywhere in the fleet;
  * a coordinator with ``transfer=True`` injects the seeds as gated
    CANDIDATEs and reaches the known best in <= 2 regenerations where a
    cold search pays the whole enumeration;
  * the knobs parse identically from env, flags and code.
"""

import argparse

import pytest

from repro.api import TuningConfig, TuningSession
from repro.core import (
    Compilette, Param, RegenerationPolicy, TunedRegistry, VirtualClock,
    VirtualClockEvaluator, product_space, scaled_profile, virtual_kernel,
)
from repro.core.profiles import ALL_PROFILES, SI_L1, TI_F3, TI_L3, TPU_V5E
from repro.core.transfer import (
    DeviceTraits,
    calibrated_traits,
    device_traits,
    similarity,
    traits_from_fingerprint,
    transfer_seeds,
)
from repro.runtime.coordinator import TuningCoordinator


def make_comp(clock, name="k", profile=TI_L3,
              cost=lambda p: 0.010 / p["unroll"]):
    sp = product_space([Param("unroll", (1, 2, 4, 8), phase=1,
                              switch_rank=0)])

    def gen(point, **spec):
        return virtual_kernel(clock, cost(point), tag=dict(point))

    comp = Compilette(name, sp, gen)
    comp.virtual = (clock, profile)
    return comp


def make_coordinator(clock, registry, device, **kw):
    kw.setdefault("policy", RegenerationPolicy(
        max_overhead_frac=1.0, invest_frac=1.0))
    return TuningCoordinator(device=device, clock=clock,
                             registry=registry, **kw)


def drive(coord, m, clock, n=200):
    for i in range(n):
        m(i)
        clock.advance(0.010)
        coord.observe_busy(0.010)
        coord.pump()


TRAITS_A = DeviceTraits.from_profile(TI_L3)


# ----------------------------------------------------------------- traits
def test_traits_from_profile_and_roundtrip():
    t = DeviceTraits.from_profile(TI_L3)
    assert t.flops == TI_L3.peak_flops
    assert t.bandwidth_gbps == TI_L3.hbm_gbps
    assert t.vmem_kb == TI_L3.vmem_kb
    assert t.issue == TI_L3.issue
    assert t.overlap == 0.0       # lean core
    assert DeviceTraits.from_profile(TI_F3).overlap == 1.0
    assert DeviceTraits.from_dict(t.to_dict()) == t


def test_traits_from_dict_is_tolerant():
    good = TRAITS_A.to_dict()
    assert DeviceTraits.from_dict(None) is None
    assert DeviceTraits.from_dict("not a dict") is None
    for axis in good:
        broken = dict(good)
        del broken[axis]
        assert DeviceTraits.from_dict(broken) is None
        broken[axis] = float("nan")
        assert DeviceTraits.from_dict(broken) is None
        broken[axis] = "fast"
        assert DeviceTraits.from_dict(broken) is None


def test_similarity_identity_symmetry_monotonicity():
    a = DeviceTraits.from_profile(TI_L3)
    near = DeviceTraits.from_profile(
        scaled_profile(TI_L3, "TI-L3+", flops=1.2, bandwidth=1.1))
    far = DeviceTraits.from_profile(SI_L1)
    assert similarity(a, a) == pytest.approx(1.0)
    assert similarity(a, near) == pytest.approx(similarity(near, a))
    assert similarity(a, far) < similarity(a, near) < 1.0
    # the overlap axis is categorical: a lean/fat flip costs similarity
    # even with every quantitative axis identical
    fat = DeviceTraits.from_dict({**a.to_dict(), "overlap": 1.0})
    assert similarity(a, fat) < 1.0
    assert 0.0 < similarity(a, far) <= 1.0


def test_scaled_profile_moves_only_roofline_terms():
    p = scaled_profile(TI_L3, "TI-L3-x2", flops=2.0, bandwidth=0.5,
                       vmem=2.0)
    assert p.name == "TI-L3-x2"
    assert p.mxu_tflops == pytest.approx(TI_L3.mxu_tflops * 2.0)
    assert p.hbm_gbps == pytest.approx(TI_L3.hbm_gbps * 0.5)
    assert p.vmem_kb == TI_L3.vmem_kb * 2
    assert (p.issue, p.overlap, p.vpus, p.clock_ghz) == (
        TI_L3.issue, TI_L3.overlap, TI_L3.vpus, TI_L3.clock_ghz)
    with pytest.raises(ValueError):
        scaled_profile(TI_L3, "bad", flops=0.0)


def test_device_traits_precedence_and_fingerprints():
    clock = VirtualClock()
    comp = make_comp(clock, profile=TI_L3)
    # virtual marker wins when no explicit profile is passed
    assert device_traits(comp, device="cpu:x") == TRAITS_A
    assert device_traits(comp, profile=TI_F3) == DeviceTraits.from_profile(
        TI_F3)
    # real backends: TPUs by device kind, hosts by platform prefix
    assert traits_from_fingerprint(
        "tpu:TPU v5 lite:jax0.9.0-jaxlib0.9.0") == (
        DeviceTraits.from_profile(TPU_V5E))
    # an unknown TPU kind has no nominal: never priced as a v5e
    assert traits_from_fingerprint("tpu:TPU v9 future:jax0.9.0") is None
    assert traits_from_fingerprint("tpu:v5e:xla-9") is None
    assert traits_from_fingerprint("cpu:host") is not None
    assert traits_from_fingerprint("quantum:q1") is None
    assert traits_from_fingerprint(None) is None
    assert device_traits(object(), device="unknown:dev") is None


def test_calibrated_traits_scales_throughput_by_probe():
    sp = product_space([Param("unroll", (1, 2), phase=1, switch_rank=0)])
    comp = Compilette("k", sp, lambda point, **spec: (lambda *a: None),
                      cost_model=lambda point, spec, profile: 0.004)
    base = traits_from_fingerprint("cpu:host")
    # observed twice as slow as predicted -> throughput halves
    cal = calibrated_traits(base, comp, {}, 0.008, device="cpu:host")
    assert cal.flops == pytest.approx(base.flops * 0.5)
    assert cal.bandwidth_gbps == pytest.approx(base.bandwidth_gbps * 0.5)
    assert (cal.vmem_kb, cal.issue, cal.overlap) == (
        base.vmem_kb, base.issue, base.overlap)
    # the probe ratio is clamped to 8x either way
    assert calibrated_traits(base, comp, {}, 1e6, device="cpu:host"
                             ).flops == pytest.approx(base.flops / 8.0)
    # no model / bad observation / virtual marker: pass through unchanged
    assert calibrated_traits(base, object(), {}, 0.008,
                             device="cpu:host") == base
    assert calibrated_traits(base, comp, {}, float("nan"),
                             device="cpu:host") == base
    clock = VirtualClock()
    vcomp = make_comp(clock)
    vt = device_traits(vcomp)
    assert calibrated_traits(vt, vcomp, {}, 123.0) == vt


# ------------------------------------------------------------ registry IO
def test_put_persists_traits_and_round_trips(tmp_path):
    reg = TunedRegistry()
    td = TRAITS_A.to_dict()
    reg.put("k", {}, "bench:a", {"unroll": 8}, 0.00125, traits=td)
    path = str(tmp_path / "tuned.json")
    reg.save(path)
    back = TunedRegistry.load(path)
    (dev, entry), = back.cross_device_entries("k", {}, exclude_device=None)
    assert dev == "bench:a"
    assert entry["traits"] == td
    # a worse-score re-put grafts traits onto a pre-transfer entry
    reg2 = TunedRegistry()
    reg2.put("k", {}, "bench:a", {"unroll": 8}, 0.00125)
    reg2.put("k", {}, "bench:a", {"unroll": 8}, 0.00300, traits=td)
    (_, entry2), = reg2.cross_device_entries("k", {})
    assert entry2["score_s"] == 0.00125 and entry2["traits"] == td


def test_cross_device_entries_filters_and_sorts():
    reg = TunedRegistry()
    reg.put("k", {}, "bench:b", {"unroll": 4}, 0.0025)
    reg.put("k", {}, "bench:a", {"unroll": 8}, 0.00125)
    reg.put("k", {"n": 1}, "bench:c", {"unroll": 2}, 0.005)   # other spec
    reg.put("other", {}, "bench:d", {"unroll": 2}, 0.005)     # other kernel
    rows = reg.cross_device_entries("k", {}, exclude_device="bench:b")
    assert [dev for dev, _ in rows] == ["bench:a"]
    rows = reg.cross_device_entries("k", {})
    assert [dev for dev, _ in rows] == ["bench:a", "bench:b"]
    # an entry quarantined under its own key never surfaces
    reg.quarantine("k", {}, "bench:a", {"unroll": 8}, "wrong output")
    assert [dev for dev, _ in reg.cross_device_entries("k", {})] == [
        "bench:b"]


def test_fleet_quarantined_points_spans_devices():
    reg = TunedRegistry()
    reg.quarantine("k", {}, "bench:a", {"unroll": 8}, "wrong output")
    reg.quarantine("k", {}, "bench:b", {"unroll": 4}, "tail")
    reg.quarantine("other", {}, "bench:a", {"unroll": 2}, "tail")
    pts = reg.fleet_quarantined_points("k", {})
    assert sorted(p["unroll"] for p in pts) == [4, 8]
    assert reg.fleet_quarantined_points("missing", {}) == []


# --------------------------------------------------------- transfer_seeds
def seeded_registry():
    """Three donors: near (same family), scaled, and a far outlier."""
    reg = TunedRegistry()
    donors = (
        ("bench:near", TI_L3, {"unroll": 8}, 0.00125),
        ("bench:scaled", scaled_profile(TI_L3, "TI-L3~", flops=1.3,
                                        bandwidth=1.2),
         {"unroll": 4}, 0.0025),
        ("bench:far", SI_L1, {"unroll": 1}, 0.010),
    )
    for dev, prof, point, score in donors:
        reg.put("k", {}, dev, point, score,
                traits=DeviceTraits.from_profile(prof).to_dict())
    return reg


def test_transfer_seeds_ranks_floors_and_caps():
    reg = seeded_registry()
    local = DeviceTraits.from_profile(TI_L3)
    seeds = transfer_seeds(reg, "k", {}, "bench:me", local,
                           top_k=3, min_similarity=0.75)
    # the far outlier is floored away; most similar donor first
    assert [s.device for s in seeds] == ["bench:near", "bench:scaled"]
    assert seeds[0].point == {"unroll": 8}
    assert seeds[0].similarity == pytest.approx(1.0)
    assert seeds[1].similarity < seeds[0].similarity
    assert transfer_seeds(reg, "k", {}, "bench:me", local,
                          top_k=1, min_similarity=0.75)[0].device == (
        "bench:near")
    # no traits / zero k -> no seeds; the requesting device is excluded
    assert transfer_seeds(reg, "k", {}, "bench:me", None) == []
    assert transfer_seeds(reg, "k", {}, "bench:me", local, top_k=0) == []
    assert all(s.device != "bench:near" for s in transfer_seeds(
        reg, "k", {}, "bench:near", local, min_similarity=0.0))


def test_transfer_seeds_dedup_by_point_keeps_most_similar_donor():
    reg = seeded_registry()
    # a second donor holding the SAME point as bench:near, less similar
    reg.put("k", {}, "bench:twin", {"unroll": 8}, 0.002,
            traits=DeviceTraits.from_profile(
                scaled_profile(TI_L3, "TI-L3~~", flops=1.5)).to_dict())
    seeds = transfer_seeds(reg, "k", {}, "bench:me",
                           DeviceTraits.from_profile(TI_L3),
                           top_k=3, min_similarity=0.0)
    points = [s.point["unroll"] for s in seeds]
    assert points.count(8) == 1
    assert seeds[0].device == "bench:near"


def test_transfer_seeds_skip_fleet_quarantined_points():
    reg = seeded_registry()
    # the point was condemned on some OTHER device entirely: it must not
    # travel to anyone, even though the donor entry itself is clean
    reg.quarantine("k", {}, "bench:elsewhere", {"unroll": 8}, "wrong")
    seeds = transfer_seeds(reg, "k", {}, "bench:me",
                           DeviceTraits.from_profile(TI_L3),
                           min_similarity=0.0)
    assert all(s.point != {"unroll": 8} for s in seeds)


def test_transfer_seeds_ignore_traitless_entries():
    reg = TunedRegistry()
    reg.put("k", {}, "bench:old", {"unroll": 8}, 0.00125)   # pre-transfer
    assert transfer_seeds(reg, "k", {}, "bench:me",
                          DeviceTraits.from_profile(TI_L3),
                          min_similarity=0.0) == []


# --------------------------------------------------- coordinator seeding
def test_coordinator_attaches_traits_to_registry_bests():
    clock = VirtualClock()
    reg = TunedRegistry()
    coord = make_coordinator(clock, reg, "bench:donor")
    m = coord.register("k", make_comp(clock), VirtualClockEvaluator(clock),
                       reference_fn=virtual_kernel(clock, 0.010))
    assert m.device_traits == TRAITS_A.to_dict()
    drive(coord, m, clock)
    (dev, entry), = reg.cross_device_entries("k", {})
    assert dev == "bench:donor"
    assert entry["point"] == {"unroll": 8}
    assert entry["traits"] == TRAITS_A.to_dict()


def test_transfer_seeded_tuner_reaches_best_in_two_regens():
    clock = VirtualClock()
    reg = TunedRegistry()
    donor = make_coordinator(clock, reg, "bench:donor")
    md = donor.register("k", make_comp(clock), VirtualClockEvaluator(clock),
                        reference_fn=virtual_kernel(clock, 0.010))
    drive(donor, md, clock)
    assert md.tuner.explorer.best_point == {"unroll": 8}

    # unseen-but-similar device: fingerprint miss, transfer seeds the best
    clock2 = VirtualClock()
    recip = make_coordinator(
        clock2, reg, "bench:unseen", transfer=True, gate_mode="check")
    profile = scaled_profile(TI_L3, "TI-L3~", flops=1.2)
    m2 = recip.register("k", make_comp(clock2, profile=profile),
                        VirtualClockEvaluator(clock2),
                        reference_fn=virtual_kernel(clock2, 0.010))
    assert not m2.warm_started
    assert m2.transfer_seed_keys, "similar foreign best must be injected"
    drive(recip, m2, clock2, n=40)
    ex = m2.tuner.explorer
    assert ex.best_point == {"unroll": 8}
    first_best = next(i for i, (p, _) in enumerate(ex.history, 1)
                      if dict(p) == {"unroll": 8})
    assert first_best <= 2, (
        f"transfer seed must reach the optimum in <=2 regens, "
        f"took {first_best}")
    s = recip.stats()
    assert s["transfer_enabled"] and s["transfer_hits"] >= 1
    assert s["transfer_adopted"] == 1
    assert s["seeded_regens_to_best"] <= 2
    assert m2.stats()["transfer_seeds"] == len(m2.transfer_seed_keys)
    # the seed passed through the gate as a CANDIDATE, not a blind swap
    assert m2.tuner.stats()["gate_checks"] >= 1


def test_transfer_off_or_warm_hit_suppresses_seeding():
    clock = VirtualClock()
    reg = TunedRegistry()
    donor = make_coordinator(clock, reg, "bench:donor")
    md = donor.register("k", make_comp(clock), VirtualClockEvaluator(clock),
                        reference_fn=virtual_kernel(clock, 0.010))
    drive(donor, md, clock)

    # transfer disabled (default): a fingerprint miss stays cold
    clock2 = VirtualClock()
    cold = make_coordinator(clock2, reg, "bench:unseen")
    m2 = cold.register("k", make_comp(clock2), VirtualClockEvaluator(clock2),
                       reference_fn=virtual_kernel(clock2, 0.010))
    assert not m2.transfer_seed_keys
    assert cold.stats()["transfer_hits"] == 0

    # exact-fingerprint hit: the warm start wins, transfer stays quiet
    clock3 = VirtualClock()
    warm = make_coordinator(clock3, reg, "bench:donor", transfer=True)
    m3 = warm.register("k", make_comp(clock3), VirtualClockEvaluator(clock3),
                       reference_fn=virtual_kernel(clock3, 0.010))
    assert m3.warm_started and not m3.transfer_seed_keys


def test_transfer_seed_failing_gate_quarantined_and_never_reseeded():
    clock = VirtualClock()
    reg = TunedRegistry()
    donor = make_coordinator(clock, reg, "bench:donor")
    md = donor.register("k", make_comp(clock), VirtualClockEvaluator(clock),
                        reference_fn=virtual_kernel(clock, 0.010))
    drive(donor, md, clock)
    bad = {"unroll": 8}

    # device B: the transferred best FAILS the local oracle
    clock2 = VirtualClock()
    recip = make_coordinator(clock2, reg, "bench:b", transfer=True,
                             gate_mode="check")
    comp2 = make_comp(clock2)
    comp2.gate_script = lambda point: dict(point) != bad
    m2 = recip.register("k", comp2, VirtualClockEvaluator(clock2),
                        reference_fn=virtual_kernel(clock2, 0.010))
    assert m2.transfer_seed_keys
    drive(recip, m2, clock2)
    assert m2.tuner.stats()["gate_failures"] >= 1
    assert m2.tuner.explorer.is_quarantined(bad)
    assert reg.is_quarantined("k", {}, "bench:b", bad)
    assert m2.tuner.stats()["active_point"] != bad

    # device C (similar to both): the condemned point must never be
    # proposed as a transfer seed again, anywhere in the fleet
    clock3 = VirtualClock()
    third = make_coordinator(clock3, reg, "bench:c", transfer=True,
                             gate_mode="check")
    m3 = third.register("k", make_comp(clock3), VirtualClockEvaluator(clock3),
                        reference_fn=virtual_kernel(clock3, 0.010))
    injected = [m3.tuner.compilette.space.key({"unroll": 8})]
    assert all(k not in injected for k in m3.transfer_seed_keys)
    assert third.stats()["transfer_adopted"] == 0


def test_coordinator_validates_transfer_knobs():
    with pytest.raises(ValueError):
        TuningCoordinator(device="d", transfer_top_k=0)
    with pytest.raises(ValueError):
        TuningCoordinator(device="d", min_similarity=0.0)
    with pytest.raises(ValueError):
        TuningCoordinator(device="d", min_similarity=1.5)


# ------------------------------------------------------------ config knobs
def test_transfer_config_env_flags_programmatic_identical():
    base = TuningConfig(enabled=False)
    env = {
        "REPRO_TUNE_TRANSFER": "1",
        "REPRO_TUNE_TRANSFER_K": "5",          # alias for transfer_top_k
        "REPRO_TUNE_MIN_SIMILARITY": "0.6",
        "REPRO_TUNE_STRATEGY": "cost_model",
    }
    cfg_env = TuningConfig.from_env(env, base=base)
    parser = argparse.ArgumentParser()
    TuningConfig.add_flags(parser, base=base)
    cfg_flags = TuningConfig.from_flags(parser.parse_args([
        "--transfer", "--transfer-top-k", "5",
        "--min-similarity", "0.6", "--strategy", "cost_model",
    ]), base=base)
    cfg_prog = TuningConfig(enabled=False, transfer=True, transfer_top_k=5,
                            min_similarity=0.6, strategy="cost_model")
    assert cfg_env == cfg_flags == cfg_prog


def test_transfer_config_validation():
    with pytest.raises(ValueError):
        TuningConfig(transfer_top_k=0)
    with pytest.raises(ValueError):
        TuningConfig(min_similarity=0.0)
    with pytest.raises(ValueError):
        TuningConfig(min_similarity=1.01)


def test_session_wires_transfer_knobs_through():
    cfg = TuningConfig(enabled=True, transfer=True, transfer_top_k=2,
                       min_similarity=0.5)
    s = TuningSession(cfg, clock=VirtualClock(), device="bench:x")
    try:
        assert s.coordinator.transfer is True
        assert s.coordinator.transfer_top_k == 2
        assert s.coordinator.min_similarity == 0.5
        assert s.coordinator.stats()["transfer_enabled"] is True
    finally:
        s.close()

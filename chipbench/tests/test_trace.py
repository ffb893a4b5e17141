"""The trace reduction on a hand-made trace with known answers, and on
a small trace recorded on a TPU v5e (``data/record_trace.py``)."""

import os

import pytest

from chipbench import trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def _proto(host, device):
    """XSpace text: host events on one thread, device ops on one TPU.
    Times in microseconds from 1000 ns."""
    def plane(pid, name, line, events):
        names = sorted({n for n, _, _ in events})
        ids = {n: i + 1 for i, n in enumerate(names)}
        evs = "".join(
            f"events {{ metadata_id: {ids[n]} offset_ps: {int(s * 1e6)} "
            f"duration_ps: {int((e - s) * 1e6)} }}\n"
            for n, s, e in events)
        meta = "".join(
            f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
            for n, i in ids.items())
        return (f'planes {{ id: {pid} name: "{name}"\n'
                f'lines {{ id: 1 name: "{line}" timestamp_ns: 1000\n{evs}}}\n'
                f"{meta}}}\n")
    return (plane(1, "/host:CPU", "python", host)
            + plane(2, "/device:TPU:0", "XLA Ops", device))


HOST = [(trace.SLICE, 10, 110), ("chipbench.request", 20, 70),
        ("PjitFunction(prefill)", 20, 22), ("PjRtExecute", 43, 47),
        ("chipbench.between_requests", 70, 80),
        ("chipbench.request", 80, 110)]
DEVICE = [("%fusion.a = f32[8] fusion(x)", 0, 5),       # before the slice
          ("%fusion.a = f32[8] fusion(x)", 15, 30),
          ("%fusion.b = f32[8] fusion(y)", 25, 40),       # overlapping
          ("%while.w = (s32[]) while(t)", 50, 65),        # holds fusion.a
          ("%fusion.a = f32[8] fusion(x)", 52, 65),
          ("%dot.c = f32[8] dot(a, b)", 85, 105),
          ("%copy.d = f32[8] copy(z)", 108, 120)]         # clipped at the end


def test_reduction_by_hand():
    from jax.profiler import ProfileData

    out = trace.reduce(ProfileData.from_text_proto(_proto(HOST, DEVICE)))
    # busy: [15,40] + [50,65] + [85,105] + [108,110] = 62 of 100 us
    assert out["window_s"] == pytest.approx(100e-6)
    assert out["busy_s"] == pytest.approx(62e-6)
    assert out["idle_pct"] == pytest.approx(38.0)
    # leaf ops by instruction name: fusion.b overlaps fusion.a but is
    # not inside it; the while op holds a fusion.a and is left out
    assert out["device_ops"] == [
        ["%fusion.a", pytest.approx(28e-6)], ["%dot.c", pytest.approx(20e-6)],
        ["%fusion.b", pytest.approx(15e-6)], ["%copy.d", pytest.approx(2e-6)]]
    # gaps: [10,15] loop, [40,50] inside a request's execute call,
    # [65,85] mid 75 between requests, [105,108] in the second request
    assert out["idle_gaps"] == [
        ["chipbench.between_requests", pytest.approx(20e-6)],
        ["chipbench.request > PjRtExecute", pytest.approx(10e-6)],
        ["harness loop", pytest.approx(5e-6)],
        ["chipbench.request", pytest.approx(3e-6)]]
    assert out["chips"] == 1


def test_no_device_ops_is_an_error():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(_proto(HOST, []))
    with pytest.raises(RuntimeError, match="no device plane"):
        trace.reduce(pd)


def test_no_slice_span_is_an_error():
    from jax.profiler import ProfileData

    pd = ProfileData.from_text_proto(_proto(HOST[1:], DEVICE))
    with pytest.raises(RuntimeError, match="no host span"):
        trace.reduce(pd)


def test_recorded_tpu_trace():
    """Two requests of two matmuls on a v5e, with host sleeps between
    and inside them (``data/record_trace.py``)."""
    out = trace.reduce(trace.load(DATA))
    assert out["chips"] == 1
    assert out["window_s"] == pytest.approx(0.01572355)
    # one of the four executions is stamped before the slice opens: the
    # device's clock sits about a millisecond behind the host's here
    assert out["busy_s"] == pytest.approx(0.000542567)
    assert sum(s for _, s in out["device_ops"]) == pytest.approx(
        out["busy_s"], rel=1e-6)
    assert [n for n, _ in out["device_ops"][:2]] == [
        "%fusion", "%convolution_tanh_fusion"]
    assert out["idle_gaps"][0] == [
        "chipbench.between_requests > $time sleep",
        pytest.approx(0.005502318)]
    causes = dict(out["idle_by_cause"])
    assert set(causes) >= {"chipbench.between_requests > $time sleep",
                           "chipbench.request > $time sleep"}
    assert out["idle_pct"] == pytest.approx(
        100 * (1 - out["busy_s"] / out["window_s"]))

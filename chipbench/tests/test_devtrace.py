"""Trace reduction: busy/idle union, device time per program, idle gaps
by host span; on a synthetic trace with known answers, and on a short
trace recorded on a TPU v5e."""
import gzip
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import devtrace


def ev(name, start, dur):
    return NS(name=name, start_ns=float(start), duration_ns=float(dur))


def synthetic():
    ops = [ev("fusion.1", 0, 100), ev("fusion.2", 50, 100),   # 0-150
           ev("dot.3", 300, 200),                             # 300-500
           ev("copy.4", 900, 100)]                            # 900-1000
    modules = [ev("jit_paged_decode_step(7)", 0, 500),
               ev("jit_prefill_collect(3)", 900, 80),
               ev("jit_scatter_prefill(4)", 980, 20)]
    device = NS(name="/device:TPU:0", stats=[], lines=[
        NS(name="XLA Ops", events=ops), NS(name="XLA Modules",
                                           events=modules)])
    host = NS(name="/host:CPU", stats=[], lines=[NS(name="python", events=[
        ev("engine.step", 0, 600), ev("client.wait", 600, 250),
        ev("client.admit", 850, 40), ev("other", 600, 400)])])
    env = NS(name="Task Environment", lines=[], stats=[
        ("profile_start_time", 5_000), ("profile_stop_time", 6_200)])
    return NS(planes=[env, device, host])


def test_synthetic_union_programs_and_gaps():
    r = devtrace.reduce(synthetic())
    assert r.devices == 1
    assert r.window_s == pytest.approx(1200e-9)
    assert r.busy_s == pytest.approx((150 + 200 + 100) * 1e-9)
    assert r.device_s("paged_decode_step") == pytest.approx(500e-9)
    assert r.device_s("prefill_collect", "scatter_prefill") \
        == pytest.approx(100e-9)
    assert r.calls("prefill_collect") == 1
    # idle: 150-300 and 500-600 under engine.step, 600-850 client.wait,
    # 850-890 client.admit, 890-900 and 1000-1200 under no span
    idle = r.idle_by_span
    assert idle["engine.step"] == pytest.approx(250e-9)
    assert idle["client.wait"] == pytest.approx(250e-9)
    assert idle["client.admit"] == pytest.approx(40e-9)
    assert idle[devtrace.NO_SPAN] == pytest.approx(210e-9)
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    b = devtrace.breakdown(r)
    assert b["device_ops"][0] == ["jit_paged_decode_step", 500e-9]
    assert len(b["idle_gaps"]) <= 10


#: three engine steps of the offline cell (one prefill, two decodes), each
#: followed by a short host sleep, traced on one TPU v5e
RECORDED = Path(__file__).parent / "data" / "v5e_offline_steps.xplane.pb.gz"


def _sweep_busy(events, hi):
    """Busy nanoseconds in [0, hi) by a sweep over interval ends: a
    second way to take the union."""
    iv = [(max(e.start_ns, 0.0), min(e.start_ns + e.duration_ns, hi))
          for e in events]
    marks = sorted([(a, 1) for a, b in iv if b > a]
                   + [(b, -1) for a, b in iv if b > a])
    busy, depth, since = 0.0, 0, None
    for t, d in marks:
        if depth == 0 and d == 1:
            since = t
        depth += d
        if depth == 0:
            busy += t - since
    return busy


def test_recorded_v5e_trace():
    import jax
    profile = jax.profiler.ProfileData.from_serialized_xspace(
        gzip.decompress(RECORDED.read_bytes()))
    r = devtrace.reduce(profile)
    planes = {p.name: p for p in profile.planes}
    lines = {ln.name: ln for ln in planes["/device:TPU:0"].lines}
    env = dict(planes["Task Environment"].stats)

    assert r.devices == 1
    assert r.window_s == pytest.approx(
        (env["profile_stop_time"] - env["profile_start_time"]) / 1e9)
    ops = list(lines["XLA Ops"].events)
    assert r.busy_s == pytest.approx(_sweep_busy(ops, r.window_s * 1e9)
                                     / 1e9, rel=1e-9)
    assert 0 < r.busy_s < r.window_s

    by_name = {}
    for e in lines["XLA Modules"].events:
        name = e.name.split("(")[0]
        by_name[name] = by_name.get(name, 0.0) + e.duration_ns / 1e9
    assert r.program_s == pytest.approx(by_name)
    assert r.calls("paged_decode_step") == 2
    assert r.calls("prefill_collect") == r.calls("scatter_prefill") == 1
    # the decode program holds most of the device time of three steps
    assert r.device_s("paged_decode_step") > 0.8 * r.busy_s

    waits = [e for ln in planes["/host:CPU"].lines for e in ln.events
             if e.name == "client.wait"]
    assert len(waits) == 3
    idle = r.idle_by_span
    assert sum(idle.values()) == pytest.approx(r.window_s - r.busy_s)
    # the host sleeps after each step's last sync: the device is idle
    # for nearly all of each wait
    wait_s = sum(e.duration_ns for e in waits) / 1e9
    assert 0.9 * wait_s <= idle["client.wait"] <= wait_s
    assert idle["engine.step"] > 0


def test_no_device_plane_is_an_error():
    p = synthetic()
    p.planes = [pl for pl in p.planes if not pl.name.startswith("/device")]
    with pytest.raises(ValueError, match="no TPU"):
        devtrace.reduce(p)


"""The ASR server's tracing: the parked-slot counter, the host spans on
the profiler's timeline, and the named device stages of the fused step
(tiny engine, CPU)."""
import glob
import os

import jax
import numpy as np
import pytest

from test_serving import _asr_engine


def _windows(eng, k):
    """Audio that buffers exactly `k` whole step windows in a slot."""
    n = eng._need + (k - 1) * eng._spp
    return np.random.default_rng(k).standard_normal(n).astype(np.float32)


def test_parked_slots_counts_slots_a_step_leaves_out():
    eng, _ = _asr_engine(4)
    for k in (5, 5, 2, 1):
        eng.open().push(_windows(eng, k))
    assert [eng.slot_windows(s) for s in range(4)] == [5, 5, 2, 1]
    # w=4 retires 4 x 2 windows (w=2: 2 x 3, w=1: 1 x 4): slots 0 and 1
    # step, slots 2 and 3 hold windows and are parked
    assert eng._step()
    assert eng.step_shapes[-1] == (2, 2, 4)
    assert eng.metrics.parked_slots == 2
    # now 1, 1, 2, 1 windows: w=1 steps every slot, nothing parked
    assert eng._step()
    assert eng.step_shapes[-1][::2] == (4, 1)
    assert eng.metrics.parked_slots == 2
    steps = eng.metrics.snapshot()["steps"]
    assert steps["parked_slots"] == 2
    assert steps["stepped_slots"] == 6


def _capture(tmp_path):
    """Profile one short decode on a warmed engine; {span name: [(start,
    end, stats) on the thread that ran it]} from `ProfileData`."""
    from jax.profiler import ProfileData

    eng, _ = _asr_engine(2)
    audio = _windows(eng, 3)
    eng.serve([audio, audio[:eng._need]])          # compile outside
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        eng.serve([audio, audio[:eng._need]])
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                   "*", "*.xplane.pb"))
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                        dict(e.stats)) for e in line.events]
                if any(n.startswith("engine.") for n, *_ in evs):
                    lines.append(evs)
    assert len(lines) == 1, "engine spans on one thread"
    spans = {}
    for name, a, b, stats in lines[0]:
        spans.setdefault(name, []).append((a, b, stats))
    return spans


@pytest.fixture(scope="module")
def spans(tmp_path_factory):
    return _capture(tmp_path_factory.mktemp("profile"))


@pytest.mark.parametrize("parent,child", [
    ("engine.step", "asr.assemble"), ("engine.step", "asr.dispatch"),
    ("engine.harvest", "asr.readout")])
def test_host_spans_nest(spans, parent, child):
    assert spans[child]
    for a, b, _ in spans[child]:
        assert any(pa <= a and b <= pb for pa, pb, _ in spans[parent]), \
            (child, a, b)


def test_host_spans_carry_their_ids(spans):
    # three windows in one slot and one in the other: w=2 retires as
    # many as w=1 (2 x 1, 1 x 2) and the larger w wins the tie, so the
    # first step parks the one-window slot
    steps = [s for _a, _b, s in spans["engine.step"]]
    assert steps[0] == {"n": 1, "w": 2, "parked": 1}
    assert {s["w"] for _a, _b, s in spans["asr.assemble"]} <= {1, 2}
    assert all("b" in s for _a, _b, s in spans["asr.assemble"])
    sids = sorted(s["sid"] for _a, _b, s in spans["engine.harvest"])
    assert sids == sorted(s["sid"] for _a, _b, s in spans["engine.admit"])
    assert len(sids) == 2
    assert len(spans["engine.push"]) == 2


@pytest.mark.parametrize("scope", ["mfcc", "tds_forward", "expand",
                                   "writeback"])
def test_step_stages_are_named_scopes(scope):
    eng, _ = _asr_engine(2)
    eng._ensure_state()
    batch = np.zeros((1, 1, eng._need), np.float32)
    idx = np.zeros((1,), np.int32)
    text = eng._jit_step.lower(
        eng.params, eng._prepared, eng._tables, eng._stream_state,
        eng._beam, batch, idx).as_text(debug_info=True)
    assert f"/{scope}/" in text

"""The ASR server's tracing and pump rounds: the catch-up and
parked-slot counters, the host spans on the profiler's timeline, and the
named device stages of the fused step (tiny engine, CPU)."""
import glob
import os

import jax
import numpy as np
import pytest

from repro.data.pipeline import SyntheticASR
from repro.serving import (AsrEngine, EngineConfig, FaultPolicy,
                           FaultSpec, SessionFaulted)
from test_serving import _asr_engine, _same


def _windows(eng, k):
    """Audio that buffers exactly `k` whole step windows in a slot."""
    n = eng._need + (k - 1) * eng._spp
    return np.random.default_rng(k).standard_normal(n).astype(np.float32)


def test_parked_slots_counts_slots_a_step_leaves_out():
    eng, _ = _asr_engine(4)
    for k in (5, 5, 2, 1):
        eng.open().push(_windows(eng, k))
    assert [eng.slot_windows(s) for s in range(4)] == [5, 5, 2, 1]
    # one pump round steps every slot once: w=4 retires 4 x 2 windows
    # (w=2: 2 x 3, w=1: 1 x 4) over slots 0 and 1; catch-ups step slot
    # 2 at w=2 (ties w=1's 1 x 2, the larger w wins) and slot 3 at w=1
    assert eng._step()
    assert list(eng.step_shapes) == [(2, 2, 4), (1, 1, 2), (1, 1, 1)]
    assert [eng.slot_windows(s) for s in range(4)] == [1, 1, 0, 0]
    assert eng.metrics.parked_slots == 0
    assert eng.metrics.catchup_steps == 2
    steps = eng.metrics.snapshot()["steps"]
    assert steps["parked_slots"] == 0
    assert steps["catchup_steps"] == 2
    assert steps["stepped_slots"] == 4
    assert steps["count"] == eng.n_steps == 3


def test_live_shaped_pool_runs_one_dispatch_per_round():
    """Every slot holding one window (80 ms chunks, stepped as they
    arrive): the round's first dispatch takes them all, no catch-up."""
    eng, _ = _asr_engine(4)
    sessions = [eng.open().push(_windows(eng, 1)) for _ in range(4)]
    for r in range(3):
        assert [eng.slot_windows(s) for s in range(4)] == [1] * 4
        assert eng._step()
        assert eng.n_steps == r + 1
        assert eng.step_shapes[-1] == (4, 4, 1)
        for sess in sessions:
            sess.push(np.zeros((eng._spp,), np.float32))
    assert eng.metrics.catchup_steps == 0
    assert eng.metrics.snapshot()["steps"]["catchup_steps"] == 0


def test_ragged_bulk_serve_with_catchups_matches_lone_engine():
    """Whole utterances of 10-16 windows over 3 slots: rounds run
    catch-up dispatches for the shorter slots, and every utterance
    decodes as a lone 1-slot engine decodes it."""
    multi, words = _asr_engine(3)
    single, _ = _asr_engine(1)
    data = SyntheticASR(words)
    utts = [data.utterance(i)["audio"] for i in range(5)]
    got = multi.serve(utts)
    assert multi.metrics.catchup_steps > 0
    assert multi.metrics.parked_slots == 0
    for audio, res in zip(utts, got):
        ref = single.serve([audio])[0]
        _same(res, ref)
        assert res["steps"] == ref["steps"]


def test_fault_in_catchup_dispatch_evicts_only_its_session():
    """The poisoned session steps only in a catch-up dispatch (w=2 over
    slots 2 and 3): bisection evicts it alone, slot 2 commits in the same
    round, and every other session finishes as on a fault-free engine."""
    windows, poison = (5, 5, 2, 2, 1), 3
    ref, _ = _asr_engine(5)
    policy = FaultPolicy([FaultSpec(
        "asr_step", count=None,
        match=lambda ctx: poison_sid in ctx.get("sids", ()),
        message="poison slot")])
    eng = AsrEngine(EngineConfig(ref.program, n_slots=5, faults=policy),
                    ref.params)
    runs = [[e.open().push(_windows(e, k)) for k in windows]
            for e in (eng, ref)]
    poison_sid = runs[0][poison].sid
    for e in (eng, ref):
        assert e._step()
    # w=4 and w=2 tie at 8 windows: the larger w takes slots 0 and 1
    assert ref.step_shapes[0] == (2, 2, 4)
    assert list(ref.step_shapes)[1:] == [(2, 2, 2), (1, 1, 1)]
    assert runs[0][poison].faulted
    assert eng.metrics.faulted_sessions == 1
    assert eng._fault_log[0]["sid"] == poison_sid
    assert all(poison_sid in e["ctx"]["sids"] for e in policy.log)
    # the survivor of the faulted catch-up committed in the same round
    assert [eng.slot_windows(s) for s in (0, 1, 2, 4)] == [1, 1, 0, 0]
    assert eng.metrics.catchup_steps == 2
    with pytest.raises(SessionFaulted, match="decoding step failed"):
        runs[0][poison].finish()
    for i, (sess, rsess) in enumerate(zip(*runs)):
        if i != poison:
            _same(sess.finish(), rsess.finish())


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
    # round's first dispatch steps the three-window slot and a catch-up
    # steps the one-window slot in the same round, before any harvest
    ordered = sorted(spans["engine.step"], key=lambda e: e[0])
    steps = [s for _a, _b, s in ordered]
    assert steps[0] == {"n": 1, "w": 2, "parked": 0, "catchup": 0}
    assert steps[1] == {"n": 1, "w": 1, "parked": 0, "catchup": 1}
    assert steps[2] == {"n": 1, "w": 1, "parked": 0, "catchup": 0}
    assert ordered[1][1] <= min(a for a, _b, _s in spans["engine.harvest"])
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

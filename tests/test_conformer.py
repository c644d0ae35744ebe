"""The streaming FastConformer-CTC on the ASR engine's normal path, at a
tiny size on the CPU, against the plain reference the benchmark decides
`correct` with (benchmarks/chip/models/fastconformer_ctc_reference.py:
the whole utterance at once under the chunk mask, no cache, no kernel).

Tiny: 2 blocks of d_model 64 (4 heads of 16), 8 subsampling channels,
chunk 4 with 8 frames of left context (att_context_size [8, 3]), 33
outputs, seeded random weights.  A window is 32 feature frames, 0.32 s.
"""
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

MODELS = Path(__file__).resolve().parents[1] / "benchmarks" / "chip" / "models"
if str(MODELS) not in sys.path:
    sys.path.insert(0, str(MODELS))

import fastconformer_ctc as fc  # noqa: E402
import fastconformer_ctc_reference as ref  # noqa: E402

from repro.core import features  # noqa: E402
from repro.kernels.policy import KernelPolicy  # noqa: E402
from repro.models import conformer  # noqa: E402

TINY = {
    "model": {"n_mels": 80, "sub_layers": 3, "sub_channels": 8,
              "d_model": 64, "n_layers": 2, "n_heads": 4,
              "ff_expansion": 4, "conv_kernel": 9,
              "att_context_size": [8, 3], "vocab": 33},
    "decoder": {"beam_size": 16, "beam_threshold": 25.0, "lm_weight": 1.5,
                "word_score": 1.0, "blank_id": 0, "max_children": 8},
    "lexicon": {"n_words": 60, "trie_nodes": 512},
    "n_slots": 4, "max_windows_per_step": 4, "kernels": "ref",
}
WINDOW = 32 * 160                  # samples a window advances by
OVERLAP = 400 - 160                # framing overlap a step keeps buffered
# float32 throughout: the streamed encoder and the whole-utterance
# reference sum the same terms in different orders (cached keys against
# a masked full score matrix, a per-chunk window against the whole map),
# which leaves a few float32 ulps on log-probs of size ~3.5; a bfloat16
# matmul anywhere moves them by 1e-3 or more
LOGP_ATOL = 2e-5
# final scores are sums of ~100 such log-probs plus LM terms, so the same
# rounding, relative to the score
SCORE_RTOL = 1e-5


def speech(seed: int, seconds: float) -> np.ndarray:
    """Voiced, syllable-paced synthetic audio at 16 kHz."""
    rng = np.random.default_rng(seed)
    t = np.arange(int(seconds * 16000)) / 16000
    f0 = rng.uniform(90, 240)
    env = np.maximum(0, np.sin(2 * np.pi * rng.uniform(3, 6) * t)) ** 2
    sig = 0.15 * env * sum(np.sin(2 * np.pi * h * f0 * t) / h
                           for h in range(1, 6))
    return (sig + 0.01 * rng.standard_normal(t.size)).astype(np.float32)


@pytest.fixture(scope="module")
def system():
    return fc.System(TINY, 3)


@pytest.fixture(scope="module")
def cfg():
    return fc.conformer_config(TINY["model"])


@pytest.fixture(scope="module")
def engine(system):
    """engine(n_slots, max_windows): one engine over `system` per slot
    count and fusion bound, built once and shared (each compiles its
    step entries on first use; sessions leave nothing behind)."""
    built = {}

    def get(n_slots, max_windows):
        if (n_slots, max_windows) not in built:
            conf = dict(system.cfg, n_slots=n_slots,
                        max_windows_per_step=max_windows)
            shadow = fc.System.__new__(fc.System)
            shadow.__dict__.update(system.__dict__, cfg=conf)
            built[n_slots, max_windows] = fc.build_engine(shadow)
        return built[n_slots, max_windows]
    return get


@pytest.fixture(scope="module")
def utterance(system):
    """(windows, reference log-probs) of one 2.3 s utterance: 7 windows,
    the last a zero-padded tail."""
    wins = ref.windows_of(speech(5, 2.3), TINY["model"])
    fwd = jax.jit(lambda p, x: ref.forward(p, TINY["model"], x))
    return wins, np.asarray(fwd(system.params, jnp.asarray(wins)[None]))[0]


@pytest.mark.parametrize("mode", ["ref", "interpret"])
@pytest.mark.parametrize("w", [1, 4])
def test_streamed_log_probs_match_reference(system, cfg, utterance, w,
                                            mode):
    """The engine's front end and encoder, streamed w windows per step
    through the carried state, give the reference's masked
    whole-utterance log-probs."""
    wins, want = utterance
    policy = KernelPolicy(mode)
    state = conformer.init_batched_stream_state(cfg, 1)
    got = []
    for i in range(0, len(wins), w):
        feats = features.logmel(jnp.asarray(wins[i:i + w])[None],
                                kernels=policy, hot=True)
        lp, state = conformer.forward_batched(
            system.params, cfg, feats.reshape(1, -1, 80), state,
            kernels=policy)
        got.append(np.asarray(lp[0]))
    got = np.concatenate(got)
    assert got.shape == want.shape == (len(wins) * 4, 33)
    np.testing.assert_allclose(got, want, rtol=0, atol=LOGP_ATOL)


def test_final_beams_match_reference_search(system, engine):
    """Whole utterances through `AsrEngine.serve` (slot pool, window
    fusion, catch-ups, tail flush) end in the reference search's best
    hypothesis over the reference's log-probs."""
    utts = [speech(11 + i, s) for i, s in enumerate((1.0, 1.7, 2.3, 0.5,
                                                      3.1))]
    served = engine(4, 4).serve(utts)
    r = fc.Reference(system)
    beams = [r.decode(lp) for lp in r.log_probs(utts, 12)]
    for res, beam in zip(served, beams):
        b = ref.best(beam)
        assert tuple(res["tokens"].tolist()) == b["tokens"]
        assert tuple(res["words"].tolist()) == b["words"]
        assert abs(res["score"] - b["score"]) <= SCORE_RTOL * abs(b["score"])
    nums = fc.compare(served, beams)
    assert nums["score_gap"] < SCORE_RTOL and nums["rescore_gap"] < SCORE_RTOL


def test_slot_result_independent_of_dispatch_partners(engine):
    """One utterance decoded alone and beside others that join and leave
    its dispatches at other times gives the same result, bit for bit:
    every batch row depends on its own slot alone.  So does decoding it
    one window per step: a w=4 step carries the state across its chunks
    as four w=1 steps do."""
    mine = speech(21, 1.9)
    alone = engine(1, 4).serve([mine])[0]
    crowd = engine(4, 4).serve(
        [speech(22, 0.7), mine, speech(23, 2.9), speech(24, 1.2),
         speech(25, 0.4)])[1]
    single = engine(1, 1).serve([mine])[0]
    for other in (crowd, single):
        assert other["tokens"].tolist() == alone["tokens"].tolist()
        assert other["words"].tolist() == alone["words"].tolist()
        assert other["score"] == alone["score"]


def test_reset_leaves_nothing_of_the_last_utterance(engine, cfg):
    """A slot admitted after another utterance finished in it holds the
    fresh state (zero caches, nothing seen), and decodes its utterance as
    a slot that held nothing before does."""
    eng = engine(1, 4)
    first, second = speech(31, 2.1), speech(32, 1.3)
    eng.serve([first])
    used = jax.tree.map(lambda a: np.asarray(a[0]), eng._stream_state)
    assert used["seen"] == cfg.left and np.abs(used["k"]).max() > 0
    sess = eng.open()
    sess.push(second)                  # admitted into slot 0: reset
    fresh = conformer.init_stream_state(cfg)
    for name, leaf in fresh.items():
        np.testing.assert_array_equal(np.asarray(eng._stream_state[name][0]),
                                      np.asarray(leaf), err_msg=name)
    res = sess.finish()
    want = engine(4, 4).serve([second])[0]
    assert res["tokens"].tolist() == want["tokens"].tolist()
    assert res["score"] == want["score"]


@pytest.mark.parametrize("tail,frames", [(0, 0), (1740, 2), (300, 3),
                                         (5000, 0)])
def test_tail_pad_frames_counts_the_flush_padding(engine, tail, frames):
    """A finished utterance's partial last window is zero-padded and
    decoded; the counter gets the window's CTC frames (4 of 1280 samples
    each) that start in the padding.  An utterance ending on a window
    boundary pads nothing."""
    eng = engine(1, 4)
    before = eng.metrics.snapshot()["steps"]["tail_pad_frames"]
    eng.serve([speech(41, (2 * WINDOW + OVERLAP + tail) / 16000)])
    assert eng.metrics.snapshot()["steps"]["tail_pad_frames"] \
        == before + frames


def test_model_follows_the_acoustic_config(engine, cfg):
    """The program picks the model from its config's type; the window is
    the model's own; what only TDS implements is refused at build."""
    from repro.configs.tds_asr import TDS_CONFIG
    from repro.core.stepplan import make_step_plan
    from repro.models import tds
    from repro.serving import AsrProgram, EngineConfig

    eng = engine(4, 4)
    prog = eng.program
    assert prog.model is conformer and prog.step_ms == 320.0
    assert (eng.plan.samples_per_step, eng.plan.feat_frames_per_step,
            eng.plan.acoustic_frames_per_step) == (WINDOW, 32, 4)
    tds_prog = AsrProgram(TDS_CONFIG, prog.lex, prog.lm)
    assert tds_prog.model is tds and tds_prog.step_ms == 80.0
    with pytest.raises(ValueError, match="window"):
        make_step_plan(cfg, step_ms=80.0)
    with pytest.raises(ValueError, match="int8"):
        AsrProgram(cfg, prog.lex, prog.lm, use_int8=True)
    mesh = jax.sharding.Mesh(np.array(jax.devices()[:1]), ("model",))
    with pytest.raises(ValueError, match="mesh"):
        EngineConfig(prog, n_slots=2, mesh=mesh)
    with pytest.raises(TypeError, match="no acoustic model"):
        AsrProgram(object(), prog.lex, prog.lm)

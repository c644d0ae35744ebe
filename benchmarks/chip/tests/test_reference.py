"""The plain reference decodes as the program does, at a tiny size on
the CPU: same best hypothesis, scores equal to float32 rounding."""
import numpy as np
import pytest

import tds_ctc
import tds_ctc_reference as ref

TINY = {
    "model": {"n_mfcc": 80, "n_mels": 80, "feat": 80, "sub_kernel": 10,
              "stages": [[1, 4, 9, 2], [1, 4, 9, 2], [1, 6, 9, 2]],
              "vocab": 40},
    "decoder": {"beam_size": 16, "beam_threshold": 25.0, "lm_weight": 1.5,
                "word_score": 1.0, "blank_id": 0, "max_children": 8},
    "lexicon": {"n_words": 60, "trie_nodes": 512},
    "n_slots": 4, "max_windows_per_step": 4, "kernels": "ref",
}


def audio(seed, seconds):
    from audio import utterance
    return utterance(seed, 0, seconds)


@pytest.fixture(scope="module")
def system():
    return tds_ctc.System(TINY, 3)


def test_windows_of_matches_engine_windowing():
    a = np.arange(1280 * 5 + 700, dtype=np.float32)
    w = ref.windows_of(a)
    # 5 whole windows fit (the 5th needs 1280*4+1520 <= 7100), then a
    # zero-padded tail of 7100 - 6400 = 700 > 240 samples
    assert w.shape == (6, 1520)
    assert w[5, :700].tolist() == a[6400:].tolist()
    assert not w[5, 700:].any()
    assert ref.windows_of(np.zeros(1280 * 3 + 240, np.float32)).shape[0] == 3


def test_reference_matches_program_decode(system):
    import jax

    engine = tds_ctc.build_engine(system)
    utts = [audio(11 + i, s) for i, s in enumerate((1.0, 1.7, 2.3))]
    with jax.default_matmul_precision("highest"):
        served = engine.serve(utts)
    r = tds_ctc.Reference(system)
    lps = r.log_probs(utts, 40)
    beams = [r.decode(lp) for lp in lps]
    for res, beam in zip(served, beams):
        b = ref.best(beam)
        assert tuple(res["tokens"].tolist()) == b["tokens"]
        assert tuple(res["words"].tolist()) == b["words"]
        assert abs(res["score"] - b["score"]) <= 1e-5 * abs(b["score"])
    nums = tds_ctc.compare(served, beams)
    assert nums["score_gap"] < 1e-5 and nums["rescore_gap"] < 1e-5


def test_compare_flags_an_altered_token(system):
    r = tds_ctc.Reference(system)
    lp = r.log_probs([audio(5, 1.5)], 40)[0]
    beam = r.decode(lp)
    b = ref.best(beam)
    good = {"tokens": np.array(b["tokens"]), "words": np.array(b["words"]),
            "score": b["score"]}
    assert tds_ctc.compare([good], [beam]) == {
        "score_gap": 0.0, "median_gap": 0.0, "rescore_gap": 0.0}
    toks = list(b["tokens"]) or [0]
    toks[-1] = (toks[-1] + 1) % TINY["model"]["vocab"]
    bad = dict(good, tokens=np.array(toks))
    assert tds_ctc.compare([bad], [beam])["rescore_gap"] > 1e-3


def test_served_form_keeps_the_last_entry_in_the_last_row():
    assert ref.served_form(range(5), 3) == (0, 1, 4)
    assert ref.served_form(range(3), 3) == (0, 1, 2)

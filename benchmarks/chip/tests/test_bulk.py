"""The bulk mix's lengths against their published source, and the
counting of audio decoded in a span."""
import json
from pathlib import Path

import pytest

import bulk
import traffic

MIX = json.loads((Path(__file__).resolve().parents[1] / "traffic"
                  / "bulk_files.json").read_text())


def test_lengths_match_the_published_corpus():
    # LibriSpeech test-clean: 5.4 h over 2620 utterances, at most 35 s
    lens = traffic.bulk_lengths(MIX, 2 ** 31 + 11)
    d = traffic.describe(lens)
    assert d["mean"] == pytest.approx(5.4 * 3600 / 2620, rel=1e-3)
    assert d["max"] == 35.0 and d["min"] == 1.0
    # every seed draws the same lengths, in its own order
    other = traffic.bulk_lengths(MIX, 3)
    assert sorted(other) == sorted(lens) and other != lens


def test_overlap():
    assert bulk.overlap(0.0, 4.0, 1.0, 3.0) == 0.5
    assert bulk.overlap(0.0, 4.0, 3.0, 9.0) == 0.25
    assert bulk.overlap(0.0, 4.0, 5.0, 9.0) == 0.0
    assert bulk.overlap(2.0, 2.0, 1.0, 3.0) == 1.0


def test_decoded_audio_spreads_each_utterance_over_its_time():
    lens = [8.0, 2.0, 6.0]
    recs = [{"index": 0, "t0": 0.0, "t1": 4.0, "result": {}},
            {"index": 1, "t0": 4.0, "t1": 5.0, "result": {}},
            {"index": 2, "t0": 5.0, "t1": 8.0, "result": None}]
    # a third of the first, all of the second, nothing of the failed one
    assert bulk.decoded_s(recs, lens, 3.0, 6.0) == pytest.approx(4.0)

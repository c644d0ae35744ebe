"""The streaming FastConformer-CTC family at a tiny size on the CPU: the
plain reference decodes as the program does, the warm-up compiles each
step entry once, a tiny cell runs through the harness untraced and
traced, and the new costs and readers count what they say.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
import io
import json
import shutil
from contextlib import redirect_stdout
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

import fastconformer_ctc as fc
import fastconformer_ctc_reference as ref
import run
from devtrace import Event, Reduced

CHIP = Path(__file__).resolve().parents[1]

TINY = {
    "family": "fastconformer_ctc",
    "model": {"n_mels": 80, "sub_layers": 3, "sub_channels": 8,
              "d_model": 64, "n_layers": 2, "n_heads": 4,
              "ff_expansion": 4, "conv_kernel": 9,
              "att_context_size": [8, 3], "vocab": 33, "dtype": "float32"},
    "decoder": {"beam_size": 16, "beam_threshold": 25.0, "lm_weight": 1.5,
                "word_score": 1.0, "blank_id": 0, "max_children": 8},
    "lexicon": {"n_words": 60, "trie_nodes": 512},
    "n_slots": 2, "max_windows_per_step": 4, "kernels": "ref",
    "matmul_precision": "highest",
}
BULK = {"entry": "bulk", "files": 6, "clients_per_slot": 1,
        "duration": {"median_s": 1.0, "sigma": 0.3, "min_s": 0.6,
                     "max_s": 2.0},
        "ramp_s": 0.5, "trace_at_s": 0.5, "trace_s": 1.0}
# the CPU computes every float32 matmul in full, whatever precision is
# asked for, so the tiny control is held one dtype lower instead
LIMITS = {"sample": 3, "reference_windows": 8,
          "control": {"dtype": "bfloat16", "precision": "default"},
          "limits": {"score_gap": 1e-4, "rescore_gap": 1e-4}}


def audio(seed, seconds):
    from audio import utterance
    return utterance(seed, 0, seconds)


@pytest.fixture(scope="module")
def system():
    return fc.System(TINY, 3)


def test_windows_of_matches_engine_windowing():
    # chunk 4: 32 frames, 5360 samples a window at a 5120-sample shift
    a = np.arange(5120 * 2 + 900, dtype=np.float32)
    w = ref.windows_of(a, TINY["model"])
    assert w.shape == (3, 5360)
    assert w[2, :900].tolist() == a[10240:].tolist()
    assert not w[2, 900:].any()
    assert ref.windows_of(np.zeros(5120 * 2 + 240, np.float32),
                          TINY["model"]).shape[0] == 2


def test_reference_matches_program_decode(system):
    import jax

    engine = fc.build_engine(system)
    utts = [audio(11 + i, s) for i, s in enumerate((1.0, 1.7, 2.3))]
    with jax.default_matmul_precision("highest"):
        served = engine.serve(utts)
    r = fc.Reference(system)
    beams = [r.decode(lp) for lp in r.log_probs(utts, 8)]
    for res, beam in zip(served, beams):
        b = ref.best(beam)
        assert tuple(res["tokens"].tolist()) == b["tokens"]
        assert tuple(res["words"].tolist()) == b["words"]
        assert abs(res["score"] - b["score"]) <= 1e-5 * abs(b["score"])
    nums = fc.compare(served, beams)
    assert nums["score_gap"] < 1e-5 and nums["rescore_gap"] < 1e-5


def test_warm_up_compiles_each_step_entry_once(system):
    engine = fc.build_engine(system)
    fc.warm_up(engine, lambda _msg: None)
    # 2 slots: slot buckets (2, 1) x window buckets (4, 2, 1)
    want = [(b, b, w) for w in (4, 2, 1) for b in (2, 1)]
    assert list(engine.step_shapes) == want
    assert engine._jit_step._cache_size() == len(want)


def make_root(tmp: Path) -> Path:
    """A checkout holding the benchmark's files plus a tiny Conformer
    bulk cell in place of the benchmark's own."""
    chip = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "tests", "out", "__pycache__"))
    (chip / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (chip / "traffic" / "tiny_bulk.json").write_text(json.dumps(BULK))
    (chip / "limits" / "fastconformer_ctc.json").write_text(
        json.dumps(LIMITS))
    import jax
    peaks = json.loads((chip / "peaks.json").read_text())
    peaks[jax.devices()[0].device_kind] = dict(
        next(iter(peaks.values())), source="test stand-in")
    (chip / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = ["tiny.fconf" for w in m["workloads"]
                              if w == "fconf8.bulk"]
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.fconf", "config": "tiny", "traffic": "tiny_bulk",
         "chips": 1, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.mark.parametrize("trace", [0, 1])
def test_cell_runs_on_the_harness(root, monkeypatch, trace):
    import jax
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    out = io.StringIO()
    with redirect_stdout(out):
        assert run.main(["--workload", "tiny.fconf", "--seed",
                         str(2 ** 31 + 9), "--seconds", "2.5", "--trace",
                         str(trace)], root=root) == 0
    lines = out.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    assert res["correct"], lines[-12:]
    assert res["attempted"] > 0 and res["failed"] == 0
    if trace:
        # on the CPU no device plane exists: the device readers find
        # nothing, and the MFU reader needs a chip that ran the steps
        assert set(res["metrics"]) <= {
            "step_device_ms.bulk", "idle_pct.bulk", "conformer_mfu.bulk",
            "hypothesis_unit_roofline", "chunk_attention_roofline"}
    else:
        assert set(res["metrics"]) == {"audio_x_rt", "setup_s"}
        assert any(ln.startswith("compilations inside the window: 0")
                   for ln in lines), lines


# ---- costs and readers ------------------------------------------------------

def cost(name):
    return run.load_module(CHIP / "costs" / f"{name}.py")


def test_forward_flops_per_window_at_published_widths():
    sizes = json.loads((CHIP / "configs" / "fastconformer_l_stream.json")
                       .read_text())["model"]
    c = cost("fastconformer_forward")
    assert c.window_s(sizes) == pytest.approx(1.12)
    macs = c.macs_per_window(sizes)
    # subsampling: 56x40x256x9 + 28x20x256x(9 + 256) + 14x10x256x(9 + 256)
    # + 14 x 2560 x 512; blocks: 17 x 14 x (2 x 2 x 512 x 2048
    # + 4 x 512^2 + (2 x 84 + 97) x 512 + 2 x 512^2 + 9 x 512 + 512^2);
    # head 14 x 512 x 1025
    assert macs == {
        "subsample": 56 * 40 * 256 * 9 + 28 * 20 * 256 * 265
        + 14 * 10 * 256 * 265 + 14 * 2560 * 512,
        "blocks": 17 * 14 * (4 * 512 * 2048 + 4 * 512 ** 2 + 265 * 512
                             + 3 * 512 ** 2 + 9 * 512),
        "head": 14 * 512 * 1025}
    # about 221 MFLOP an encoder frame
    assert c.flops_per_window(sizes) / 14 == pytest.approx(220.96e6,
                                                           rel=1e-4)


def test_chunk_attention_calls():
    # chunk 4, left 8, 4 heads of 16, 2 layers; b = 3 rows, w = 2 windows
    t, keys, dists = 8, 12, 15
    flops = 2 * 3 * 4 * 2 * 4 * 16 * (2 * keys + dists)
    nbytes = 4 * (3 * 4 * 16 * (2 * t + 2 * (8 + t)) + 4 * dists * 16
                  + 2 * 4 * 16 + 3 * (8 + t))
    assert cost("chunk_attention").calls(TINY["model"], 3, 2) == [
        (float(flops), float(nbytes))] * 2


def _reduced(n_calls):
    """One chip, three jit_step executions of 2 rows x 1 window (4
    frames): each runs 4 hypothesis_unit calls and `n_calls`
    chunk_attention calls of 1 ms."""
    ops, mods = [], []
    for i in range(3):
        t0 = 10.0 * i
        mods.append(Event("jit_step", t0, 5.0))
        ops += [Event(f"hypothesis_unit.{j}", t0 + 0.1 * j, 0.01,
                      "hypothesis_unit", (2, 16)) for j in range(4)]
        ops += [Event(f"chunk_attention.{j}", t0 + 1 + j, 1e-3)
                for j in range(n_calls)]
    return Reduced(window_s=30.0, chips=1, ops=[sorted(
        ops, key=lambda e: e.start)], modules=[mods])


def test_chunk_attention_roofline_reads_calls_by_name():
    peak = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e9}
    view = SimpleNamespace(trace=_reduced(2), sizes=TINY["model"],
                           peak=peak, cost=cost)
    reader = run.load_module(CHIP / "metrics" /
                             "chunk_attention_roofline.py")
    (f, nbytes), = set(cost("chunk_attention").calls(TINY["model"], 2, 1))
    least = max(f / 1e12, nbytes / 1e9)
    # the middle execution alone is whole: 2 calls of 1 ms
    assert reader.read(view) == pytest.approx(100 * 2 * least / 2e-3)
    view.trace = _reduced(3)             # not one call per layer: left out
    assert reader.read(view) is None


def test_conformer_mfu_reads_the_benchmarks_own_audio_count():
    view = SimpleNamespace(
        measured={"trace_audio_s": 2.24}, sizes=TINY["model"],
        trace=SimpleNamespace(chips=1, window_s=2.0),
        peak={"bf16_flops_per_s": 1e12}, cost=cost)
    reader = run.load_module(CHIP / "metrics" / "conformer_mfu.bulk.py")
    per_window = cost("fastconformer_forward").flops_per_window(
        TINY["model"])
    # 2.24 s of audio is 7 windows of 0.32 s
    assert reader.read(view) == pytest.approx(
        100 * 7 * per_window / (2.0 * 1e12))

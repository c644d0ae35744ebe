"""The harness end to end at a tiny size on the CPU, with its look for a
chip skipped: a bulk cell through the engine worker, untraced and
traced.  Then the same run with the timed path broken underneath, once
per fault the cell can have, must come out not correct.

    PYTHONPATH=src python -m pytest -q benchmarks/chip/tests
"""
import io
import json
import shutil
from contextlib import redirect_stdout
from pathlib import Path

import pytest

CHIP = Path(__file__).resolve().parents[1]

TINY = {
    "family": "tds_ctc",
    "model": {"n_mfcc": 80, "n_mels": 80, "feat": 80, "sub_kernel": 10,
              "stages": [[1, 4, 9, 2], [1, 4, 9, 2], [1, 6, 9, 2]],
              "vocab": 40, "dtype": "float32"},
    "decoder": {"beam_size": 16, "beam_threshold": 25.0, "lm_weight": 1.5,
                "word_score": 1.0, "blank_id": 0, "max_children": 8},
    "lexicon": {"n_words": 60, "trie_nodes": 512},
    "n_slots": 2, "max_windows_per_step": 4, "kernels": "ref",
    "matmul_precision": "highest",
}
DURATION = {"median_s": 1.0, "sigma": 0.3, "min_s": 0.6, "max_s": 2.0}
BULK = {"entry": "bulk", "duration": DURATION, "files": 6,
        "clients_per_slot": 1, "ramp_s": 0.5, "trace_at_s": 0.5,
        "trace_s": 1.0}
# the CPU computes every float32 matmul in full, whatever precision is
# asked for, so the tiny control is held one dtype lower instead
LIMITS = {"sample": 3, "reference_windows": 40,
          "control": {"dtype": "bfloat16", "precision": "default"},
          "limits": {"score_gap": 1e-4, "rescore_gap": 1e-4}}


def make_root(tmp: Path) -> Path:
    """A checkout holding the benchmark's files plus a tiny bulk cell."""
    chip = tmp / "benchmarks" / "chip"
    shutil.copytree(CHIP, chip, ignore=shutil.ignore_patterns(
        "tests", "out", "__pycache__"))
    (chip / "configs" / "tiny.json").write_text(json.dumps(TINY))
    (chip / "traffic" / "tiny_bulk.json").write_text(json.dumps(BULK))
    (chip / "limits" / "tds_ctc.json").write_text(json.dumps(LIMITS))
    import jax
    peaks = json.loads((chip / "peaks.json").read_text())
    peaks[jax.devices()[0].device_kind] = dict(
        next(iter(peaks.values())), source="test stand-in")
    (chip / "peaks.json").write_text(json.dumps(peaks))
    bench = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
    rename = {"edge8.bulk": "tiny.bulk"}
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in m:
            m["workloads"] = sorted({rename[w] for w in m["workloads"]})
    bench["configs"] = [{"name": "tiny", "source": "test",
                         "file": "benchmarks/chip/configs/tiny.json",
                         "reduced": [], "why": "test"}]
    bench["workloads"] = [
        {"name": "tiny.bulk", "config": "tiny", "traffic": "tiny_bulk",
         "chips": 1, "why": "test"}]
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench))
    return tmp


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return make_root(tmp_path_factory.mktemp("checkout"))


@pytest.fixture
def harness(monkeypatch):
    import jax
    import run
    monkeypatch.setattr(run, "require_chips", lambda n: jax.devices()[:n])
    return run


def run_cell(harness, root, workload, seed=2**31 + 5, trace=0):
    out = io.StringIO()
    with redirect_stdout(out):
        rc = harness.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", "2.5", "--trace", str(trace)],
                          root=root)
    assert rc == 0
    lines = out.getvalue().strip().splitlines()
    return json.loads(lines[-1]), lines


def test_bulk_cell_runs_and_is_correct(harness, root):
    res, lines = run_cell(harness, root, "tiny.bulk")
    assert res["correct"], lines[-12:]
    assert set(res["metrics"]) == {"audio_x_rt", "setup_s"}
    assert res["metrics"]["audio_x_rt"]["value"] > 0
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert any(ln.startswith("compilations inside the window: 0")
               for ln in lines), lines


def test_bulk_cell_traced(harness, root):
    res, lines = run_cell(harness, root, "tiny.bulk", trace=1)
    assert res["correct"], lines[-12:]
    # on the CPU no device plane exists: the device readers find nothing
    assert set(res["metrics"]) <= {"step_mfu.bulk", "step_device_ms.bulk",
                                   "tds_conv_roofline", "idle_pct.bulk",
                                   "hypothesis_unit_roofline"}
    assert res["device"]["platform"] == "cpu"


def _faulty(harness, monkeypatch, fault):
    real = harness.Cell.model

    def model(self):
        mod = real(self)
        build = mod.build_engine

        def build_engine(system):
            eng = build(system)
            fault(eng)
            return eng
        mod.build_engine = build_engine
        return mod
    monkeypatch.setattr(harness.Cell, "model", model)


def _state_unchanged(eng):
    step = eng._jit_step

    def broken(params, prepared, tables, ss, beam, batch, idx):
        step(params, prepared, tables, ss, beam, batch, idx)
        return ss, beam
    eng._jit_step = broken


def _half_batch(eng):
    import jax
    step = eng._jit_step

    @jax.jit
    def undo_second_half(new, old, idx):
        keep = idx[idx.shape[0] // 2:]
        return jax.tree.map(lambda n, o: n.at[keep].set(o[keep]), new, old)

    def broken(params, prepared, tables, ss, beam, batch, idx):
        new = step(params, prepared, tables, ss, beam, batch, idx)
        return undo_second_half(new, (ss, beam), idx)
    eng._jit_step = broken


def _token_altered(eng):
    fin = eng._finalize_slot

    def broken(slot):
        res = fin(slot)
        res["tokens"] = (list(res["tokens"][:-1])
                         + [int(res["tokens"][-1]) + 1]
                         if len(res["tokens"]) else [1])
        return res
    eng._finalize_slot = broken


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch,
                                   _token_altered],
                         ids=["state_unchanged", "half_batch",
                              "token_altered"])
def test_broken_timed_path_is_not_correct(harness, root, monkeypatch,
                                          fault):
    _faulty(harness, monkeypatch, fault)
    res, lines = run_cell(harness, root, "tiny.bulk", seed=77)
    assert res["correct"] is False, lines[-6:]

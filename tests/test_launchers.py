"""Launcher integration: train loop (loss decreases, ckpt resume) and the
ASRPU serving path, exercised end-to-end on tiny configs."""
import numpy as np


def test_train_launcher_tiny(tmp_path):
    from repro.launch import train
    losses = train.main(["--arch", "mamba2-1.3b", "--tiny", "--steps", "30",
                         "--batch", "4", "--seq", "32", "--lr", "3e-3",
                         "--ckpt", str(tmp_path), "--ckpt-every", "10",
                         "--log-every", "100"])
    assert len(losses) == 30
    assert losses[-1] < losses[0]
    # resume
    losses2 = train.main(["--arch", "mamba2-1.3b", "--tiny", "--steps", "5",
                          "--batch", "4", "--seq", "32", "--ckpt",
                          str(tmp_path), "--resume", "--log-every", "100"])
    assert len(losses2) == 5
    assert np.isfinite(losses2).all()


def test_train_launcher_moe_tiny():
    from repro.launch import train
    losses = train.main(["--arch", "qwen2-moe-a2.7b", "--tiny", "--steps",
                         "10", "--batch", "4", "--seq", "32",
                         "--log-every", "100"])
    assert np.isfinite(losses).all()


def test_serve_asr_launcher(capsys, monkeypatch):
    from repro.launch import serve
    # main() turns on the persistent compile cache; keep this worker's
    # later compiles out of the checkout
    monkeypatch.setattr("repro.runtime.compile_cache.use_persistent_cache",
                        lambda: "off")
    serve.main(["--mode", "asr", "--utterances", "1"])
    out = capsys.readouterr().out
    assert "RTF" in out and "best words" in out

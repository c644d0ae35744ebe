"""Synthetic speech-like audio from a seed (numpy only).

A voiced carrier (a gliding fundamental with decaying harmonics) under a
syllable-rate envelope, plus low noise, at 16 kHz, peak about 0.3.
Utterance `index` of seed `seed` is the same array in every process, so
the reference decodes the audio the program served."""
from __future__ import annotations

import numpy as np

SAMPLE_RATE = 16000


def utterance(seed: int, index: int, seconds: float) -> np.ndarray:
    rng = np.random.default_rng([int(seed) % 2 ** 63, int(index)])
    n = int(round(seconds * SAMPLE_RATE))
    t = np.arange(n) / SAMPLE_RATE
    f0 = rng.uniform(90.0, 240.0) * (1 + 0.08 * np.sin(
        2 * np.pi * rng.uniform(0.5, 2.0) * t + rng.uniform(0, 6.3)))
    phase = 2 * np.pi * np.cumsum(f0) / SAMPLE_RATE
    voiced = sum(np.sin(h * phase) / h for h in range(1, 8))
    rate = rng.uniform(3.0, 6.0)                      # syllables per second
    env = np.maximum(0.0, np.sin(2 * np.pi * rate * t
                                 + rng.uniform(0, 6.3))) ** 2
    sig = 0.15 * env * voiced + 0.01 * rng.standard_normal(n)
    return sig.astype(np.float32)

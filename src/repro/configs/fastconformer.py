"""Cache-aware streaming FastConformer-CTC: the second acoustic model.

Conformer blocks (Gulati et al., arXiv:2005.08100) at FastConformer's
widths and 8x depthwise-striding subsampling (Rekesh et al.,
arXiv:2305.05084), trained for cache-aware chunked streaming (Noroozi
et al., arXiv:2312.17279), as NVIDIA NeMo releases it in
`stt_en_fastconformer_hybrid_large_streaming_multi`, whose CTC head is
the one served here.  `att_context_size` [70, 13] is `chunked_limited`
attention: chunks of 13 + 1 = 14 encoder frames, each frame seeing its
own chunk and the 70 / 14 = 5 chunks before it.
"""
from __future__ import annotations

from dataclasses import dataclass


@dataclass(frozen=True)
class ConformerConfig:
    name: str = "fastconformer-ctc-streaming"
    n_mels: int = 80             # log-mel inputs, 25 ms / 10 ms
    sub_layers: int = 3          # stride-2 3x3 convs: subsampling by 8
    sub_channels: int = 256
    d_model: int = 512
    n_layers: int = 17
    n_heads: int = 8
    ff_expansion: int = 4
    conv_kernel: int = 9         # causal depthwise conv of the conv module
    chunk: int = 14              # att_context_size right (13) + 1
    left_chunks: int = 5         # att_context_size left (70) / chunk
    vocab_size: int = 1025       # 1024 SentencePiece tokens + blank (id 0)
    ln_eps: float = 1e-5

    @property
    def subsample(self) -> int:
        return 2 ** self.sub_layers

    @property
    def sub_freq(self) -> int:
        """Mel bins left after the subsampling (80 -> 40 -> 20 -> 10)."""
        return self.n_mels // self.subsample

    @property
    def left(self) -> int:
        """Encoder frames of left context a frame's attention may see
        beyond its own chunk (att_context_size left, 70)."""
        return self.chunk * self.left_chunks

    @property
    def d_head(self) -> int:
        return self.d_model // self.n_heads

    @property
    def d_ff(self) -> int:
        return self.d_model * self.ff_expansion


FASTCONFORMER_L_STREAM = ConformerConfig()

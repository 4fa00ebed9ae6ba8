"""Pallas TPU kernels for the hot ops.

Reference parity: this package plays the role of deeplearning4j-cuda's
cuDNN helper plug-ins (SURVEY §2.3 — `ConvolutionHelper` etc. loaded
reflectively by layer impls) — hand-tuned kernels behind the layer seam.
TPU-first difference: XLA already emits excellent conv/BN/pool kernels, so
those need no helpers; the wins are the ops XLA can't fuse across time
steps — the LSTM recurrence (the reference's `LSTMHelpers.java` fused
fwd/bwd, flagged in SURVEY §7 as the Pallas obligation) and blockwise
attention. Layers pick these up automatically on TPU and fall back to the
pure-XLA path elsewhere (mirroring the reference's helper-or-builtin
dispatch, `ConvolutionLayer.java:67-77`).
"""

from deeplearning4j_tpu.observe.trace import span as _span

with _span("import.ops"):
    from deeplearning4j_tpu.ops.lstm import fused_lstm, fused_lstm_available
    from deeplearning4j_tpu.ops.attention import flash_attention
    from deeplearning4j_tpu.ops.banded_attention import (
        banded_attention,
        banded_decode_attention,
        banded_eligible,
    )

__all__ = [
    "fused_lstm",
    "fused_lstm_available",
    "flash_attention",
    "banded_attention",
    "banded_decode_attention",
    "banded_eligible",
]

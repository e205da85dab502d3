"""paddle_tpu.ops — Pallas TPU kernels for ops XLA won't fuse optimally.

The reference's 650-kernel operator library (paddle/fluid/operators/) maps
almost entirely to XLA-fused lax ops; this package holds the hand kernels
that beat the compiler.  A kernel's tile is a rule of its arguments'
shapes, written beside the kernel from a table timed on the chip; nothing
is measured at run time (``autotune.py`` says what that name still holds):

* ``flash_attention`` (+ ``flash_attention_fwd_lse`` /
  ``flash_attention_bwd_chunk``) — O(S)-memory attention, forward and
  backward, triangle-grid causal path (flash_attention.py);
* ``conv1x1_bn_relu`` / ``conv1x1_bn_stats`` / ``bn_apply_relu`` —
  1x1-conv GEMM with the train-mode BatchNorm statistics fused into the
  epilogue, plus the one-pass normalize + residual-add + ReLU apply
  kernel the ResNet bottleneck tail dispatches to
  (fused_conv1x1_bn.py);
* ``grouped_matmul`` — one masked matmul over the MoE experts' ragged
  capacity-bucketed row groups (grouped_matmul.py);
* ``paged_flash_decode`` — flash-decode attention over a paged KV pool
  with the page-table walk in-kernel and per-page int8/fp8 dequant
  fused into the online-softmax loop, the paged serving decode hot
  path (paged_attention.py);
* ``gated_delta_chunk`` / ``gated_delta_step`` — the gated delta rule
  (linear attention over a decaying matrix state): a prompt in chunks with
  the state resident in VMEM, and one token a slot updating the stored
  states in place (gated_delta.py); ``kda_chunk`` / ``kda_step`` — the
  same rule with one decay a key channel (kda.py);
* ``quantized_matmul`` / ``fp8_matmul`` — int8×int8→int32 (and
  fp8-e4m3) matmul with the dequant + bias epilogue fused, the serving
  quantization hot path (quantized_matmul.py);
* ``layernorm_residual`` — residual add + LayerNorm in one HBM pass
  (fused_layernorm.py);
* ``softmax_cross_entropy`` — online-logsumexp label cross-entropy that
  never materializes the [rows, vocab] probability matrix
  (fused_softmax_xent.py);
* ``autotune`` — Mosaic's tile and VMEM constants, the block clamp, and
  the gates the model hot paths ask before they call a kernel
  (autotune.py; the measured tile search it was left in PR 48).
"""
from . import autotune  # noqa: F401
from .flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_bwd_chunk,
    flash_attention_fwd_lse,
)
from .fused_conv1x1_bn import (  # noqa: F401
    bn_apply_relu,
    conv1x1_bn_relu,
    conv1x1_bn_stats,
)
from .fused_layernorm import layernorm_residual  # noqa: F401
from .gated_delta import (  # noqa: F401
    gated_delta_chunk,
    gated_delta_step,
)
from .grouped_matmul import grouped_matmul  # noqa: F401
from .kda import kda_chunk, kda_step  # noqa: F401
from .paged_attention import (  # noqa: F401
    paged_flash_decode,
    paged_flash_eligible,
)
from .quantized_matmul import (  # noqa: F401
    fp8_matmul,
    quantized_linear,
    quantized_matmul,
)
from .fused_softmax_xent import softmax_cross_entropy  # noqa: F401

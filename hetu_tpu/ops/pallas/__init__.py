"""hetu_tpu.ops.pallas — TPU Pallas kernels for the ops XLA can't fuse well.

The reference's hot CUDA kernels (src/ops/*.cu) mostly map to single XLA HLOs;
the long-tail that needs hand-tiling on TPU lives here.  Flash attention is
the MFU-critical one (SURVEY §7: "BERT-large ≥45% MFU requires fused
attention"); the LM-head kernels are the memory-critical ones (the (N, vocab)
logits tensor is the peak of LM pretraining, and never materializes during
decode either — cross entropy for training, fused sampling for serving);
paged-decode attention is the serving-critical one (K/V pages read in place,
no contiguous gather per token).  All tunable block choices persist in one
shared autotune database (autotune.py) keyed by (kernel, device kind, shape).
"""

from hetu_tpu.ops.pallas.autotune import (autotune_flash_blocks,
                                          autotune_fused_ln_rows,
                                          autotune_lm_head_blocks,
                                          autotune_paged_decode,
                                          record_entry, tuned_blocks,
                                          tuned_entry)
from hetu_tpu.ops.pallas.flash import (flash_attention,
                                       flash_attention_bhsd, flash_attn_fn,
                                       flash_block_bwd, flash_block_fwd)
from hetu_tpu.ops.pallas.kda import chunk_kda
from hetu_tpu.ops.pallas.fused_ln import fused_residual_dropout_ln
from hetu_tpu.ops.pallas.lm_head import (lm_head_cross_entropy_pallas,
                                         lm_head_sample_pallas)
from hetu_tpu.ops.pallas.paged_decode import paged_decode_attention
from hetu_tpu.ops.pallas.paged_mla_decode import paged_mla_decode

__all__ = ["chunk_kda", "autotune_flash_blocks", "autotune_fused_ln_rows",
           "autotune_lm_head_blocks", "autotune_paged_decode",
           "flash_attention", "flash_attention_bhsd", "flash_attn_fn",
           "flash_block_fwd", "flash_block_bwd",
           "fused_residual_dropout_ln", "lm_head_cross_entropy_pallas",
           "lm_head_sample_pallas", "paged_decode_attention",
           "paged_mla_decode",
           "record_entry", "tuned_blocks", "tuned_entry"]

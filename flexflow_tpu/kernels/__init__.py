"""Pallas TPU kernels.

flash_attention — block-wise online-softmax attention (fwd + custom VJP),
the cuDNN-fused-attention replacement (reference src/ops/attention.cu:35);
the shape picks how its operands lie (`merged`, `two_heads`, `swapped`).
head_turn — a head's RMS norm and rotate-half turn on the merged axis `[b,
s, h * d]`, forward and backward: what sits between a projection and the
flash kernels' merged entry.
dequant_attention — fused int8-dequant + decode attention over the
quantized paged KV cache (serving --kv-cache-dtype int8).
ssd_scan — the Mamba-2 chunked scan with the mixer's skip, gate and norm on
the same tile (forward; ops/ssm_ops.py gives it the XLA form's gradient).
kda_scan — the chunked delta-rule scan of Kimi Delta Attention with the unit
vectors and the gated head norm on the same tile (forward; ops/kda_ops.py
gives it the XLA form's gradient).
retention_step — one decode step of power retention for the live slots: the
update of a slot's state and the float32 read-out of the new state on one
tile, the state read from HBM once and written once in place
(ops/power_retention_ops.py chooses it where a head is whole 128-lane slabs).
moe_step — one decode step's routed experts: each hit expert's two matrices
streamed once over all of the step's (at most 16) rows, the gated sum kept on
the chip (forward; ops/moe_ops.py chooses it from the block's shapes and
gives it the grouped product's gradient).
moe_rows — the routed experts of a wave's or a chunk's row buffer: rows
sorted by expert in tiles of 256, a tile that straddles groups visited once a
group under a row mask, both products and the activation in one pass with
`ab` and `mid` kept on the chip and an expert's matrices fetched once while
consecutive visits name it (forward; ops/moe_ops.py chooses it a rung from
the buffer's shapes and gives it the grouped product's gradient).
mamba2_step — one decode step of the Mamba-2 recurrence for the live slots:
the update of a slot's state and the float32 read-out of the new state on one
tile, the state read from HBM once and written once in place, a slot that is
not live never touched (ops/ssm_ops.py chooses it where a head's state is
whole f32 tiles and a B/C group's heads whole sublane tiles).
selective_scan — the Mamba-1 selective scan of a `[tokens, channels]` block
from a state: the recurrence stepped a position at a time with the `[N,
channels]` state in vector registers, `exp(dt A)` made on the tile, the skip
and the gate applied before the tile is written (forward; ops/mamba_ops.py
chooses it where the channels are whole 512-lane tiles and gives it the XLA
form's gradient).
sparse_attend_step — one decode step's attention over the keys an indexer
kept, for the live slots: a slot's pages under its position fetched by page
from the K and V pools where they lie, online softmax under the indexer's
mask (ops/attention_ops.py chooses it where a K/V head is whole 128-lane
slabs and a page whole tiles).
sparse_attend_chunk — a prefill chunk's attention over the keys an indexer
kept: dense under the indexer's mask over the slot's gathered pages, tiles of
queries against tiles of keys up to each query block's last position, the
scores and the step kernel's online softmax in VMEM (ops/attention_ops.py
chooses it from the same facts and the chunk's length).
The three per-slot step kernels' grids walk `partition.live_order`: the live
slots first, and their count.
"""

from flexflow_tpu.kernels.dequant_attention import (  # noqa: F401
    dequant_decode_attention,
)
from flexflow_tpu.kernels.flash_attention import (  # noqa: F401
    flash_attention,
    flash_attention_qkv,
)
from flexflow_tpu.kernels.kda_scan import (  # noqa: F401
    kda_chunk_scan,
    kda_chunk_scan_gated,
)
from flexflow_tpu.kernels.ssd_scan import (  # noqa: F401
    scan_tiles,
    ssd_chunk_scan,
    ssd_chunk_scan_gated,
)

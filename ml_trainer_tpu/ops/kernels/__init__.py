"""Pallas TPU kernels for the serving + training hot paths.

Every kernel in this package ships as a PAIR under one dispatcher:

* a **lax reference** — ordinary jnp/lax ops, bitwise-identical to the
  pre-kernel XLA path it replaces (that identity is pinned by
  ``tests/test_kernels.py``), shipped as the CPU/GPU runtime path;
* a **Pallas TPU kernel** — the fused program that removes the HBM
  round-trips the XLA path pays, pinned bit-for-bit against the lax
  reference in interpret mode on CPU (the repo's kernel discipline;
  ``decode_attention``, whose softmax runs online, by a stated tolerance
  as ``ops/attention.py``'s flash kernel is).

``implementation='auto'`` resolves to the Pallas kernel on TPU and the
lax reference everywhere else, so enabling a kernel knob never changes
bytes on a non-TPU backend — byte-identity gates stay exact while the
TPU path earns the fusion win.

Catalog (see docs/kernels.md for block layouts and measured numbers):

* ``paged_attention`` — paged-attention decode: fuses the per-step
  page-table gather (``pool[table]`` materializing [B, H, L, D] twice)
  into the attention kernel; pages stream HBM->VMEM via a
  scalar-prefetched table index_map.
* ``unscale_sqsum`` / ``fused_adam_update`` — the ``dp_update='sharded'``
  optimizer tail: one pass over the 1/N dim-0 shard for unscale +
  global-norm contribution, and one for clip + Adam moments + schedule
  step + param write (optax opt_state structure preserved bit-for-bit).
* ``int8_matmul`` / ``quantize_per_channel`` — int8 weight-quantized
  matmul with per-output-channel scales, backing the opt-in quantized
  decode path (``Server(quant_int8=True)``).
* ``decode_attention_append`` / ``decode_attention`` — the slot engine's
  decode step over its cache, every step and behind no knob: one query
  position a row against the blocks of that row that hold a live position,
  where XLA's masked attention reads all ``L`` positions of every row, and
  this step's K and V put into the row's last block on the way, its tile
  alone written back.  Online softmax, so pinned to its reference by
  tolerance, not bit for bit; the caches bit for bit.
* ``latent_decode_attention`` — a latent attention layer's decode step
  over its latent cache, every step and behind no knob: each row's live
  blocks fetched once and used as the keys and the values of one
  key-value head that every query head reads, where XLA reads the whole
  pool twice.  Online softmax, so pinned by tolerance.
* ``slot_cache_write`` / ``slot_row_write`` — the per-row append alone, for
  a cache that XLA reads whole (a window layer's ring) or that a read
  kernel does not write (a latent cache): one
  in-place grid over the rows where XLA runs the scatter as a sequential
  loop over them.
* ``retention_state_step`` — a power retention layer's pass over its state
  pool, every decode step and behind no knob: one read and one write in
  place, ``phi`` of the queries and the key made in VMEM, where XLA reads
  the pool twice and writes it once.  The sum over ``phi``'s entries runs
  lane by lane, so pinned by tolerance.
"""

from ml_trainer_tpu.ops.kernels.decode_attention import (  # noqa: F401
    attended_positions,
    decode_attention,
    decode_attention_append,
    decode_attention_append_reference,
    decode_attention_reference,
    grouped_decode_attention,
)

from ml_trainer_tpu.ops.kernels.latent_decode_attention import (  # noqa: F401
    latent_decode_attention,
    latent_decode_attention_reference,
)
from ml_trainer_tpu.ops.kernels.paged_attention import (  # noqa: F401
    paged_attention,
    paged_attention_reference,
)
from ml_trainer_tpu.ops.kernels.retention_state_step import (  # noqa: F401
    retention_state_step,
)
from ml_trainer_tpu.ops.kernels.slot_cache_write import (  # noqa: F401
    slot_cache_write,
    slot_cache_write_reference,
    slot_row_write,
)
from ml_trainer_tpu.ops.kernels.fused_adam import (  # noqa: F401
    adam_scalars,
    fused_adam_update,
    unscale_sqsum,
)
from ml_trainer_tpu.ops.kernels.int8_matmul import (  # noqa: F401
    int8_matmul,
    quantize_per_channel,
    quantize_tree,
)

__all__ = [
    "attended_positions",
    "decode_attention",
    "decode_attention_append",
    "decode_attention_append_reference",
    "decode_attention_reference",
    "grouped_decode_attention",
    "latent_decode_attention",
    "latent_decode_attention_reference",
    "paged_attention",
    "retention_state_step",
    "paged_attention_reference",
    "adam_scalars",
    "fused_adam_update",
    "unscale_sqsum",
    "int8_matmul",
    "quantize_per_channel",
    "quantize_tree",
    "slot_cache_write",
    "slot_cache_write_reference",
    "slot_row_write",
]

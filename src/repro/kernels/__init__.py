# Pallas TPU kernels for the paper's compute hot-spots (DESIGN.md §3):
#   sgmv          — multi-LoRA grouped matmul (rollout, paper §4.5)
#   gqa_decode    — flash-decode attention over contiguous KV caches
#   paged_decode  — flash-decode over the block-pool (paged) KV cache: the
#                   block table rides the scalar-prefetch channel so each
#                   logical page DMAs straight from its pooled location
#   token_logprob — fused LSE+gather+entropy over big vocabs (GRPO training)
# Each has ops.py wrappers and ref.py pure-jnp oracles; validated in
# interpret mode on CPU, compiled for a described TPU v5e by
# tests/test_tpu_compile.py, and checked against the oracles on the chip
# by chip_smoke.py.
from . import ops, ref

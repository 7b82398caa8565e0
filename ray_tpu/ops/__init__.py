"""TPU compute kernels (Pallas) with jnp references.

The hot ops of the transformer stack: fused attention (flash),
fused RMSNorm, rotary embeddings, weight-only int8 matmul, the
selective scan of a Mamba-1 mixer (ops/selective_scan.py, forward
only; imported as a module, its function bears the module's name). Each op
exposes a reference implementation used for tests/CPU and a Pallas
TPU kernel selected automatically on TPU backends."""

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.quant_matmul import int8_matmul, quantize_int8
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

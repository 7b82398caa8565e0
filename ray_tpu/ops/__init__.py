"""TPU compute kernels (Pallas) with jnp references.

The hot ops of the transformer stack: fused attention (flash),
fused RMSNorm, rotary embeddings, weight-only int8 matmul, the
selective scan of a Mamba-1 mixer (ops/selective_scan.py, forward only)
and a Mamba-2 mixer's decode update (ops/ssd_update.py), both imported
as modules whose function bears the module's name. Each op exposes a
jnp reference for tests/CPU and a Pallas kernel chosen on TPU backends."""

from ray_tpu.ops.attention import flash_attention
from ray_tpu.ops.quant_matmul import int8_matmul, quantize_int8
from ray_tpu.ops.rmsnorm import rms_norm
from ray_tpu.ops.rope import apply_rope, rope_frequencies

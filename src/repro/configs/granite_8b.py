"""Granite-8B-Code [arXiv:2405.04324] — llama-arch dense decoder for code.

36 layers, d_model=4096, 32 heads (GQA kv=8), d_ff=14336, vocab=49152.
long_500k runs as the swa-variant (8k window ring cache).

Presets:
  * ``FULL``  — the published model (granite-8b-code-base config.json).
  * ``CHIP``  — the share of the published model that one TPU v5e chip
    serves (below).
  * ``SMOKE`` — a CPU unit-test toy of the same family.
"""
from repro.configs.base import ArchConfig, MonitorConfig

FULL = ArchConfig(
    name="granite-8b", family="dense", citation="arXiv:2405.04324",
    n_layers=36, d_model=4096, n_heads=32, n_kv_heads=8, d_ff=14336,
    vocab_size=49152, rope_theta=1e4, tie_embeddings=True,
    long_context_window=8192,
    monitor=MonitorConfig(n_layers=2, d_model=256, n_heads=4, d_ff=1024,
                          n_features=64),
)

# One chip's share of a pipelined deployment.
#   source:     granite-8b-code-base (arXiv:2405.04324, HF config.json):
#               36 layers, d_model 4096, 32 query / 8 KV heads, head_dim
#               128, d_ff 14336, vocab 49152, tied embeddings.
#   deployment: the 36 layers as a pipeline of about seven v5e chips, one
#               stage of 5 layers per chip, each layer whole on its chip
#               (no tensor parallelism, so no width is divided).
#   cut:        depth only, 36 -> 5 layers: a dense model's period is one
#               layer, and 5 is the floor of one period plus four.  The
#               stage also holds the tied embedding/unembedding, which the
#               pipeline's first and last stages would hold.  Every width,
#               dtype and param_dtype is FULL's own.
#   sizing:     a layer is 218.1 M parameters (attention 41.9 M, SwiGLU
#               176.2 M), 0.87 GB at param_dtype float32; the tied
#               embedding is 0.81 GB.  The bf16 KV cache is 2 (K,V) x 8
#               heads x 128 x 2 B = 4 KiB per token per layer, so 0.27 GB
#               a layer at batch 32 x max_len 2048.  Compiled for a v5e
#               (memory_analysis, arguments + outputs + temporaries), the
#               catch-up replay takes 18.66 GB at 8 layers, 14.43 GB at 6
#               and 12.32 GB at 5; the offline scan 18.88 / 14.65 / 12.54
#               GB, plus the engine's own server cache.  Beyond weights and
#               cache, both programs hold a bf16 copy of every weight (the
#               f32 -> bf16 convert is hoisted out of the loop) and a
#               second and third cache buffer.  Only 5 layers leave room
#               under the chip's 16 GB for every phase of chip_smoke.py,
#               the wire phase's second engine included.
#   reduced:    n_layers (36 -> 5).
CHIP = FULL.replace(n_layers=5)

SMOKE = FULL.replace(
    n_layers=2, d_model=256, n_heads=4, n_kv_heads=2, d_ff=512,
    vocab_size=512, remat=False, dtype="float32",
    monitor=MonitorConfig(n_layers=1, d_model=64, n_heads=2, d_ff=128,
                          n_features=16),
)

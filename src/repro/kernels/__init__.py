"""Pallas TPU kernels for the compute hot spots (+ jnp oracles in ref.py).

flash_attention        prefill/train attention (MXU-tiled online softmax)
decode_attention       flash-decoding vs a KV cache (per-row lengths, GQA-native)
paged_decode_attention the same sweep through a per-row page table
ssd_scan               Mamba2 chunked state-space dual form (VMEM-carried state)
moe_gmm                grouped expert GEMM (per-expert MXU-tiled matmul)

Model code under jit uses the mathematically-identical jnp paths in
repro.models (XLA fuses those); the kernels are validated against ref.py in
interpret mode and compiled for v5e in tests/test_tpu_compile.py.
"""

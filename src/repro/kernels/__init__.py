"""Pallas TPU kernels for the compute hot-spots (validated in interpret mode
on CPU; selected on TPU by ops.py wrappers):

    stencil_bands    — pure-jnp oracles of the band stencils whose fused
                       VMEM-ring kernels `runtime.pallas_codegen` generates
    flash_attention  — blocked causal GQA attention (triangular block skip,
                       online softmax in VMEM scratch)
    gla_timemix      — chunkwise-parallel RWKV-6/GLA core: (hd×hd) fp32
                       state carried in VMEM across the sequential chunk
                       grid (the paper's t−1→t FIFO stream), MXU matmuls
                       in-chunk, overflow-safe pairwise decay form
"""

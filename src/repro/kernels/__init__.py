"""Pallas TPU kernels for the compute hot-spots our architectures hit:
flash attention (forward and backward; a transformer block's attention on
the TPU in training and prefill) and the RWKV6 chunked WKV scan.  Each
ships ``kernel.py`` (pl.pallas_call + BlockSpec VMEM tiling), ``ops.py``
(the wrapper callers use) and ``ref.py`` (pure-jnp oracle).  Each
compiles for the TPU by default; CPU callers pass ``interpret=True``, as
the parity tests do.

The paper itself has no kernel-level contribution (it is a scheduling
paper) — these kernels are where the per-stage FLOPs of its pipeline go.
"""

"""Pallas TPU kernels for the solver's compute hot-spots.

ell_spmv           — ELLPACK reduced-Laplacian matvec (PCG inner loop)
edge_reweight      — fused IRLS reweighting pass (eq. 4 → eq. 8)
block_diag_matmul  — block-Jacobi preconditioner apply (batched MXU GEMV)

Validated on CPU via interpret=True against ref.py jnp oracles, and
compiled for a described TPU v5e in tests/test_tpu_compile.py.
"""
from . import ops, ref

"""TPU kernels (pallas) for the hot ops.

The compute path is jax/XLA first — XLA already fuses the transformer
well — and pallas where a hand-written kernel beats the fusion:
flash attention (ops/flash_attention.py) keeps the O(S^2) score matrix
out of HBM entirely, which matters from mid-size sequence lengths up.
"""

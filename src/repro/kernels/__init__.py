"""Pallas TPU kernels for MARCA hot spots + pure-jnp oracles.

Kernels (each validated against ``ref.py`` on CPU, where they run under
the Pallas interpreter; compiled on TPU — see ``backend.py``):

  * ``selective_scan`` — fused selective-SSM scan (the paper's core).
  * ``fast_exp``       — biased Schraudolph exponential (EXP-RCU).
  * ``piecewise_silu`` — range-detect + quadratic SiLU (SiLU-RCU).
  * ``conv1d``         — causal depthwise conv (Mamba short conv).
  * ``flash_attention``— online-softmax GQA attention (prefill_32k).
"""
from repro.kernels import ops, ref  # noqa: F401

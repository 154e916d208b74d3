"""repro: TAMUNA (Condat et al., 2023) as a production-grade multi-pod JAX
training/serving framework.

Subpackages:
  core      the paper's algorithm + baselines + theory (convex reproduction)
  models    functional model zoo (dense/GQA, MoE, Mamba2, RWKV-6, enc-dec)
  configs   the 10 assigned architectures + input shapes + input_specs
  dist      sharding rules, TAMUNA-DP trainer, blocked uplink, model API
  kernels   Pallas TPU kernels (compress, local step, flash-decode attention)
  data      synthetic per-client pipeline
  optim     SGD / momentum / AdamW
  launch    mesh, multi-pod dry-run, train and serve drivers
"""

__version__ = "1.0.0"

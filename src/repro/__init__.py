"""repro — a TPU-native reproduction of "The BRAM is the Limit" (FCCM'24).

Layers:
  core/     Gold Standard models + IMAGine bit-serial PIM simulator
  kernels/  Pallas TPU bit-plane GEMV/GEMM kernels
  quant/    bit-plane quantization containers
  models/   pure-JAX model zoo (10 assigned architectures)
  dist/     sharding rules + collective reduction schedules
  optim/    optimizers + gradient compression
  data/     synthetic deterministic data pipeline
  train/    loss + train step + trainer loop
  serve/    KV-cache serving engine + batch scheduler
  ckpt/     fault-tolerant checkpointing
  configs/  architecture registry
  launch/   mesh / dryrun / train / serve entry points
"""

__version__ = "1.0.0"

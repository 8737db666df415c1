"""Plain float32 ``jax.numpy`` references, one module a configuration.

Written from the published descriptions (arXiv:1806.10293 appendix for
Grasping44; arXiv:1603.05027 and arXiv:1811.06964 for Grasp2Vec's
ResNet-50 v2 towers and N-pairs loss). Nothing here imports
``tensor2robot_tpu``; only the names of the program's parameter tree are
known, so that weights made here can be handed to it.
"""

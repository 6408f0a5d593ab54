"""Pallas TPU kernels for the perf-critical compute layers.

put_copy / reduce_combine mirror the paper's hand-tuned copy loop and
reduction combine; flash_attention / ssd_scan are the model zoo's hot
spots; paged_decode is the serving engine's decode attention over its
paged KV pool.  ops.py holds the jit'd public wrappers, ref.py the pure-jnp
oracles used by the allclose tests.
"""
from . import ops, ref

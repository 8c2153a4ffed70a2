"""Pallas TPU kernels for the partitioner + DLRM hot spots.

connectivity.py — hyperedge connectivity / cut via block bitmask + popcount
gain.py         — FM move-gain assembly (fused gather-reduce over dual CSR)
embedding_bag.py— DLRM EmbeddingBag (scalar-prefetch dynamic row gather)
ops.py          — jit'd wrappers + host layout converters
ref.py          — pure-jnp oracles (test ground truth)
"""
from . import ops, ref
from .connectivity import connectivity_pallas, cutsize_pallas
from .gain import gain_stream_pallas, gain_stream_batch_pallas
from .embedding_bag import embedding_bag_pallas

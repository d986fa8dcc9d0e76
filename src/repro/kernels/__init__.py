# OPTIONAL layer. Add <name>.py (or .cu) + ops.py + ref.py ONLY
# for compute hot-spots the paper itself optimizes with a custom
# kernel. Leave this package empty if the paper has none.
"""Pallas TPU kernels (flash attention, Mamba-2 SSD) behind ``use_pallas``.

``ops`` holds the jit'd wrappers the models call, ``ref`` the pure-jnp
oracles the kernel tests compare against.
"""

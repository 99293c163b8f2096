"""Pallas TPU kernels and their pure-jnp oracles (``ref.py``).

Every kernel takes ``interpret`` explicitly: ``False`` (the default)
compiles it with Mosaic for the TPU; CPU tests pass ``interpret=True``.
"""

"""Pallas TPU kernels and their pure-jnp oracles (``ref``)."""

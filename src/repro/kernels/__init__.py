"""Pallas TPU kernels with their pure-jnp references: attention, SSD chunk scan, Jacobi stencil."""

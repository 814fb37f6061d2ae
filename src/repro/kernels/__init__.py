"""Pallas TPU kernels (``kan_fused``, ``cim_mac``, ``ssd_scan``), their
jitted padding wrappers (``ops``) and pure-jnp oracles (``ref``)."""

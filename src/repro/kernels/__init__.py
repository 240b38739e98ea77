"""Pallas kernels of the checkpoint path: ``delta_encode.delta_mask``
(the changed-block scan) and its jnp oracle in ``ref.py``."""

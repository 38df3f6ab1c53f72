"""The benchmark of lodestar-tpu's served BLS verification path on a TPU.

Imports nothing at load time: worker processes import the traffic
generators under it and must stay free of JAX.
"""

# Pallas TPU kernels, each beside its pure-jnp oracle in ref.py. Every
# kernel takes ``interpret`` as a required keyword: CPU callers pass
# True (the Pallas interpreter), chip callers False.

# Pallas kernels, one <name>.py each, with their plain-jax references in
# ref.py. Callers import the kernel modules directly;
# `core.engine.default_interpret` says when to run them in interpret mode.

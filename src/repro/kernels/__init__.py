# Pallas kernels: <name>.py holds a kernel and its public entry, ref.py
# the pure-jnp oracle each is tested against.  Each entry decides its own
# tiles and interpretation (compiled on TPU, interpreted elsewhere).

# Pallas TPU kernels for the serving hot spots: prefill flash attention and
# cached decode attention. Each kernel ships with ops.py (jit'd wrapper,
# compiled on TPU and interpreted elsewhere via dispatch.py) and ref.py
# (pure-jnp oracle used by the tests).

"""`jax.monitoring` compile events less persistent-cache hits inside the
window. Should read 0."""


def read(facts):
    return facts["run"]["xla_compiles_in_window"]

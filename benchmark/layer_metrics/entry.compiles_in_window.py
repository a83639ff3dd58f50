"""Programs JAX compiled, or fetched from its persistent cache, between the
window's two stamps (the benchmark's listener on jax.monitoring). Should be
0: whatever compiles belongs to set-up."""


def read(run):
    return float(run["compiles_in_window"])

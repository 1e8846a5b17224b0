"""GAP "urand": both endpoints of every edge drawn uniformly, an
Erdős–Rényi graph of the same degree as kron."""


def generate(spec, rng):
    n, m = 1 << spec["scale"], spec["edgefactor"] << spec["scale"]
    return rng.integers(0, n, m), rng.integers(0, n, m)

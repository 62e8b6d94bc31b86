"""Programs compiled inside the window (JAX monitoring events); expect 0."""


def read(result, cell, peaks):
    return float(result["compile"]["programs"])

"""ABFT-LA: Algorithm-Based Fault Tolerance for JAX at pod scale.

Reproduction + extension of Bosilca, Delmas, Dongarra, Langou (2008),
"Algorithmic Based Fault Tolerance Applied to High Performance Computing".
"""
__version__ = "1.0.0"

"""repro: PANIGRAHAM-JAX — consistent non-blocking dynamic-graph operations
(Chatterjee, Peri, Sa — CS.DC 2020) rebuilt as a multi-pod JAX graph
service.
"""

__version__ = "0.1.0"

from .rmat import rmat_edges, load_rmat_graph  # noqa: F401

"""``minplus_sweep`` with a float32 weight read per edge-direction: the
shape of an SSSP iteration, for the harness's reader, which gives a
roofline module no more than the sizes and the iterations."""

import seams

_sweep = seams.load_module(None, "rooflines", "minplus_sweep")


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict) -> dict:
    return _sweep.least_seconds(n_nodes, n_edges, iterations, peak,
                                weighted=True)

"""Bytes one iteration of an undirected min-semiring sweep must move,
whatever implements it: from the relationship and node counts alone.

An iteration relaxes both orientations of each of E relationships: per
edge-direction it reads the two int32 endpoints and gathers the source's
int32 or float32 value (12 B), and reads a float32 weight where the
sweep has one (4 B more: SSSP's, not BFS's or WCC's); per node it reads
the iterate and writes it once (8 N). That is the least traffic: an
implementation that writes the gathered values out and reads them back
for the scatter, keeps padding edges, or scans the edges twice counts
against its share. A sweep does one add or compare and one min per
edge-direction, far below any chip's arithmetic, so HBM bounds it.

E is the graph's relationships, one per undirected pair, as loaded plus
the window's writes; N its vertices.
"""

#: bytes per edge-direction: two int32 endpoints, one gathered value
EDGE_BYTES = 12
#: a float32 weight read with each edge-direction
WEIGHT_BYTES = 4


def per_iteration(n_nodes: int, n_edges: int, weighted: bool = False) -> dict:
    per_edge = EDGE_BYTES + (WEIGHT_BYTES if weighted else 0)
    return {"bytes": 2 * n_edges * per_edge + 8 * n_nodes,
            "operations": 4 * n_edges + n_nodes}


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict, weighted: bool = False) -> dict:
    work = per_iteration(n_nodes, n_edges, weighted)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["operations"] / peak["flops_per_s_bf16"]
    return {"seconds": iterations * max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}

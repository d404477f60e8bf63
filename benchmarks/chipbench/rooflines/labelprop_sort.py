"""Bytes one synchronous label-propagation round must move when it
elects by sorting, whatever implements the sort: from the relationship
and node counts alone.

A round over both orientations of each of E relationships gathers each
edge-direction's source label (the int32 source index read, the label
gathered and written beside its destination: 12 B), then sorts the
(destination, label, weight) triples, reading and writing each once at
the least (24 B), and reads and writes every node's label once (8 N).
The run-length and segment reductions that follow read the sorted keys
again and are the implementation's cost, as are a sort's extra passes.
Comparisons are far below any chip's arithmetic, so HBM bounds it.

E is the graph's relationships, one per undirected pair, as loaded plus
the window's writes; N its vertices.
"""

#: bytes per edge-direction: the gather (12) and one sort pass (24)
EDGE_BYTES = 12 + 24


def per_round(n_nodes: int, n_edges: int) -> dict:
    return {"bytes": 2 * n_edges * EDGE_BYTES + 8 * n_nodes,
            "operations": 2 * n_edges + n_nodes}


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict) -> dict:
    work = per_round(n_nodes, n_edges)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["operations"] / peak["flops_per_s_bf16"]
    return {"seconds": iterations * max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}

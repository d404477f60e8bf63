"""Operations and bytes one PageRank iteration must move, whatever
implements it: from the edge and node counts alone.

One pass reads each edge's two int32 endpoints, reads the rank and the
inverse out-degree of every node, and writes every node's new rank, all
float32: 8 E + 12 N bytes. It multiplies and adds once per edge and
applies the damping epilogue per node: 2 E + 4 N operations. On any
chip this is bound by memory bandwidth, not arithmetic. What an
implementation moves beyond that (routing masks, padding, a second
pass) counts against its share of the roofline.
"""


def per_iteration(n_nodes: int, n_edges: int) -> dict:
    return {"bytes": 8 * n_edges + 12 * n_nodes,
            "operations": 2 * n_edges + 4 * n_nodes}


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict) -> dict:
    work = per_iteration(n_nodes, n_edges)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["operations"] / peak["flops_per_s_bf16"]
    return {"seconds": iterations * max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}

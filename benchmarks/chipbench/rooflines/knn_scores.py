"""Operations and bytes one exact nearest-neighbour search must move,
whatever implements it: from the row count and the width alone.

One search of one query against n stored vectors of ``WIDTH`` float32
components reads the matrix once (4 * WIDTH * n bytes, at the
deployment's stated float32), reads each row's validity (4 n) and
writes each row's score once (4 n); the query and the k results are
noise beside that. It multiplies and adds once per component:
2 * WIDTH * n operations. With one query row this is bound by memory
bandwidth on any chip. What an implementation moves beyond that
(normalising the matrix again for every request, a cast copy, masked
rows of a matrix grown past n) counts against its share of the
roofline; one that stores the matrix in a narrower type moves less than
this count and may read above 100 % — that is then its claim to state,
not this file's to follow.

n is the loaded node count (every loaded node holds an embedding): the
few rows a window inserts are left out, on the low side.
"""

#: the deployment's embedding width (configs/graphrag_medium_inproc.json
#: ``embedding_width``; a test holds the two equal)
WIDTH = 384


def per_search(n_rows: int) -> dict:
    return {"bytes": 4 * WIDTH * n_rows + 8 * n_rows,
            "operations": 2 * WIDTH * n_rows}


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict) -> dict:
    """`iterations` searches over `n_nodes` rows; the edge count plays
    no part."""
    work = per_search(n_nodes)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["operations"] / peak["flops_per_s_bf16"]
    return {"seconds": iterations * max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}

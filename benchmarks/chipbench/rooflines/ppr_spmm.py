"""Operations and bytes one iteration of a batched personalized-PageRank
fixpoint must move, whatever implements it: from the edge count, the
node count and the number of lanes alone.

One iteration over L lanes (L restart vectors iterated side by side,
one SpMM) reads each edge's two int32 endpoints and its float32
multiplier once, whatever L is (12 E bytes: the edge stream is what a
batch shares), and per lane reads the iterate, reads the restart vector
and writes the new iterate, all float32 (12 N L bytes). It multiplies
and adds once per edge and lane (2 E L operations) and applies the
damping and dangling epilogue and the convergence sum per node and lane
(6 N L). On any chip this is bound by memory bandwidth.

L is the lanes the program ran, the bucket its batch was padded to, not
the riders in it: padding is the implementation's cost, as are routing
masks, the (E, L) gather and product it materialises, and a second pass.

``least_seconds`` has the signature the harness's reader calls, which
has no lane count to give: the reader counts iterations by an op that
every bucket's program names alike. So it counts ``LANES`` = 1, the
fewest lanes an iteration can have run: the share it yields is a floor
of the kernel's share, never above it (at 16 lanes the least bytes are
1.8 times these at the medium graph's sizes). ``per_iteration`` takes
the lanes, for a reading that knows them (PERF.md section 5 gives the
share by bucket, from a bare client beside the cell).
"""

#: the lanes ``least_seconds`` counts where it is not told
LANES = 1


def per_iteration(n_nodes: int, n_edges: int, lanes: int) -> dict:
    return {"bytes": 12 * n_edges + 12 * n_nodes * lanes,
            "operations": 2 * n_edges * lanes + 6 * n_nodes * lanes}


def least_seconds(n_nodes: int, n_edges: int, iterations: float,
                  peak: dict, lanes: int = LANES) -> dict:
    work = per_iteration(n_nodes, n_edges, lanes)
    by_bytes = work["bytes"] / peak["hbm_bytes_per_s"]
    by_ops = work["operations"] / peak["flops_per_s_bf16"]
    return {"seconds": iterations * max(by_bytes, by_ops),
            "bound": "hbm" if by_bytes >= by_ops else "flops"}

"""GraphRAG hybrid retrieval: vector kNN → k-hop expand → PageRank rerank.

The BASELINE.md config #5 pipeline (reference pieces:
query_modules/vector_search_module.cpp + hops expansion + pagerank rerank,
with mage/python/llm_util formatting the retrieved context). Every stage
runs on device: MXU matmul kNN seeds, Bellman-Ford k-hop frontier, and
personalized PageRank restarted on the seed set — one pipeline, no
host round-trips between stages beyond index bookkeeping.
"""

from __future__ import annotations

import numpy as np

from . import mgp


@mgp.read_proc("graphrag.retrieve",
               args=[("property", "STRING"), ("query_vector", "LIST"),
                     ("k_seeds", "INTEGER")],
               opt_args=[("hops", "INTEGER", 2),
                         ("limit", "INTEGER", 10),
                         ("damping", "FLOAT", 0.85),
                         ("metric", "STRING", "cosine")],
               results=[("node", "NODE"), ("score", "FLOAT"),
                        ("seed_similarity", "FLOAT")])
def retrieve(ctx, property, query_vector, k_seeds, hops=2, limit=10,
             damping=0.85, metric="cosine"):
    """Hybrid retrieval over the current graph snapshot."""
    from ..observability import trace as mgtrace
    from ..ops.pagerank import personalized_pagerank
    from ..ops.traversal import khop_neighborhood
    from .vector_search import _get_index, _search_entry

    entry = _get_index(ctx, str(property))
    if entry.matrix is None:
        return
    graph = ctx.device_graph()
    if graph.n_nodes == 0:
        return

    # 1) seed selection: vector kNN over the embedding index (MXU,
    #    delta-maintained — streaming GraphRAG never full-rebuilds)
    sims, idx = _search_entry(entry, [query_vector], int(k_seeds),
                              str(metric))
    if sims is None:
        return
    seed_sim: dict[int, float] = {}
    seed_indices = []
    for sim, i in zip(sims[0], idx[0]):
        gid = entry.row_gids[int(i)]
        if gid is None:
            continue
        di = graph.gid_to_idx.get(gid)
        if di is not None:
            seed_indices.append(di)
            seed_sim[di] = float(sim)
    if not seed_indices:
        return

    # 2+3) expansion + rerank. With a resident kernel server configured
    # the whole tail is ONE coalesced round trip: the seeds restart a
    # personalized-PageRank fixpoint batched with every concurrent
    # retrieve/search on the daemon, the server extracts the top-k on
    # device, and repeats ride its change-log-invalidated result cache.
    # PPR mass localizes around the restart set, so the top-k IS the
    # neighborhood expansion + rerank in one step.
    from .graph_algorithms import _kernel_server_ppr
    served = _kernel_server_ppr(ctx, graph, seed_indices, float(damping),
                                100, 1e-6, top_k=int(limit))
    if served is not None:
        _h, out = served
        for score, i in zip(out["topk_val"], out["topk_idx"]):
            if score <= 0:
                break
            node = ctx.vertex_by_index(graph, int(i))
            if node is not None:
                yield {"node": node, "score": float(score),
                       "seed_similarity": seed_sim.get(int(i), 0.0)}
        return

    # in-process: k-hop neighborhood mask (device frontier), then
    # personalized PageRank restarted on the seeds; each phase ends in
    # the readback that waits for its program
    with mgtrace.span("graphrag.expand"):
        mask = khop_neighborhood(graph, seed_indices, int(hops),
                                 directed=False)
    with mgtrace.span("graphrag.ppr"):
        ranks, _, _ = personalized_pagerank(graph, seed_indices,
                                            damping=float(damping),
                                            max_iterations=100)
    with mgtrace.span("graphrag.rows"):
        scores = np.where(mask, ranks, 0.0)
        rows = []
        for i in np.argsort(-scores)[:int(limit)]:
            if scores[i] <= 0:
                break
            node = ctx.vertex_by_index(graph, int(i))
            if node is not None:
                rows.append({"node": node, "score": float(scores[i]),
                             "seed_similarity": seed_sim.get(int(i), 0.0)})
    yield from rows


@mgp.read_proc("graphrag.context",
               args=[("nodes", "LIST")],
               opt_args=[("include_edges", "BOOLEAN", True)],
               results=[("context", "STRING")])
def context(ctx, nodes, include_edges=True):
    """Format retrieved nodes (+ interconnecting edges) as LLM context —
    the llm_util analog (reference: mage/python/llm_util.py)."""
    storage = ctx.storage
    lm, pm, tm = (storage.label_mapper, storage.property_mapper,
                  storage.edge_type_mapper)
    lines = []
    gid_set = {n.gid for n in nodes if n is not None}
    for n in nodes:
        if n is None:
            continue
        labels = ":".join(lm.id_to_name(l) for l in n.labels(ctx.view))
        props = ", ".join(
            f"{pm.id_to_name(k)}: {v!r}"
            for k, v in sorted(n.properties(ctx.view).items())
            if not isinstance(v, list) or len(v) <= 8)
        lines.append(f"({labels} {{{props}}})")
        if include_edges:
            for ea in n.out_edges(ctx.view):
                if ea.to_vertex().gid in gid_set:
                    lines.append(
                        f"  -[{tm.id_to_name(ea.edge_type)}]-> "
                        f"node:{ea.to_vertex().gid}")
    yield {"context": "\n".join(lines)}


@mgp.read_proc("graphrag.schema",
               results=[("schema", "STRING")])
def schema(ctx):
    """Graph schema summary for Text2Cypher prompts (reference:
    SHOW SCHEMA INFO / llm_util schema formatting)."""
    storage = ctx.storage
    label_counts: dict[int, int] = {}
    edge_patterns: dict[tuple, int] = {}
    label_props: dict[int, set] = {}
    for va in ctx.accessor.vertices(ctx.view):
        for l in va.labels(ctx.view):
            label_counts[l] = label_counts.get(l, 0) + 1
            label_props.setdefault(l, set()).update(
                va.properties(ctx.view).keys())
    for ea in ctx.accessor.edges(ctx.view):
        src_labels = tuple(sorted(ea.from_vertex().labels(ctx.view)))
        dst_labels = tuple(sorted(ea.to_vertex().labels(ctx.view)))
        key = (src_labels, ea.edge_type, dst_labels)
        edge_patterns[key] = edge_patterns.get(key, 0) + 1
    lm, pm, tm = (storage.label_mapper, storage.property_mapper,
                  storage.edge_type_mapper)
    lines = ["Node labels:"]
    for l, count in sorted(label_counts.items()):
        props = ", ".join(sorted(pm.id_to_name(p)
                                 for p in label_props.get(l, ())))
        lines.append(f"  :{lm.id_to_name(l)} ({count} nodes) "
                     f"properties: [{props}]")
    lines.append("Relationships:")
    for (src, t, dst), count in sorted(edge_patterns.items(),
                                       key=lambda kv: -kv[1]):
        src_s = ":".join(lm.id_to_name(l) for l in src) or "?"
        dst_s = ":".join(lm.id_to_name(l) for l in dst) or "?"
        lines.append(f"  (:{src_s})-[:{tm.id_to_name(t)}]->(:{dst_s}) "
                     f"x{count}")
    yield {"schema": "\n".join(lines)}

"""Graph embeddings end-to-end (the reference's deeplearning4j-graph +
nearestneighbors workflow): build a graph, learn DeepWalk vertex
embeddings (skip-gram + degree-keyed Huffman hierarchical softmax over
vectorised random walks), recover the communities with k-means, and
serve nearest-vertex queries over REST.

Reference classes: graph/models/deepwalk/DeepWalk,
clustering/kmeans/KMeansClustering, NearestNeighborsServer.
Synthetic stochastic-block graph (zero-egress environment).

Run: python examples/deepwalk_communities.py [--communities 4]
"""
from __future__ import annotations

import argparse
import json
import urllib.request

import numpy as np

from deeplearning4j_tpu.clustering import (
    KMeansClustering, NearestNeighborsServer)
from deeplearning4j_tpu.graph import DeepWalk, Graph


def stochastic_block_graph(communities: int, size: int, rng,
                           p_in: float = 0.4,
                           p_out: float = 0.01) -> Graph:
    n = communities * size
    g = Graph(n)
    for i in range(n):
        for j in range(i + 1, n):
            p = p_in if i // size == j // size else p_out
            if rng.random() < p:
                g.addEdge(i, j)
    return g


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--communities", type=int, default=4)
    ap.add_argument("--size", type=int, default=20)
    args = ap.parse_args()

    rng = np.random.default_rng(0)
    g = stochastic_block_graph(args.communities, args.size, rng)
    n = g.numVertices()
    print(f"graph: {n} vertices, {g.numEdges()} edges, "
          f"{args.communities} planted communities")

    dw = (DeepWalk.Builder().vectorSize(64).windowSize(4)
          .learningRate(0.15).seed(7).batchSize(1024).build())
    dw.fit(g, walk_length=30, walks_per_vertex=10, epochs=5)
    emb = dw.getVectorMatrix()

    # k-means over the embeddings recovers the planted partition
    cs = KMeansClustering.setup(args.communities, max_iterations=50,
                                seed=1).applyTo(emb)
    truth = np.arange(n) // args.size
    agree = 0
    for cl in cs.getClusters():
        ids = [p.id for p in cl.getPoints()]
        if ids:
            agree += np.bincount(truth[ids]).max()
    purity = agree / n
    print(f"k-means purity over embeddings: {purity:.3f}")
    assert purity > 0.9, "communities not recovered"

    # nearest-vertex serving
    srv = NearestNeighborsServer(emb, default_k=6)
    port = srv.start()
    try:
        q = 3   # a vertex in community 0
        body = json.dumps({"point": emb[q].tolist()}).encode()
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/v1/serving/predict", data=body,
            headers={"Content-Type": "application/json"})
        idx, _ = json.loads(
            urllib.request.urlopen(req, timeout=10).read())["output"]
        neighbours = [v for v in idx if v != q]   # drop the self-match
        same = sum(1 for v in neighbours if truth[v] == truth[q])
        print(f"k-NN server: {same}/{len(neighbours)} of vertex {q}'s "
              "neighbours share its community")
        assert same >= len(neighbours) - 1
    finally:
        srv.stop()
    print("OK")


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()

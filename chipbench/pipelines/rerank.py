"""The retrieve pipeline plus a second route: rest_connector ->
query_as_of_now (k candidates, one row a match) -> CrossEncoderReranker over
every (query, document) pair -> the ``top`` best by score."""

import pathway_tpu as pw

from chipbench.pipelines._store import free_port, store_server


def build(ctx) -> dict:
    k, top = ctx.config["rerank_candidates"], ctx.config["rerank_top"]
    store, routes = store_server(ctx)
    port = free_port()
    queries, respond = pw.io.http.rest_connector(
        host="127.0.0.1", port=port, schema=pw.schema_from_types(query=str)
    )
    hits = store.index.query_as_of_now(
        queries.query, number_of_matches=k, collapse_rows=False
    ).select(qid=pw.right["__qid"], q=pw.left.query, text=pw.right.text)
    # the reranker as a top-level column, so the batched UDF rides the microbatcher
    scored = hits.select(hits.qid, hits.text, score=ctx.reranker(hits.text, hits.q))
    ranked = scored.groupby(scored.qid, id=scored.qid).reduce(
        n=pw.reducers.count(),
        texts=pw.reducers.tuple(scored.text),
        scores=pw.reducers.tuple(scored.score),
    )

    def pack(texts, scores):
        order = sorted(range(len(scores)), key=lambda i: -scores[i])[:top]
        return pw.Json([{"text": texts[i], "score": float(scores[i])} for i in order])

    # a query is answered once, when all of its k pairs are scored
    respond(ranked.filter(ranked.n == k).select(result=pw.apply(pack, ranked.texts, ranked.scores)))
    routes["rerank"] = [port, "/"]
    return routes

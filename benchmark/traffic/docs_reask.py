"""Closed-loop question answering over long documents (``kind:
docs_reask``): each client works through documents, and asks each one
``asks_per_doc`` times in a row: the whole document + a question. The first
ask finds nothing in the prefix cache; the others find the document's pages
there and prefill the question alone.

As ``generate.closed_loop_requests``: the lengths and their deal come from
the mix's own ``deal_seed`` (the same schedule under every ``--seed``),
``--seed`` draws the token ids. Document lengths are the mid-quantiles of a
log-normal, clipped and rounded to ``doc_len.grid`` (a whole number of KV
pages); question and answer lengths likewise, without a grid. Client ``c``
starts ``c % asks_per_doc`` asks into its first document, so the clients'
phases are spread over the cycle from the first tick on.
"""
from __future__ import annotations

import numpy as np

from benchmark.traffic.generate import lognormal_grid


def _lengths(spec, n):
    out = lognormal_grid(n, spec["median"], spec["sigma"], spec["min"],
                         spec["max"])
    grid = spec.get("grid", 1)
    return [int(min(max(round(x / grid) * grid, spec["min"]), spec["max"]))
            for x in out]


def docs_reask_requests(traffic, seed, vocab):
    """-> (plan, asks): ``plan[c]`` is client ``c``'s list of (prompt ids,
    new tokens), ``asks[c]`` the ask number (0 = the document's first) of
    each."""
    if traffic["kind"] != "docs_reask":
        raise ValueError(f"not docs_reask traffic: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 2])
    deal = np.random.default_rng([int(traffic["deal_seed"]), 4])
    clients, docs = traffic["clients"], traffic["docs_per_client"]
    per_doc = traffic["asks_per_doc"]
    n_docs, n_asks = clients * docs, clients * docs * per_doc
    doc_len = np.asarray(_lengths(traffic["doc_len"], n_docs))[
        deal.permutation(n_docs)].reshape(clients, docs)
    q_len = np.asarray(_lengths(traffic["question_len"], n_asks))[
        deal.permutation(n_asks)].reshape(clients, docs, per_doc)
    a_len = np.asarray(_lengths(traffic["answer_len"], n_asks))[
        deal.permutation(n_asks)].reshape(clients, docs, per_doc)
    plan, asks = [], []
    for c in range(clients):
        reqs, nums = [], []
        for d in range(docs):
            doc = rng.integers(1, vocab, int(doc_len[c, d]))
            for a in range(per_doc):
                question = rng.integers(1, vocab, int(q_len[c, d, a]))
                if d == 0 and a < c % per_doc:
                    continue              # this client starts mid-cycle
                reqs.append((np.concatenate([doc, question]),
                             int(a_len[c, d, a])))
                # a client that starts mid-cycle sends its first document
                # whole on its first request, whatever the ask's number
                nums.append(a if nums else 0)
        plan.append(reqs)
        asks.append(nums)
    return plan, asks

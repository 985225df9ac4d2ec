"""Short chat turns and long documents in one closed loop (``kind:
mixed_len``): the first ``long.clients`` clients work through documents and
ask each ``asks_per_doc`` times in a row (the whole document + a question:
the first ask is cold, the others find the document in the prefix cache),
the other ``short.clients`` send unshared chat turns. One queue for all.

As ``docs_reask.py``: lengths are the mid-quantiles of log-normals, clipped
(documents rounded to ``doc_len.grid``, a whole number of KV pages), and
their deal comes from the mix's own ``deal_seed``, the same schedule under
every ``--seed``, which draws the token ids. Long client ``c`` starts ``c %
asks_per_doc`` asks into its first document.
"""
from __future__ import annotations

import numpy as np

from benchmark.traffic.docs_reask import _lengths


def mixed_len_requests(traffic, seed, vocab):
    """-> (plan, asks): ``plan[c]`` is client ``c``'s list of (prompt ids,
    new tokens); ``asks[c]`` says of each which ask of its document it is
    (0 = the first) for a long client and -1 for a short one."""
    if traffic["kind"] != "mixed_len":
        raise ValueError(f"not mixed_len traffic: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 2])
    deal = np.random.default_rng([int(traffic["deal_seed"]), 4])
    lo, sh = traffic["long"], traffic["short"]
    per_doc, docs = lo["asks_per_doc"], lo["docs_per_client"]
    n_docs, n_asks = lo["clients"] * docs, lo["clients"] * docs * per_doc
    doc_len = np.asarray(_lengths(lo["doc_len"], n_docs))[
        deal.permutation(n_docs)].reshape(lo["clients"], docs)
    q_len = np.asarray(_lengths(lo["question_len"], n_asks))[
        deal.permutation(n_asks)].reshape(lo["clients"], docs, per_doc)
    a_len = np.asarray(_lengths(lo["answer_len"], n_asks))[
        deal.permutation(n_asks)].reshape(lo["clients"], docs, per_doc)
    plan, asks = [], []
    for c in range(lo["clients"]):
        reqs, nums = [], []
        for d in range(docs):
            doc = rng.integers(1, vocab, int(doc_len[c, d]))
            for a in range(per_doc):
                question = rng.integers(1, vocab, int(q_len[c, d, a]))
                if d == 0 and a < c % per_doc:
                    continue              # this client starts mid-cycle
                reqs.append((np.concatenate([doc, question]),
                             int(a_len[c, d, a])))
                # whatever its number, a client's first request finds
                # nothing cached
                nums.append(a if nums else 0)
        plan.append(reqs)
        asks.append(nums)
    n_turns = sh["clients"] * sh["requests_per_client"]
    p_len = np.asarray(_lengths(sh["prompt_len"], n_turns))[
        deal.permutation(n_turns)].reshape(sh["clients"], -1)
    o_len = np.asarray(_lengths(sh["answer_len"], n_turns))[
        deal.permutation(n_turns)].reshape(sh["clients"], -1)
    for c in range(sh["clients"]):
        plan.append([(rng.integers(1, vocab, int(p)), int(o))
                     for p, o in zip(p_len[c], o_len[c])])
        asks.append([-1] * sh["requests_per_client"])
    return plan, asks

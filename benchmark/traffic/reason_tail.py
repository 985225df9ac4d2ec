"""Reasoning requests with a tail of very long documents in one closed loop
(``kind: reason_tail``): the first ``long.clients`` clients send unshared
documents, each once (a document + a question, a short answer), the other
``short.clients`` send unshared reasoning requests (a prompt of about a
thousand tokens, an answer of thousands). One queue for all.

As ``docs_reask.py``: lengths are the mid-quantiles of log-normals, clipped
(documents rounded to ``doc_len.grid``), and their deal comes from the mix's
own ``deal_seed``: the same schedule under every ``--seed``, which draws the
token ids. ``ramp``: client ``c``'s FIRST answer is cut to ``(c %
ramp.phases + 1) / ramp.phases`` of its length, so that the slots' ages are
spread when the window opens.
"""
from __future__ import annotations

import numpy as np

from benchmark.traffic.docs_reask import _lengths


def _dealt(spec, n, deal, shape):
    return np.asarray(_lengths(spec, n))[deal.permutation(n)].reshape(shape)


def reason_tail_requests(traffic, seed, vocab):
    """-> (plan, kinds): ``plan[c]`` is client ``c``'s list of (prompt ids,
    new tokens); ``kinds[c]`` is ``"long"`` or ``"short"``."""
    if traffic["kind"] != "reason_tail":
        raise ValueError(f"not reason_tail traffic: {traffic['kind']!r}")
    rng = np.random.default_rng([int(seed), 2])
    deal = np.random.default_rng([int(traffic["deal_seed"]), 4])
    lo, sh = traffic["long"], traffic["short"]
    n_docs = lo["clients"] * lo["docs_per_client"]
    shape = (lo["clients"], lo["docs_per_client"])
    doc_len = _dealt(lo["doc_len"], n_docs, deal, shape)
    q_len = _dealt(lo["question_len"], n_docs, deal, shape)
    a_len = _dealt(lo["answer_len"], n_docs, deal, shape)
    plan = [[(rng.integers(1, vocab, int(d) + int(q)), int(a))
             for d, q, a in zip(doc_len[c], q_len[c], a_len[c])]
            for c in range(lo["clients"])]
    n_turns = sh["clients"] * sh["requests_per_client"]
    shape = (sh["clients"], sh["requests_per_client"])
    p_len = _dealt(sh["prompt_len"], n_turns, deal, shape)
    o_len = _dealt(sh["answer_len"], n_turns, deal, shape)
    plan += [[(rng.integers(1, vocab, int(p)), int(o))
              for p, o in zip(p_len[c], o_len[c])]
             for c in range(sh["clients"])]
    phases = (traffic.get("ramp") or {}).get("phases")
    if phases:
        for c, reqs in enumerate(plan):
            prompt, new = reqs[0]
            reqs[0] = (prompt, max(new * (c % phases + 1) // phases, 1))
    return plan, ["long"] * lo["clients"] + ["short"] * sh["clients"]

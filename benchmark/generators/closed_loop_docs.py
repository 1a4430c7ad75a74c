"""Closed loop over documents: ``clients`` workers, each sending its next
request when the last is answered.  A worker holds one document and asks
questions about it: each request is the document followed by a fresh
question.  Every ``questions_per_document``-th request the worker takes up a
new document, which replaces its old one in the working set; worker i starts
i requests into that cycle, so the new documents do not all come at once.
The working set is ``clients`` documents, each asked about
``questions_per_document`` times.

Set-up sends each worker's first document once (one token of answer), which
the traffic needs: the measured window opens on a working set that is
resident, as a running pipeline's is.

Every seed gets the same multiset of document, question and answer lengths
(uniform quantiles) and token ids of its own.  The ORDER of the lengths is
the seed's own too, unless the mix states ``order_seed``: then that number
draws the order, for every seed alike, and a run's seed draws only the token
ids (and, in the runner, the weights).  A closed loop's schedule follows from
the lengths alone, and the engine's adaptive rounds turn the smallest change
of order into another schedule (PERF.md section 2): a mix that states
``order_seed`` runs ONE schedule, so that two runs differ by what the system
did and not by what they were asked.

Parameters (``traffic/<mix>.json``):
  clients, requests_per_client, questions_per_document
  document, question, answer   {"min", "max"}: uniform lengths
  order_seed                   optional: draws the order of the lengths
"""

import numpy as np


def _uniform_lengths(spec, n):
    u = (np.arange(n) + 0.5) / n
    return np.rint(spec["min"] + u * (spec["max"] - spec["min"])
                   ).astype(np.int64)


def plan(params, seed, seconds, vocab_size):
    rng = np.random.default_rng(seed)
    order = (np.random.default_rng(params["order_seed"])
             if "order_seed" in params else rng)
    clients, per = params["clients"], params["requests_per_client"]
    every = params["questions_per_document"]
    n = clients * per
    takes_new = [[i > 0 and (i + c) % every == 0 for i in range(per)]
                 for c in range(clients)]
    n_docs = clients + sum(map(sum, takes_new))
    doc_lens = list(order.permutation(_uniform_lengths(params["document"],
                                                       n_docs)))
    q_lens = order.permutation(_uniform_lengths(params["question"], n))
    a_lens = order.permutation(_uniform_lengths(params["answer"], n))

    def tokens(length):
        return rng.integers(1, vocab_size, int(length), dtype=np.int32)

    streams, setup, k = [], [], 0
    for c in range(clients):
        doc = tokens(doc_lens.pop())
        setup.append({"due_s": None, "prompt": doc, "max_new": 1,
                      "tags": {"setup": True}})
        stream = []
        for i in range(per):
            new_doc = takes_new[c][i]
            if new_doc:
                doc = tokens(doc_lens.pop())
            stream.append({
                "due_s": None,
                "prompt": np.concatenate([doc, tokens(q_lens[k])]),
                "max_new": int(a_lens[k]),
                "tags": {"new_document": bool(new_doc),
                         "document_tokens": int(doc.shape[0])},
            })
            k += 1
        streams.append(stream)
    return {"mode": "closed", "clients": streams, "setup": setup}

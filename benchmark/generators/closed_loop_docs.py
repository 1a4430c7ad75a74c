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
(uniform quantiles), in an order of its own, and token ids of its own.

Parameters (``traffic/<mix>.json``):
  clients, requests_per_client, questions_per_document
  document, question, answer   {"min", "max"}: uniform lengths
"""

import numpy as np


def _uniform_lengths(spec, n):
    u = (np.arange(n) + 0.5) / n
    return np.rint(spec["min"] + u * (spec["max"] - spec["min"])
                   ).astype(np.int64)


def plan(params, seed, seconds, vocab_size):
    rng = np.random.default_rng(seed)
    clients, per = params["clients"], params["requests_per_client"]
    every = params["questions_per_document"]
    n = clients * per
    takes_new = [[i > 0 and (i + c) % every == 0 for i in range(per)]
                 for c in range(clients)]
    n_docs = clients + sum(map(sum, takes_new))
    doc_lens = list(rng.permutation(_uniform_lengths(params["document"],
                                                     n_docs)))
    q_lens = rng.permutation(_uniform_lengths(params["question"], n))
    a_lens = rng.permutation(_uniform_lengths(params["answer"], n))

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

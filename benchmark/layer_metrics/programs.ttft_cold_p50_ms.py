"""Client clock, send to first token, median over the window's requests that
brought a new document: 2k-6k tokens of chunked prefill between the decode
rounds of the other clients."""

from benchmark.lib import stats


def read(run):
    values = [r["first"] - r["sent"] for r in run["records"]
              if r["first"] is not None
              and r["request"]["tags"].get("new_document") is True]
    return 1e3 * stats.percentile(values, 0.5) if values else None

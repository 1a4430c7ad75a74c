"""Client clock, send to first token, median over the window's requests
whose document was already resident (asked about before)."""

from benchmark.lib import stats


def read(run):
    values = [r["first"] - r["sent"] for r in run["records"]
              if r["first"] is not None
              and r["request"]["tags"].get("new_document") is False]
    return 1e3 * stats.percentile(values, 0.5) if values else None

"""How late the generator sent requests (sent - due), 95th percentile: a
starved generator must not read as a fast server."""

from benchmark.lib import stats


def read(run):
    late = [r["sent"] - r["due"] for r in stats.counted(run)
            if r["due"] is not None]
    return 1e3 * stats.percentile(late, 0.95) if late else None

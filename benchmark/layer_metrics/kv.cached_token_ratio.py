"""Share of the window's prompt tokens that were served from shared pages
and not computed (``stats()`` cached_prompt_tokens / prompt_tokens, as
deltas), in percent."""

from benchmark.lib import stats


def read(run):
    prompt = stats.delta(run, "prompt_tokens")
    if not prompt:
        return None
    return 100.0 * stats.delta(run, "cached_prompt_tokens") / prompt

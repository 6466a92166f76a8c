"""Prompt tokens served from the prefix cache, in percent of all prompt
tokens admitted over the run: delta prefix_tokens_reused /
(delta prefill_tokens + delta prefix_tokens_reused)."""


def read(facts):
    if facts["kind"] != "serve":
        return None
    a, b = facts["after"]["engine"], facts["before"]["engine"]
    reused = a["prefix_tokens_reused"] - b["prefix_tokens_reused"]
    computed = a["prefill_tokens"] - b["prefill_tokens"]
    if reused + computed <= 0:
        return None
    return 100.0 * reused / (reused + computed)

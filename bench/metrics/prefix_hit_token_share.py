"""Prefix cache: prompt tokens served from cached pages (the engine's
``prefix.tokens_matched``) over the prompt tokens admitted in the
window."""


def read(run):
    total = run.admitted_prompt_tokens()
    if not total:
        return None
    return 100.0 * run.counters["prefix.tokens_matched"] / total

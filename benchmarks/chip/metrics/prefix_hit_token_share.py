"""Share of the prompt tokens admitted in the window that the KV manager
served from cached prefix pages (kv.stats() prefix_hit_tokens), in %."""


def read(run):
    if run.prompt_tokens <= 0:
        return None
    return 100.0 * run.kv_delta["prefix_hit_tokens"] / run.prompt_tokens

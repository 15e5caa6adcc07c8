"""Output tokens emitted inside the window over the window's seconds."""


def read(run):
    return run.out_tokens / run.elapsed if run.elapsed > 0 else None

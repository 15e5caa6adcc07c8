"""Output tokens emitted in the window per launch the engine's
DoorbellTracker counted."""


def read(run):
    return run.out_tokens / run.doorbells if run.doorbells else None

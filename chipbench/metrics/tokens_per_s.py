"""Output tokens completed in the window over the window."""


def read(run):
    if not run.tokens:
        return None
    return run.tokens / run.window_s

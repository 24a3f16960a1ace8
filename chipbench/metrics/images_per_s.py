"""Images whose logits reached the host in the window over the window."""


def read(run):
    if not run.images:
        return None
    return run.images / run.window_s

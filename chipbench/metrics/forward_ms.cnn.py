"""Mean device time of one forward program (the harness's
``cnn_forward`` jit of ``models/cnn.forward``) in the traced window."""

PROGRAM = "cnn_forward"


def read(run):
    if run.trace is None:
        return None
    n, t = run.trace.module_time(PROGRAM)
    return 1e3 * t / n if n else None

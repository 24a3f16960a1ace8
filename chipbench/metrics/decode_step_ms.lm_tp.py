"""Mean device time of one decode-step program (the engine's meshed
``decode_greedy`` jit) in the traced window, per chip: the trace holds
every chip's runs of the program."""

PROGRAM = "decode_greedy"


def read(run):
    if run.trace is None:
        return None
    n, t = run.trace.module_time(PROGRAM)
    return 1e3 * t / n if n else None

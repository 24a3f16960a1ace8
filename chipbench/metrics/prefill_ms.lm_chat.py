"""Mean device time of one admission-prefill program (the engine's
``prefill_slot`` jit) in the traced window."""

PROGRAM = "prefill_slot"


def read(run):
    if run.trace is None:
        return None
    n, t = run.trace.module_time(PROGRAM)
    return 1e3 * t / n if n else None

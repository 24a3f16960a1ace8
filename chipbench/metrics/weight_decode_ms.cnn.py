"""Device time of weight decode in one CNN forward: the ops of the
harness's ``cnn_forward`` program under the program's
``elp_bsd_decode`` scope (codes unpacked and decoded to float32
weights, scaled and laid out, outside any kernel), over the runs of
that program in the traced window.

An op counts under the scope of its fusion's root: a decode that XLA
fuses into a convolution's or a dot's operand counts as that
convolution or dot, not here.
"""
from harness.scopes import per_run_ms

PROGRAM = "cnn_forward"
SCOPE = "elp_bsd_decode"


def read(run):
    return per_run_ms(run, PROGRAM, SCOPE)

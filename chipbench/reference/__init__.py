"""Plain references of each kind of model the benchmark runs.

They import nothing of the program: they read the configuration file
and the neutral weights of :mod:`harness.weights`, decode the codes
with their own table, and compute in float32 at the highest matmul
precision. ``mode="fp8"`` is the control: every matmul operand rounded
to float8 e4m3 with a per-tensor scale, the step below the program's
bf16 datapath that a later change could be tempted to take.
"""

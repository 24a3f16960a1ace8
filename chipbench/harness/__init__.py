"""The chip benchmark's harness: everything a cell's run shares.

``run.py`` names a cell; :mod:`harness.spec` finds its configuration,
its traffic and its metric readers by the names in ``BENCHMARK.json``;
a driver (:mod:`harness.lm_serve`, :mod:`harness.cnn_forward`) makes the
weights from the seed, warms up, measures one window and checks what
the window produced against the plain reference under ``reference/``.
"""

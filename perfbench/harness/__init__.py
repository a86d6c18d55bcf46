"""The chip benchmark's harness: deployment and traffic from data files,
the measured window over the served path, the plain references that decide
``correct``, and the reductions from spans and device traces to metrics.

Nothing here is imported by the program, and nothing of the program is
imported by :mod:`perfbench.harness.reference`."""
